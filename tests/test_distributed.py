"""Distributed lowering invariants, run in subprocesses so the fake-device
XLA flag never leaks into this process (smoke tests must see 1 device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_tiny_train_step_sharded_end_to_end():
    """A reduced arch trains ONE REAL step on a 4x2 mesh and the loss is
    finite — exercising param/opt/batch shardings with actual data."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced
        from repro.models import Model
        from repro.train import init_train_state, make_train_step
        from repro.parallel import ParallelismConfig, param_shardings, opt_shardings, batch_shardings
        from repro.parallel.actctx import activation_context
        from repro.train.step import TrainState
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(data=4, model=2)
        cfg = reduced(get_config("qwen3-8b"))
        model = Model(cfg)
        pcfg = ParallelismConfig(zero3=True)
        state = init_train_state(model, jax.random.key(0))
        psh = param_shardings(model, mesh, pcfg)
        osh = opt_shardings(model, mesh, pcfg)
        rep = NamedSharding(mesh, P())
        ssh = TrainState(params=psh, opt={"m": osh, "v": osh, "count": rep}, step=rep, err=None)
        tok = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab)
        batch = {"tokens": tok, "targets": jnp.roll(tok, -1, 1)}
        bsh = batch_shardings(mesh, batch)
        step = make_train_step(model, peak_lr=1e-3)
        with mesh, activation_context(mesh):
            f = jax.jit(step, in_shardings=(ssh, bsh), out_shardings=(ssh, rep), donate_argnums=(0,))
            state2, m = f(state, batch)
        assert np.isfinite(float(m["loss"]))
        print("LOSS", float(m["loss"]))
    """)
    assert "LOSS" in out


@pytest.mark.slow
def test_decode_cache_time_sharding_flash_pattern():
    """Time-sharded KV cache decode emits only small all-reduces (the
    flash-decode pattern) and never gathers the cache."""
    out = _run("""
        import jax, jax.numpy as jnp, re, json
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced, ShapeSpec
        from repro.models import Model
        from repro.parallel import ParallelismConfig, param_shardings, cache_shardings
        from repro.parallel.actctx import activation_context
        import dataclasses
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(data=2, model=4)
        cfg = dataclasses.replace(reduced(get_config("qwen3-8b")), n_kv_heads=2, n_heads=4)
        # kv=2 < model=4 -> time sharding kicks in
        model = Model(cfg)
        pcfg = ParallelismConfig()
        params = model.abstract(dtype=jnp.bfloat16)
        psh = param_shardings(model, mesh, pcfg)
        cache = model.init_cache(8, 64, abstract=True)
        csh = cache_shardings(model, mesh, pcfg, cache)
        # verify the time dim got the model axis
        leaf_sh = jax.tree.leaves(csh)[0]
        assert "model" in str(leaf_sh.spec[2]), leaf_sh.spec
        tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
        rep = NamedSharding(mesh, P())
        with mesh, activation_context(mesh):
            c = jax.jit(model.decode_step,
                        in_shardings=(psh, csh, NamedSharding(mesh, P("data", None)), rep),
                        out_shardings=(NamedSharding(mesh, P("data", None)), csh),
                        donate_argnums=(1,)).lower(
                params, cache, tok, jax.ShapeDtypeStruct((), jnp.int32)).compile()
        txt = c.as_text()
        ags = [l for l in txt.splitlines() if "all-gather(" in l and "bf16" in l]
        # no all-gather of a (*, 64, kv, dh)-sized cache tensor
        big = [l for l in ags if ",64," in l.split("all-gather")[0]]
        print("BIGGATHERS", len(big))
    """)
    assert "BIGGATHERS 0" in out


@pytest.mark.slow
def test_multipod_mesh_lowering():
    """The 3-axis (pod, data, model) mesh lowers a reduced train step —
    the same code path the 512-chip dry-run uses."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced, SHAPES, ShapeSpec
        from repro.launch.specs import build_cell, parallelism_for
        from repro.parallel.actctx import activation_context
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = reduced(get_config("gemma2-9b"))
        shape = ShapeSpec("t", 64, 8, "train")
        cell = build_cell(cfg, shape, mesh, parallelism_for(cfg))
        with mesh, activation_context(mesh):
            c = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                        out_shardings=cell.out_shardings,
                        donate_argnums=cell.donate_argnums).lower(*cell.args).compile()
        print("MEM", c.memory_analysis().temp_size_in_bytes > 0)
    """)
    assert "MEM True" in out


@pytest.mark.slow
def test_elastic_restore_across_meshes():
    """Save on a 2-device mesh, restore onto a 8-device mesh (re-shard)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile, os
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_pytree, load_pytree
        from repro.launch.mesh import make_host_mesh
        m2 = make_host_mesh(data=2, model=1)
        tree = {"w": jax.device_put(jnp.arange(128.0).reshape(16, 8),
                                    NamedSharding(m2, P("data", None)))}
        td = tempfile.mkdtemp()
        save_pytree(os.path.join(td, "c.bskt"), tree)
        m8 = make_host_mesh(data=4, model=2)
        sh = {"w": NamedSharding(m8, P("data", "model"))}
        got, _ = load_pytree(os.path.join(td, "c.bskt"), template=tree, shardings=sh)
        assert got["w"].sharding == sh["w"]
        np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
        print("ELASTIC OK")
    """)
    assert "ELASTIC OK" in out
