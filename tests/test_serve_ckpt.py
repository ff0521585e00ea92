"""``launch/serve.py --ckpt-dir`` on four host devices: a train-style
checkpoint of tiny Jamba weights is restored into the serving mesh
(``data=1, model=4``), each leaf in its param sharding with no device
receiving more than its shard, gives the logits it gave before the save,
and serves.  Runs in a subprocess, which alone can ask XLA for four CPU
devices."""

import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import dataclasses, tempfile
    import jax, jax.numpy as jnp, numpy as np
    from repro import obs
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config, reduced
    from repro.launch import serve
    from repro.models import Model

    assert len(jax.devices()) == 4
    # float32 compute: the four-way split then moves the logits by ~1e-5
    model = Model(dataclasses.replace(reduced(get_config("jamba2-mini")),
                                      dtype="float32"))
    params = model.init(jax.random.key(0))
    tok = jax.random.randint(jax.random.key(1), (2, 12), 0, model.cfg.vocab)
    before, _ = model.prefill(params, {"tokens": tok}, max_len=16)

    d = tempfile.mkdtemp()
    mgr = CheckpointManager(d)
    opt = jax.tree.map(jnp.zeros_like, params)      # stays on disk
    mgr.save(3, {"params": params, "opt": {"m": opt, "v": opt}}, wait=True)

    mesh, psh = serve.serving_layout(model, 4)
    h2d0 = obs.snapshot()["counters"].get("ckpt.h2d_bytes", 0)
    got = serve.restore_weights(mgr, {"params": psh})
    assert set(got) == {"params"}
    got = got["params"]
    shard_bytes = 0
    for x, s, p in zip(jax.tree.leaves(got), jax.tree.leaves(psh),
                       jax.tree.leaves(params)):
        assert x.sharding == s, (x.sharding, s)
        assert np.array_equal(np.asarray(x), np.asarray(p))
        shard_bytes += sum(sh.data.nbytes for sh in x.addressable_shards)
    h2d = obs.snapshot()["counters"]["ckpt.h2d_bytes"] - h2d0
    raw = sum(x.nbytes for x in jax.tree.leaves(got))
    assert h2d == shard_bytes and raw < h2d < 1.1 * raw, (h2d, shard_bytes, raw)
    assert sum(1 for x in jax.tree.leaves(got)
               if x.addressable_shards[0].data.size < x.size) > 20

    from repro.parallel.actctx import activation_context
    with mesh, activation_context(mesh):
        after, _ = jax.jit(lambda p, b: model.prefill(p, b, max_len=16))(
            got, {"tokens": tok})
    err = float(jnp.max(jnp.abs(after - before)) / jnp.max(jnp.abs(before)))
    assert err < 1e-4, err
    rc = serve.main(["--arch", "jamba2-mini", "--reduced", "--ckpt-dir", d,
                     "--tp", "4", "--requests", "4", "--slots", "2",
                     "--max-len", "32", "--max-new", "4"])
    assert rc == 0
    print("SERVED", err)
""")


def test_serve_restores_a_checkpoint_into_the_four_device_mesh(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    p = subprocess.run([sys.executable, "-c", SCRIPT], cwd=str(tmp_path), env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0 and "SERVED" in p.stdout, p.stderr[-3000:]
    assert "restored" in p.stdout and "4 requests" in p.stdout
