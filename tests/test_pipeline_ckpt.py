"""Data pipeline + checkpoint fault-tolerance invariants."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, save_pytree, load_pytree
from repro.core.bfile import BasketFile
from repro.data import TokenPipeline, write_token_shards, make_events, write_event_file
from repro.models import Model, ModelConfig
from repro.train import init_train_state


@pytest.fixture(scope="module")
def shards(tmp_path_factory):
    td = tmp_path_factory.mktemp("shards")
    paths = [str(td / f"s{i}.bskt") for i in range(3)]
    write_token_shards(paths, vocab=512, tokens_per_shard=20_000, seed=1)
    return paths


def test_pipeline_deterministic(shards):
    a = TokenPipeline(shards, batch=4, seq_len=64, seed=5)
    b = TokenPipeline(shards, batch=4, seq_len=64, seed=5)
    for _ in range(4):
        np.testing.assert_array_equal(next(a)["tokens"], next(b)["tokens"])
    a.close(); b.close()


def test_pipeline_restart_exact(shards):
    p = TokenPipeline(shards, batch=4, seq_len=64, seed=5)
    for _ in range(5):
        next(p)
    st = p.state_dict()
    nxt = next(p)["tokens"]
    p.close()
    q = TokenPipeline(shards, batch=4, seq_len=64, seed=5)
    q.load_state_dict(st)
    np.testing.assert_array_equal(next(q)["tokens"], nxt)
    q.close()


def test_pipeline_host_disjoint(shards):
    mine = [TokenPipeline(shards, batch=2, seq_len=32, host_id=h, n_hosts=3).my_paths
            for h in range(3)]
    assert not (set(mine[0]) & set(mine[1]))
    assert set(mine[0]) | set(mine[1]) | set(mine[2]) == set(shards)


def test_pipeline_targets_shifted(shards):
    p = TokenPipeline(shards, batch=2, seq_len=32)
    b = next(p)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["targets"][:, :-1])
    p.close()


def test_event_file_fig6_structure(tmp_path, rng):
    ev = write_event_file(str(tmp_path / "e.bskt"), n_events=500, seed=2)
    f = BasketFile(str(tmp_path / "e.bskt"))
    assert np.all(np.diff(ev["Jet_offsets"]) >= 0)
    # the offsets branch must compress far better than the float branches
    assert f.compression_ratio("Jet_offsets") > 3 * f.compression_ratio("Jet_pt")


# ---------------------------------------------------------------------------
# checkpoints
# ---------------------------------------------------------------------------

def _state_tree():
    cfg = ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                      d_head=16, d_ff=64, vocab=64)
    m = Model(cfg)
    st = init_train_state(m, jax.random.key(0))
    return {"params": st.params, "opt": st.opt, "step": st.step, "err": st.err}


def test_save_restore_exact(tmp_path):
    tree = _state_tree()
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(5, tree, extra_meta={"data_cursor": {"epoch": 1, "file_idx": 2}},
             wait=True)
    got, meta = mgr.restore(template=tree)
    assert meta["data_cursor"]["file_idx"] == 2
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_roundtrip(tmp_path):
    tree = {"w": jnp.asarray([1.5, -2.25, 3.0], jnp.bfloat16)}
    save_pytree(str(tmp_path / "b.bskt"), tree)
    got, _ = load_pytree(str(tmp_path / "b.bskt"), template=tree)
    assert got["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got["w"], np.float32),
                                  np.asarray(tree["w"], np.float32))


def test_retention_and_latest(tmp_path):
    tree = {"x": jnp.arange(10)}
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, tree, wait=True)
    assert mgr.steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_truncated_checkpoint_ignored(tmp_path):
    tree = {"x": jnp.arange(100)}
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, tree, wait=True)
    mgr.save(2, tree, wait=True)
    # corrupt step 2's data file (simulated crash mid-write + bad rename)
    p = mgr._data_path(2)
    blob = open(p, "rb").read()
    open(p, "wb").write(blob[: len(blob) // 2])
    got, _ = mgr.restore(step=1, template=tree)
    np.testing.assert_array_equal(np.asarray(got["x"]), np.arange(100))
    with pytest.raises(ValueError):
        mgr.restore(step=2, template=tree)


def test_elastic_reshard_device_put(tmp_path):
    """Restore with explicit shardings (single-device here; the mesh case
    is exercised in test_distributed.py)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    sh = {"w": NamedSharding(mesh, P("data", None))}
    save_pytree(str(tmp_path / "e.bskt"), tree)
    got, _ = load_pytree(str(tmp_path / "e.bskt"), template=tree, shardings=sh)
    assert got["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))


def test_checkpoint_compression_wins(tmp_path):
    tree = _state_tree()
    stats = save_pytree(str(tmp_path / "c.bskt"), tree)
    assert stats["comp"] < stats["raw"]
