"""Sharding rule engine: pure-logic tests (no multi-device needed — rules
are computed from specs and mesh shapes)."""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced
from repro.models import Model
from repro.models.specs import ParamSpec
from repro.parallel import ParallelismConfig, logical_to_pspec
from repro.parallel.sharding import abstract_mesh, dp_spec


@pytest.fixture(scope="module")
def mesh():
    # single real device is fine: rules only read mesh SHAPE
    return abstract_mesh((16, 16), ("data", "model"))


def test_tp_divisible_dims_shard(mesh):
    pc = ParallelismConfig(zero3=False)
    sp = ParamSpec((4096, 32, 128), ("embed", "heads", "head_dim"))
    assert logical_to_pspec(sp, mesh, pc) == P(None, "model", None)
    sp = ParamSpec((4096, 12288), ("embed", "ff"))
    assert logical_to_pspec(sp, mesh, pc) == P(None, "model")
    sp = ParamSpec((151936, 4096), ("vocab", "embed"))
    assert logical_to_pspec(sp, mesh, pc) == P("model", None)


def test_non_divisible_falls_back(mesh):
    pc = ParallelismConfig(zero3=False)
    sp = ParamSpec((5120, 40, 128), ("embed", "heads", "head_dim"))
    # 40 % 16 != 0 -> no TP on heads
    assert logical_to_pspec(sp, mesh, pc) == P(None, None, None)
    sp = ParamSpec((5120, 8, 128), ("embed", "kv_heads", "head_dim"))
    assert logical_to_pspec(sp, mesh, pc) == P(None, None, None)


def test_zero3_shards_largest_divisible(mesh):
    pc = ParallelismConfig(zero3=True)
    sp = ParamSpec((5120, 40, 128), ("embed", "heads", "head_dim"))
    assert logical_to_pspec(sp, mesh, pc) == P("data", None, None)


def test_experts_fsdp(mesh):
    pc = ParallelismConfig()
    sp = ParamSpec((128, 5120, 8192), ("experts", "embed", "ff"))
    assert logical_to_pspec(sp, mesh, pc) == P("data", None, "model")


def test_each_mesh_axis_used_once(mesh):
    pc = ParallelismConfig(zero3=True)
    for arch in ("qwen3-8b", "llama4-maverick-400b-a17b", "jamba2-mini"):
        model = Model(get_config(arch))
        from repro.models.specs import tree_paths
        for path, spec in tree_paths(model.param_specs()).items():
            ps = logical_to_pspec(spec, mesh, pc)
            used = [e for e in ps if e is not None]
            assert len(used) == len(set(used)), (arch, path, ps)
            # divisibility holds wherever an axis was assigned
            for dim, ax in zip(spec.shape, tuple(ps) + (None,) * 9):
                if ax:
                    assert dim % mesh.shape[ax] == 0, (arch, path, ps)


def test_dp_spec_divisibility():
    mesh = abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    assert dp_spec(mesh, 256) == ("pod", "data")
    assert dp_spec(mesh, 1) is None
    assert dp_spec(mesh, 13) is None
    single = abstract_mesh((16, 16), ("data", "model"))
    assert dp_spec(single, 128) == "data"
