"""The weights driver on the CPU at a tiny Jamba size: a sound run is
correct, an altered restore and the float8 control are not, and on four
devices every piece lands where the serving layout keeps its rows."""

import json
import os
import subprocess
import sys

import jax
import pytest

from chipbench import bench
from chipbench.drivers import weights

from conftest import HERE, ROOT, make_tiny_base

SECONDS = 0.5
TINY_WEIGHTS = {"driver": "weights", "block_bytes": 65536, "trees": ["params"],
                "prompts": 2, "prompt_len": 16, "decode_steps": 4}
LIMITS = {"bits_differing": 0, "blocks_failed": 0, "logits_gap": 0.03}


def make_weights_base(dest: str, chips: int = 1) -> str:
    """The tiny benchmark files plus the cell ``tiny.weights``: the tiny
    Jamba in bfloat16 on a ``model=chips`` mesh."""
    base = make_tiny_base(dest)
    with open(os.path.join(HERE, "data", "jamba_tiny.json")) as f:
        cfg = json.load(f)
    cfg["mesh"] = {"data": 1, "model": chips}
    for sub, name, obj in [
            ("configs", "jamba-tiny", cfg), ("traffic", "tiny_weights", TINY_WEIGHTS),
            ("workloads", "tiny.weights", {"config": "jamba-tiny",
                                           "traffic": "tiny_weights",
                                           "chips": chips, "limits": LIMITS})]:
        with open(os.path.join(base, sub, f"{name}.json"), "w") as f:
            json.dump(obj, f)
    return base


@pytest.fixture(scope="module")
def weights_base(tmp_path_factory):
    return make_weights_base(str(tmp_path_factory.mktemp("weights")))


def _run(base, devices):
    cell = bench.load_cell("tiny.weights", base)
    out = bench.driver(cell).run(cell, 2 ** 33 + 7, SECONDS, False, bench.Spans(),
                                 devices, 0.0)
    return out, bench.compared(out["checks"], cell["limits"])


def test_sound_run_is_correct(weights_base):
    out, checks = _run(weights_base, jax.devices()[:1])
    assert bench.judge(checks) and out["failed"] == 0, checks
    assert out["attempted"] > 0 and out["raw_bytes"] > 0
    assert 0 < checks["logits_gap"]["value"] < LIMITS["logits_gap"]
    assert out["ckpt_save_MBps"] > 0 and out["ckpt_restore_MBps"] > 0
    assert out["ckpt_stored_per_raw"] > 0       # tiny blocks: the TOC outweighs them


def test_altered_restore_is_caught(weights_base, monkeypatch):
    from repro.checkpoint import CheckpointManager
    orig = CheckpointManager.restore

    def altered(self, *a, **k):
        tree, meta = orig(self, *a, **k)
        first = sorted(tree)[0]
        tree[first] = tree[first].at[(0,) * tree[first].ndim].add(1.0)
        return tree, meta

    monkeypatch.setattr(CheckpointManager, "restore", altered)
    out, checks = _run(weights_base, jax.devices()[:1])
    assert checks["bits_differing"]["value"] > 0
    assert not bench.judge(checks) and out["failed"] > 0


def test_float8_control_fails_the_limit(weights_base):
    from chipbench import control
    cell = bench.load_cell("tiny.weights", weights_base)
    r = weights.control_readings(cell, 11, jax.devices()[:1])
    judged = control.judged(r, cell["limits"])["control_fp8"]
    assert judged["checks"]["logits_gap"]["value"] > LIMITS["logits_gap"]
    assert not judged["correct"]


FOUR = """
import sys
sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
import jax
import numpy as np
from chipbench import bench
from chipbench.drivers import weights
from test_chipbench_weights import make_weights_base
base = make_weights_base({dest!r}, chips=4)
cell = bench.load_cell("tiny.weights", base)
model, mesh, psh, params = weights.setup_weights(cell, 5, jax.devices())
leaves, specs = weights.ckpt._leaves(params), weights.ckpt._leaves(psh)
plan = weights.ckpt.block_plan({{"params": {{k: (x.shape, 2) for k, x in leaves.items()}}}},
                               cell["traffic"])
kinds = set()
for block in plan:
    for leaf, r0, n in block:
        lay = weights.piece_layout(leaves[leaf].shape, specs[leaf].spec, mesh, r0, n)
        x = weights.make_piece(leaves[leaf], lay, r0, n)
        want = leaves[leaf].reshape(-1, leaves[leaf].shape[-1])[r0:r0 + n]
        assert (np.asarray(x).reshape(want.shape) == np.asarray(want)).all(), leaf
        assert x.sharding == lay[1], (leaf, x.sharding, lay[1])
        kinds.add("one" if lay[2] else ("split" if len(x.sharding.device_set) == 4
                                        and x.addressable_shards[0].data.size < x.size
                                        else "copies"))
assert kinds == {{"one", "split", "copies"}}, kinds
out = bench.driver(cell).run(cell, 9, 0.5, False, bench.Spans(), jax.devices(), 0.0)
checks = bench.compared(out["checks"], cell["limits"])
assert bench.judge(checks) and out["failed"] == 0 and out["attempted"] > 0, checks

# the benchmark's own cell: every piece of its plan has a place in the
# layout, from shapes alone
cell = bench.load_cell("jamba2mini_8l.weights_4chip", {root!r} + "/chipbench")
model = bench.family(cell).program_model(cell["config"])
mesh, psh = weights.serving_layout(model, 4)
shapes = {{k: x.shape for k, x in weights.ckpt._leaves(model.abstract()).items()}}
specs = {{k: s.spec for k, s in weights.ckpt._leaves(psh).items()}}
plan = weights.ckpt.block_plan({{"params": {{k: (s, 2) for k, s in shapes.items()}}}},
                               cell["traffic"])
assert len(plan) == 1024, len(plan)
one = sum(1 for b in plan for leaf, r0, n in b
          if weights.piece_layout(shapes[leaf], specs[leaf], mesh, r0, n)[2])
print("FOUR OK", checks, one)
"""


def test_pieces_land_where_the_four_device_layout_keeps_them(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    code = FOUR.format(root=ROOT, src=os.path.join(ROOT, "src"), tests=HERE,
                       dest=str(tmp_path))
    p = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path), env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0 and "FOUR OK" in p.stdout, p.stderr[-3000:]
