"""The benchmark's files: found by name, consistent with BENCHMARK.json,
and extended by adding files alone."""

import json
import os
import re

import numpy as np
import pytest

from chipbench import bench
from chipbench.drivers import ckpt

from conftest import ROOT

BASE = os.path.join(ROOT, "chipbench")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _names(sub):
    return sorted(f[:-len(".json")] if f.endswith(".json") else f[:-len(".py")]
                  for f in os.listdir(os.path.join(BASE, sub))
                  if f.endswith((".json", ".py")))


@pytest.mark.parametrize("cell", _names("workloads"))
def test_cell_found_by_name(cell):
    """Every cell file resolves; one that BENCHMARK.json lists agrees with
    its entry and reports an end-to-end metric beside ``setup_s``."""
    c = bench.load_cell(cell, BASE)
    assert os.path.exists(os.path.join(BASE, "drivers", c["traffic"]["driver"] + ".py"))
    assert bench.family(c).program_model(c["config"]) is not None
    entry = next((w for w in SPEC["workloads"] if w["name"] == cell), None)
    if entry is None:
        return
    assert c["workload"]["config"] == entry["config"]
    assert c["workload"]["traffic"] == entry["traffic"]
    assert c["chips"] == entry["chips"]
    e2e, per = bench.cell_metrics(SPEC, cell)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2 and per
    assert all(m["moves"] in names for m in per)


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_holds_what_runs(cfg):
    with open(os.path.join(ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert body["name"] == cfg["name"] and body["source"] == cfg["source"]
    assert sorted(body["reduced"]) == sorted(cfg["reduced"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) for k in cfg["reduced"])


@pytest.mark.parametrize("metric", sorted(set(_names("metrics"))
                                           | {m["name"] for m in SPEC["per_layer"]}))
def test_metric_reader_found_by_name(metric):
    reader = bench.metric_reader(metric, BASE)
    assert reader.read({"out": {}, "spans": bench.Spans(), "chips": 1,
                        "peak": {"bf16_flops": 1.0}, "cell": {}}) is None


def test_names_and_bounds_keep_the_contract():
    things = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] + SPEC["per_layer"]
    assert all(NAME.match(t["name"]) for t in things)
    assert len({t["name"] for t in things}) == len(things)
    assert all(0.01 <= m["bound"] <= 0.25 for m in SPEC["end_to_end"])
    assert all(len(w["why"]) <= 200 for w in SPEC["workloads"] + SPEC["configs"])
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(SPEC["workloads"]) // 2)


def test_added_workload_file_is_picked_up(tmp_path):
    from conftest import make_tiny_base
    base = make_tiny_base(str(tmp_path))
    cell = bench.load_cell("tiny.train", base)
    assert cell["config"]["name"] == "rwkv6-tiny"
    assert cell["traffic"]["driver"] == "train"
    assert bench.driver(cell).run is not None
    assert bench.family(cell).flops_per_token(cell["config"]) > 0


def test_peaks_refuse_an_unlisted_device():
    assert bench.peaks("TPU v5 lite", BASE)["bf16_flops"] == 197e12
    with pytest.raises(bench.NoChip):
        bench.peaks("TPU v4", BASE)


def test_rwkv6_flops_match_a_hand_count():
    fam = bench.family(bench.load_cell("rwkv6_4l.ckpt", BASE))
    with open(os.path.join(BASE, "configs", "rwkv6-1.6b-4l.json")) as f:
        cfg = json.load(f)
    d, f_, V, r, H, D = 2048, 7168, 65536, 64, 32, 64
    # time mix r, k, v, g, out and the decay LoRA; channel mix key, value,
    # receptance; the head.  Multiply-adds are 2 FLOPs, backward twice forward
    per_layer = 5 * d * d + 2 * d * r + 2 * d * f_ + d * d
    dense = 3 * 2 * (4 * per_layer + d * V)
    # per head and token: k^T v and r . S, D x D multiply-adds each
    wkv = 3 * 4 * H * (2 * D * D + 2 * D * D)
    assert fam.flops_per_token(cfg) == dense + wkv
    assert abs(fam.flops_per_token(cfg) / 1e9 - 2.1265) < 1e-3


def _state_shapes(L):
    shape = {"embed": ((512, 64), 4), "layers.w": ((L, 64, 224), 4),
             "layers.b": ((L, 64), 4), "norm": ((64,), 4)}
    return {t: dict(shape) for t in ("params", "opt.m", "opt.v")}


@pytest.mark.parametrize("block_bytes", [4096, 65536])
def test_block_order_is_fixed_and_covers_the_state(block_bytes):
    traffic = {"block_bytes": block_bytes, "trees": ["params", "opt.m", "opt.v"]}
    shapes = _state_shapes(3)
    plan = ckpt.block_plan(shapes, traffic)
    assert plan == ckpt.block_plan(shapes, dict(traffic))
    k = len(plan)
    rows = {leaf: (int(np.prod(shape[:-1])) if len(shape) > 1 else 1, shape[-1] * item)
            for leaf, (shape, item) in shapes["params"].items()}
    total = 3 * sum(r * rb for r, rb in rows.values())
    assert total <= k * block_bytes and (k == 1 or total > k // 2 * block_bytes)
    for leaf, (r, rb) in rows.items():
        got = [(r0, n) for b in plan for lf, r0, n in b if lf == leaf]
        # every row once, in order
        assert got[0][0] == 0 and sum(n for _, n in got) == r
        assert all(a[0] + a[1] == b[0] for a, b in zip(got, got[1:]))
        # every block holds a like share of every leaf
        per_block = [sum(n for lf, _, n in b if lf == leaf) for b in plan]
        assert set(per_block) <= {r // k, -(-r // k)}
    slack = 3 * sum(rb for _, rb in rows.values())
    for b in plan:
        assert 3 * sum(n * rows[lf][1] for lf, _, n in b) <= total / k + slack
