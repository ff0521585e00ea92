"""The trace reduction on a small recorded trace with known busy, idle and
collective time."""

import json
import os

import pytest

from chipbench import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6


def _synthetic():
    dev = "/device:TPU:0"
    ev = [
        ("/host:CPU", "python", "chipbench.trace_window", 0.0, 100 * MS),
        ("/host:CPU", "python", "chipbench.pipeline.wait", 40 * MS, 20 * MS),
        (dev, "XLA Ops", "fusion.1", 0.0, 30 * MS),
        (dev, "XLA Ops", "all-gather-start", 25 * MS, 10 * MS),   # 5 ms exposed
        (dev, "XLA Ops", "fusion.2", 70 * MS, 20 * MS),
        (dev, "XLA Ops", "reduce-scatter.3", 85 * MS, 10 * MS),   # 5 ms exposed
        (dev, "XLA Modules", "jit_step", 0.0, 95 * MS),           # not an op line
        ("/device:TPU:1", "XLA Ops", "fusion.9", 0.0, 100 * MS),  # another chip
    ]
    return ev


def test_busy_idle_and_collectives_of_a_known_trace():
    r = tracing.reduce(_synthetic(), [0])
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.06)       # 0-35, 70-95 ms
    assert r["collective_s"] == pytest.approx(0.02)
    assert r["collective_exposed_s"] == pytest.approx(0.01)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["pipeline.wait", pytest.approx(0.035)]   # 35-70 ms
    assert gaps[1] == ["no benchmark span", pytest.approx(0.005)]
    assert r["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(0.03)]


def test_chips_are_averaged():
    r = tracing.reduce(_synthetic(), [0, 1])
    assert r["busy_s"] == pytest.approx((0.06 + 0.1) / 2)


def test_recorded_tpu_trace():
    path = os.path.join(HERE, "data", "trace_v5e.json")
    with open(path) as f:
        rec = json.load(f)
    r = tracing.reduce([tuple(e) for e in rec["events"]], [0])
    assert r["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(rec["expect"]["window_s"], rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["breakdown"]["idle_gaps"][0][0] == "host_sleep"
