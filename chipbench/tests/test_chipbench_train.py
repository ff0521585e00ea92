"""The train driver on the CPU at a tiny size: a sound run is correct, and
each fault planted under the timed path, and the control, are not."""

import dataclasses

import jax
import pytest

from chipbench import bench, control, system

SECONDS = 0.5


def _run(base, seed=3000000019):
    cell = bench.load_cell("tiny.train", base)
    out = bench.driver(cell).run(cell, seed, SECONDS, False, bench.Spans(),
                                 jax.devices()[:1], 0.0)
    checks = bench.compared(out["checks"], cell["limits"])
    return out, checks


def test_sound_run_is_correct(tiny_base):
    out, checks = _run(tiny_base)
    assert bench.judge(checks), checks
    assert out["failed"] == 0 and out["steps"] > 0
    assert out["train_tokens_per_s"] > 0 and out["setup_s"] > 0


def _wrap_step(monkeypatch, wrap):
    orig = system.make_step

    def broken(model, opt):
        return wrap(orig(model, opt))

    monkeypatch.setattr(system, "make_step", broken)


def test_state_left_unchanged_is_caught(tiny_base, monkeypatch):
    def wrap(step):
        def f(state, batch):
            return state, step(state, batch)[1]
        return f

    _wrap_step(monkeypatch, wrap)
    _, checks = _run(tiny_base)
    assert not bench.judge(checks)
    assert checks["change_norm_gap"]["value"] == pytest.approx(1.0, rel=1e-3)


def test_update_of_the_least_leaf_dropped_is_caught(tiny_base, monkeypatch):
    def wrap(step):
        def f(state, batch):
            new, metrics = step(state, batch)
            params = dict(new.params, final_norm=state.params["final_norm"])
            return dataclasses.replace(new, params=params), metrics
        return f

    _wrap_step(monkeypatch, wrap)
    _, checks = _run(tiny_base)
    assert not bench.judge(checks)
    assert checks["change_norm_gap"]["value"] == pytest.approx(1.0, rel=1e-3)


def test_half_the_batch_left_out_is_caught(tiny_base, monkeypatch):
    def wrap(step):
        def f(state, batch):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(state, half)
        return f

    _wrap_step(monkeypatch, wrap)
    _, checks = _run(tiny_base)
    assert not bench.judge(checks)


def test_token_altered_in_the_feed_is_caught(tiny_base, monkeypatch):
    orig = system.open_pipeline

    class Altered:
        def __init__(self, pipe):
            self.pipe = pipe

        def __next__(self):
            raw = next(self.pipe)
            raw["tokens"][0, 7] = (raw["tokens"][0, 7] + 1) % 512
            return raw

        def close(self):
            self.pipe.close()

    monkeypatch.setattr(system, "open_pipeline",
                        lambda *a, **k: Altered(orig(*a, **k)))
    _, checks = _run(tiny_base)
    assert checks["rows_not_in_stream"]["value"] > 0
    assert not bench.judge(checks)


def test_control_and_faults_fail_the_limits(tiny_base):
    cell = bench.load_cell("tiny.train", tiny_base)
    judged = control.judged(control.train_readings(cell, 7, jax.devices()[:1]), cell["limits"])
    assert set(judged) == {"control_fp8", "half_batch", "state_unchanged",
                           "least_leaf_unmoved", "least_leaf_doubled"}
    for name, j in judged.items():
        assert not j["correct"], (name, j["checks"])


SHARDED = """
import json, os, sys
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src"), {tests!r}]
import jax
import conftest
from chipbench import bench
base = conftest.make_tiny_base(sys.argv[1])
path = os.path.join(base, "configs", "rwkv6-tiny.json")
with open(path) as f:
    cfg = json.load(f)
cfg.update(mesh={{"data": 2, "model": 2}}, zero3=True)
with open(path, "w") as f:
    json.dump(cfg, f)
cell = bench.load_cell("tiny.train", base)
out = bench.driver(cell).run(cell, 99, 0.5, False, bench.Spans(), jax.devices()[:4], 0.0)
print(json.dumps(bench.compared(out["checks"], cell["limits"])))
"""


def test_sharded_step_on_four_virtual_chips(tmp_path):
    """The ZeRO-3 (data=2, model=2) path of the train driver, on four CPU
    devices in a process of its own."""
    import json
    import os
    import subprocess
    import sys
    from conftest import HERE, ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SHARDED.format(root=ROOT, tests=HERE),
                        str(tmp_path)], env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    checks = json.loads(p.stdout.strip().splitlines()[-1])
    assert bench.judge(checks), checks
