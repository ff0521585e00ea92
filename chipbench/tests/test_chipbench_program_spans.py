"""The program's checkpoint spans and basket stages, as the benchmark reads
them: on the profiler's clock in a trace, and in the tiny checkpoint
cell's per-layer metrics."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import bench, tracing
from chipbench import run as runmod

from conftest import cpu_chips

PROGRAM_METRICS = [
    "ckpt_save.snapshot_s_per_GB", "ckpt_save.precond_MBps",
    "ckpt_save.compress_MBps", "ckpt_save.io_s_per_GB",
    "ckpt_save.unattributed_s_per_GB", "ckpt_restore.io_s_per_GB",
    "ckpt_restore.decompress_MBps", "ckpt_restore.unprecond_MBps",
    "ckpt_restore.unattributed_s_per_GB"]
OURS = ("ckpt.", "basket.")


def test_profiler_trace_holds_program_spans_on_its_clock(tmp_path):
    """Under ``jax.profiler.trace`` a save, wait and restore leave the
    program's phase spans and basket stages in the ``.xplane.pb``, inside
    the stretch of the XLA runtime's own events around them."""
    from repro.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    tree = {"a": jnp.linspace(0.0, 1.0, 300_000, dtype=jnp.float32),
            "b": jnp.ones((1000, 300), jnp.float32)}
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    trace_dir = str(tmp_path / "trace")
    with jax.profiler.trace(trace_dir):
        jax.jit(lambda x: x * 3)(np.ones(17, np.float32)).block_until_ready()
        mgr.save(1, tree, snapshot=True)
        mgr.wait()
        got, _ = mgr.restore(1, template={k: 0 for k in tree},
                             shardings={k: sharding for k in tree})
        jax.block_until_ready(got)
        jax.jit(lambda x: x - 1)(np.ones(19, np.float32)).block_until_ready()
    assert all(np.array_equal(got[k], tree[k]) for k in tree)
    evs = tracing.events_from_xplane(trace_dir)
    ours = [e for e in evs if e[2].startswith(OURS)]
    names = {e[2] for e in ours}
    assert {"ckpt.snapshot", "ckpt.commit", "ckpt.read_branch"} <= names
    assert any(n.startswith("basket.stage_s{op=pack") for n in names)
    assert any(n.startswith("basket.stage_s{op=unpack") for n in names)
    runtime = [e for e in evs if e[1] != "python" and not e[2].startswith(OURS)]
    lo = min(e[3] for e in runtime)
    hi = max(e[3] + e[4] for e in runtime)
    assert all(lo <= e[3] <= hi for e in ours)


@pytest.mark.parametrize("obs_on", [True, False], ids=["obs_on", "obs_off"])
def test_tiny_ckpt_reports_the_program_metrics(tiny_root, monkeypatch, capsys,
                                               obs_on):
    """``--trace 1`` reports every metric read from the program's registry
    as a finite number; with obs off (``REPRO_OBS=off``) none of them."""
    from repro import obs

    monkeypatch.setattr(runmod, "ROOT", tiny_root)
    monkeypatch.setattr(bench, "find_chips", cpu_chips)
    monkeypatch.setattr(bench, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(bench, "peaks", lambda kind, base: {"bf16_flops": 1e12})
    prev = obs.set_enabled(obs_on)
    try:
        rc = runmod.main(["--workload", "tiny.ckpt", "--seed", "3000000019",
                          "--seconds", "0.5", "--trace", "1"])
    finally:
        obs.set_enabled(prev)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    got = {n: line["metrics"][n]["value"] for n in PROGRAM_METRICS
           if n in line["metrics"]}
    if not obs_on:
        assert got == {}
        return
    assert set(got) == set(PROGRAM_METRICS)
    assert all(isinstance(v, float) and math.isfinite(v) for v in got.values())
    assert all(got[n] > 0 for n in PROGRAM_METRICS if "unattributed" not in n)
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        _, per = bench.cell_metrics(json.load(f), "tiny.ckpt")
    assert set(PROGRAM_METRICS) <= {m["name"] for m in per}
