"""``run.py`` end to end with the look for a chip stood in for: the last
line holds the contract's keys, and a run with no chip or no program prints
no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import bench
from chipbench import run as runmod

from conftest import ROOT, cpu_chips

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("cell,trace", [("tiny.train", 0), ("tiny.train", 1),
                                        ("tiny.ckpt", 0), ("tiny.ckpt", 1)])
def test_last_line_has_the_contract_keys(tiny_root, monkeypatch, capsys, cell, trace):
    monkeypatch.setattr(runmod, "ROOT", tiny_root)
    monkeypatch.setattr(bench, "find_chips", cpu_chips)
    monkeypatch.setattr(bench, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(bench, "peaks", lambda kind, base: {"bf16_flops": 1e12})
    rc = runmod.main(["--workload", cell, "--seed", "2147483649",
                      "--seconds", "0.5", "--trace", str(trace)])
    out, err = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    keys = list(line)
    assert keys[:5] == KEYS and keys[-1] == "checks"
    assert set(keys) <= set(KEYS) | {"breakdown", "checks"}
    assert line["correct"] is True
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        e2e, per = bench.cell_metrics(json.load(f), cell)
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        # the CPU has no device plane, so the device-trace readers are silent
        assert set(line["metrics"]) <= {m["name"] for m in per}
    else:
        assert set(line["metrics"]) == {m["name"] for m in e2e}
        assert all(m["value"] > 0 for m in line["metrics"].values())
    last = err.strip().splitlines()[-len(line["checks"]) - 1:]
    assert last[0] == "correct: True"
    assert [x.split(":")[0] for x in last[1:]] == [f"check {c}" for c in line["checks"]]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                           "rwkv6_4l.ckpt", "--seed", "1", "--seconds", "1",
                           "--trace", "0"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_chip_prints_no_result():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_print_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0 and p.stdout == ""
