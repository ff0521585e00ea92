"""Shared set-up of the benchmark's tests: a copy of the benchmark's files
with a tiny rwkv6 configuration added as files, run on the CPU."""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_TRAIN = {"driver": "train", "batch": 2, "seq_len": 64, "shards": 2,
              "windows_per_shard": 16, "zipf_a": 1.3,
              "checked_steps": 3, "trace_steps": 2,
              "optimizer": {"peak_lr": 3e-4, "warmup": 0, "total_steps": 100,
                            "clip_norm": 1.0, "weight_decay": 0.1}}
TINY_CKPT = {"driver": "ckpt", "batch": 2, "seq_len": 64, "dense_steps": 2,
             "block_bytes": 65536,
             "trees": ["params", "opt.m", "opt.v"],
             "optimizer": TINY_TRAIN["optimizer"]}
# the tiny configuration computes in float32, where the program matches the
# reference to about 1e-5
TRAIN_LIMITS = {"rows_not_in_stream": 0, "loss_gap": 1e-3,
                "grad_norm_gap": 1e-3, "change_norm_gap": 1e-3,
                "window_loss_not_finite": 0}


def make_tiny_base(dest: str) -> str:
    """A copy of the benchmark's files plus the tiny cells ``tiny.train``
    and ``tiny.ckpt``, added as files only."""
    base = os.path.join(dest, "chipbench")
    shutil.copytree(os.path.dirname(HERE), base,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))

    def put(sub, name, obj):
        with open(os.path.join(base, sub, f"{name}.json"), "w") as f:
            json.dump(obj, f)

    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        put("configs", "rwkv6-tiny", json.load(f))
    put("traffic", "tiny_train", TINY_TRAIN)
    put("traffic", "tiny_ckpt", TINY_CKPT)
    put("workloads", "tiny.train", {"config": "rwkv6-tiny", "traffic": "tiny_train",
                                    "chips": 1, "limits": TRAIN_LIMITS})
    put("workloads", "tiny.ckpt", {"config": "rwkv6-tiny", "traffic": "tiny_ckpt",
                                   "chips": 1, "limits": {"bits_differing": 0,
                                                          "blocks_failed": 0}})
    return base


# the train metrics, which the benchmark keeps as files for the train cell
# that waits under PERF.md's Open questions
TRAIN_E2E = [{"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
              "bound": 0.01, "source": "host_clock", "workloads": ["tiny.train"]}]
TRAIN_PER_LAYER = [
    {"name": n, "unit": u, "better": b, "source": "host_clock", "layer": n,
     "moves": "train_tokens_per_s", "workloads": ["tiny.train"]}
    for n, u, b in [("train_step.mfu", "%", "higher"),
                    ("device.idle_share.train", "%", "lower"),
                    ("pipeline.wait_ms_per_step", "ms", "lower")]]


def make_tiny_root(dest: str) -> str:
    """A checkout holding the tiny cells: the benchmark's files, a
    ``BENCHMARK.json`` that lists them, and the program."""
    base = make_tiny_base(dest)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w.replace("rwkv6_4l", "tiny") for w in m["workloads"]]
    names = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
    spec["end_to_end"] += [m for m in TRAIN_E2E if m["name"] not in names]
    spec["per_layer"] += [m for m in TRAIN_PER_LAYER if m["name"] not in names]
    spec["workloads"] = [{"name": "tiny.train", "config": "rwkv6-tiny",
                          "traffic": "tiny_train", "chips": 1, "why": "test"},
                         {"name": "tiny.ckpt", "config": "rwkv6-tiny",
                          "traffic": "tiny_ckpt", "chips": 1, "why": "test"}]
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(dest, "src"))
    return dest


@pytest.fixture(scope="module")
def tiny_base(tmp_path_factory):
    return make_tiny_base(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("root")))


def cpu_chips(chips, base):
    """Stands in for the harness's look for a chip, on the CPU."""
    import jax
    return {"platform": "cpu", "kind": "cpu", "count": chips,
            "devices": jax.devices()[:chips]}
