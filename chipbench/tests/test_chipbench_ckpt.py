"""The checkpoint driver on the CPU at a tiny size: a sound run is correct,
and an altered answer and the control are not."""

import jax

from chipbench import bench, control

SECONDS = 0.5


def _run(base):
    cell = bench.load_cell("tiny.ckpt", base)
    out = bench.driver(cell).run(cell, 2 ** 33 + 5, SECONDS, False,
                                 bench.Spans(), jax.devices()[:1], 0.0)
    checks = bench.compared(out["checks"], cell["limits"])
    return out, checks


def test_sound_run_is_correct(tiny_base):
    out, checks = _run(tiny_base)
    assert bench.judge(checks) and out["failed"] == 0, checks
    assert out["attempted"] > 0
    assert 0 < out["ckpt_stored_per_raw"] < 1.1
    assert out["ckpt_save_MBps"] > 0 and out["ckpt_restore_MBps"] > 0
    raw = sum(b["raw"] for b in out["blocks"])
    assert raw == out["raw_bytes"] > 0


def test_answer_altered_in_restore_is_caught(tiny_base, monkeypatch):
    from repro.checkpoint import CheckpointManager
    orig = CheckpointManager.restore

    def altered(self, *a, **k):
        tree, meta = orig(self, *a, **k)
        first = sorted(tree)[0]
        tree[first] = tree[first].at[0].add(1.0)
        return tree, meta

    monkeypatch.setattr(CheckpointManager, "restore", altered)
    out, checks = _run(tiny_base)
    assert checks["bits_differing"]["value"] > 0
    assert not bench.judge(checks) and out["failed"] > 0


def test_control_fails_the_limit(tiny_base):
    cell = bench.load_cell("tiny.ckpt", tiny_base)
    judged = control.judged(control.ckpt_readings(cell, 11, jax.devices()[:1]),
                            cell["limits"])
    for name in ("control_bf16", "answer_altered"):
        assert judged[name]["checks"]["bits_differing"]["value"] > 0
        assert not judged[name]["correct"]
