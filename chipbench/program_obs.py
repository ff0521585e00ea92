"""The program's own instruments, as the checkpoint metrics read them.

The program times each checkpoint phase into ``ckpt.phase_s{phase=...}``
and each basket's stages into ``basket.stage_s{op=pack|unpack,
stage=precond|codec|checksum|io}``, with the raw bytes of those baskets in
``basket.stage_bytes{op=...}`` (``repro.obs``).  The readers take the
registry at the end of the run: its sums outlive the span ring, which the
checkpoint driver drains every round trip.  The registry holds every
round trip of the run, the set-up one and the traced one among them; the
blocks are alike, so each ratio is taken over the bytes that its own
series counted.

A restore decodes a branch's baskets on several threads, so the unpack
stages are thread-seconds; where they overlap, their sum exceeds the wall
time of the reads that hold them, and they count at most that wall time
in what a restore leaves unattributed.

Where obs is off, the run has no blocks, or the program has no such
series (one older than these instruments), every function here gives
None or 0, and a reader then reports nothing.
"""

from __future__ import annotations

STAGES = ("precond", "codec", "checksum", "io")


def snapshot(ctx):
    """The program's registry at the end of the run, or None."""
    if not ctx["out"].get("blocks"):
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.snapshot() if obs.enabled() else None


def _get(snap, kind: str, name: str, **labels):
    if not snap:
        return None
    from repro.obs import format_key
    return snap[kind].get(format_key(name, labels))


def stage_s(snap, op: str, stage: str) -> float:
    """Seconds in one basket stage, summed over every basket."""
    h = _get(snap, "hists", "basket.stage_s", op=op, stage=stage)
    return float(h["sum"]) if h else 0.0


def phase_s(snap, phase: str) -> float:
    """Seconds in one checkpoint phase, summed over the run."""
    h = _get(snap, "hists", "ckpt.phase_s", phase=phase)
    return float(h["sum"]) if h else 0.0


def raw_gb(snap, op: str) -> float:
    """Raw GB of the baskets packed (``op="pack"``) or unpacked."""
    return (_get(snap, "counters", "basket.stage_bytes", op=op) or 0) / 1e9


def save_io_s(snap) -> float:
    """Basket writes, then commit (TOC, fsyncs, rename), manifest and GC."""
    return stage_s(snap, "pack", "io") + sum(
        phase_s(snap, p) for p in ("commit", "manifest", "gc"))


def save_attributed_s(snap) -> float:
    """Every save stage after ``save()`` returns (the snapshot is before)."""
    return sum(stage_s(snap, "pack", st) for st in STAGES[:3]) + save_io_s(snap)


def restore_attributed_s(snap) -> float:
    """Open, the basket stages of the reads, and the device puts."""
    decode = sum(stage_s(snap, "unpack", st) for st in STAGES)
    return (phase_s(snap, "open") + min(decode, phase_s(snap, "read_branch"))
            + phase_s(snap, "device_put"))


def driver_s_per_gb(ctx, key: str) -> float:
    """The driver's own seconds ``key`` of the window's blocks per raw GB."""
    blocks = ctx["out"]["blocks"]
    return sum(b[key] for b in blocks) / (sum(b["raw"] for b in blocks) / 1e9)
