"""Seconds of the program's ``ckpt.snapshot`` phase (the host copy that
``save(snapshot=True)`` makes before it returns) per raw GB packed."""

from chipbench import program_obs as po


def read(ctx):
    snap = po.snapshot(ctx)
    s, gb = po.phase_s(snap, "snapshot"), po.raw_gb(snap, "pack")
    return s / gb if s and gb else None
