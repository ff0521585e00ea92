"""Seconds of the restore's file work per raw GB unpacked: the basket
reads (``basket.stage_s{op=unpack,stage=io}``, thread-seconds) and the
program's ``ckpt.open`` phase (container, TOC and ``__meta__``)."""

from chipbench import program_obs as po


def read(ctx):
    snap = po.snapshot(ctx)
    io, gb = po.stage_s(snap, "unpack", "io"), po.raw_gb(snap, "unpack")
    return (io + po.phase_s(snap, "open")) / gb if io and gb else None
