"""Seconds of the save's file work per raw GB packed: the basket writes
(``basket.stage_s{op=pack,stage=io}``) and the program's ``ckpt.commit``
(TOC, fsyncs, rename), ``ckpt.manifest`` and ``ckpt.gc`` phases."""

from chipbench import program_obs as po


def read(ctx):
    snap = po.snapshot(ctx)
    s, gb = po.save_io_s(snap), po.raw_gb(snap, "pack")
    return s / gb if po.stage_s(snap, "pack", "io") and gb else None
