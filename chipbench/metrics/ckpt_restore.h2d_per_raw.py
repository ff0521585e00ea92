"""Bytes the restores sent host-to-device (the program's counter
``ckpt.h2d_bytes``: each device's shard, once for every device it lands
on) per raw byte unpacked (``basket.stage_bytes{op=unpack}``).  1.0 where
every restored array lands on one device; above 1 by what replicated
pieces send again."""

from chipbench import program_obs as po


def read(ctx):
    snap = po.snapshot(ctx)
    h2d = po._get(snap, "counters", "ckpt.h2d_bytes")
    gb = po.raw_gb(snap, "unpack")
    return h2d / 1e9 / gb if h2d and gb else None
