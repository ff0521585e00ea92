"""Raw MB per second of the codec in the program's basket decodes
(``basket.stage_s{op=unpack,stage=codec}``, per decode thread)."""

from chipbench import program_obs as po


def read(ctx):
    snap = po.snapshot(ctx)
    s, gb = po.stage_s(snap, "unpack", "codec"), po.raw_gb(snap, "unpack")
    return gb * 1e3 / s if s and gb else None
