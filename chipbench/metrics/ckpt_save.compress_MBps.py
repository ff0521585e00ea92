"""Raw MB per second of the codec in the program's basket packs
(``basket.stage_s{op=pack,stage=codec}``)."""

from chipbench import program_obs as po


def read(ctx):
    snap = po.snapshot(ctx)
    s, gb = po.stage_s(snap, "pack", "codec"), po.raw_gb(snap, "pack")
    return gb * 1e3 / s if s and gb else None
