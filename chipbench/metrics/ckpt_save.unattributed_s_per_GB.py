"""Seconds per raw GB from ``save()`` returning to ``wait()`` returning
(the driver's clock) that no save stage of the program covers: the
driver's seconds per GB minus the basket stages' (precondition, codec,
checksum, writes) and the commit, manifest and GC phases' per GB packed."""

from chipbench import program_obs as po


def read(ctx):
    snap = po.snapshot(ctx)
    s, gb = po.save_attributed_s(snap), po.raw_gb(snap, "pack")
    if not (po.stage_s(snap, "pack", "precond") and gb):
        return None
    return po.driver_s_per_gb(ctx, "host") - s / gb
