"""Seconds per raw GB of ``restore()`` until the block is on the device
(the driver's clock) that no restore stage of the program covers: the
driver's seconds per GB minus the ``ckpt.open`` phase, the basket stages
of the reads (at most the wall time of the ``ckpt.read_branch`` spans
that hold them) and the ``ckpt.device_put`` phases, per GB unpacked.
Mostly the wait for the host-to-device copies."""

from chipbench import program_obs as po


def read(ctx):
    snap = po.snapshot(ctx)
    s, gb = po.restore_attributed_s(snap), po.raw_gb(snap, "unpack")
    if not (po.stage_s(snap, "unpack", "precond") and gb):
        return None
    return po.driver_s_per_gb(ctx, "restore") - s / gb
