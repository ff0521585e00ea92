"""GB per second of the restores' host-to-device puts: the program's
counter ``ckpt.h2d_bytes`` over the seconds of its ``ckpt.device_put``
phases (``ckpt.phase_s{phase=device_put}``)."""

from chipbench import program_obs as po


def read(ctx):
    snap = po.snapshot(ctx)
    h2d = po._get(snap, "counters", "ckpt.h2d_bytes")
    s = po.phase_s(snap, "device_put")
    return h2d / 1e9 / s if h2d and s else None
