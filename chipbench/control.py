"""Readings that set the upper end of each correctness limit: the control
and the planted faults, put in the program's place.

    python chipbench/control.py --workload <cell> --seeds <n> [<n> ...]

Train cells: the control is the plain reference computed with float8
(e4m3) matmul operands, one step below the configuration's bfloat16
compute; the faults are half of the batch left out (the mean taken over
the rest), a state left unchanged, and the update of the leaf that moves
least left out or doubled.  Checkpoint cells: the control stores each
block in bfloat16, one step below its float32; the fault alters one word
of one restored block.  Each reading is the number the cell compares,
taken against the float32 reference, and is judged by the cell's own
limits as a run is: every line gives the readings, each beside its limit,
and whether they would pass as ``correct``.  The benchmark's own runs
never run this; it runs on the chip like them, one line of JSON per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def train_readings(cell: dict, seed: int, devices) -> dict:
    import jax.numpy as jnp
    from chipbench import bench
    from chipbench.drivers import train

    tr, cfg = cell["traffic"], cell["config"]
    fam = bench.family(cell)
    B = tr["batch"] * cfg["mesh"]["data"]
    S, n = tr["seq_len"], tr["checked_steps"]
    w = S + 1
    stream = train.token_stream(seed, 0, n * B * w, tr, cfg["vocab_size"])
    wins = stream.reshape(n, B, w)
    batches = [(jnp.asarray(x[:, :-1]), jnp.asarray(x[:, 1:])) for x in wins]
    opt = tr["optimizer"]
    sh, bsh = fam.ref_placement(cfg, devices)
    ref = fam.ref_train(cfg, seed, batches, opt, shardings=sh, batch_sharding=bsh)

    def read(run):
        return dict(train.gaps(run["loss"], run["grad_norm"], run["change_norm"],
                               ref, 0, run["global_grad_norm"]),
                    window_loss_not_finite=0)

    def one_leaf(factor):
        med = statistics.median(ref["grad_norm"].values())
        least = min((k for k, g in ref["grad_norm"].items() if g >= 1e-3 * med),
                    key=ref["change_norm"].get)
        change = dict(ref["change_norm"], **{least: factor * ref["change_norm"][least]})
        return dict(ref, change_norm=change)

    ctl = fam.ref_train(cfg, seed, batches, opt, q=fam.fp8, shardings=sh,
                        batch_sharding=bsh)
    # half the rows may not split over every chip; then they go unplaced
    hsh = bsh if bsh is not None and (B // 2) % bsh.mesh.size == 0 else None
    half = fam.ref_train(cfg, seed, [(t[: B // 2], y[: B // 2])
                                     for t, y in batches], opt, shardings=sh,
                         batch_sharding=hsh)
    still = dict(ref, change_norm={k: 0.0 for k in ref["change_norm"]})
    return {"control_fp8": read(ctl), "half_batch": read(half),
            "state_unchanged": read(still), "least_leaf_unmoved": read(one_leaf(0.0)),
            "least_leaf_doubled": read(one_leaf(2.0))}


def ckpt_readings(cell: dict, seed: int, devices) -> dict:
    import jax.numpy as jnp
    from chipbench.drivers import ckpt

    tr = cell["traffic"]
    sysm = ckpt.dense_state(cell, seed, devices)
    leaves = ckpt.state_trees(sysm.state, tr["trees"])
    plan = ckpt.block_plan({t: {k: (x.shape, x.dtype.itemsize)
                                for k, x in ls.items()}
                            for t, ls in leaves.items()}, tr)
    bf16 = altered = 0
    for block in plan[:6]:
        for x in ckpt.block_arrays(leaves, block).values():
            y = x.astype(jnp.bfloat16).astype(x.dtype)
            bf16 += int(ckpt._differing(x, y))
            z = x.at[0].set(x[0] + 1)
            altered += int(ckpt._differing(x, z))
    del sysm, leaves
    return {"control_bf16": {"bits_differing": bf16, "blocks_failed": 0},
            "answer_altered": {"bits_differing": altered, "blocks_failed": 0}}


def judged(readings: dict, limits: dict) -> dict:
    """Each control's or fault's readings, judged by the cell's limits."""
    from chipbench import bench
    out = {}
    for name, r in readings.items():
        checks = bench.compared(r, limits)
        out[name] = {"correct": bench.judge(checks), "checks": checks,
                     "readings": {k: v for k, v in r.items() if k not in checks}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import bench
    base = os.path.join(ROOT, "chipbench")
    cell = bench.load_cell(args.workload, base)
    try:
        chips = bench.find_chips(cell["chips"], base)
    except bench.NoChip as e:
        print(f"chipbench control: {e}", file=sys.stderr)
        return 3
    bench.enable_compile_cache()
    for seed in args.seeds:
        if cell["traffic"]["driver"] == "train":
            r = train_readings(cell, seed, chips["devices"])
        else:
            r = ckpt_readings(cell, seed, chips["devices"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "judged": judged(r, cell["limits"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
