"""Checkpoint window: blocks of a dense train state through the program's
``CheckpointManager``, saved to disk and restored onto the device.

Set-up makes the train state from the seed and runs ``dense_steps`` train
steps, so that Adam's moments hold real values (zero moments would
compress to nothing).  The state is cut into blocks of at most
``block_bytes``, each a like cross-section of every leaf of params, m and
v (:func:`block_plan`), the same for every seed.  Each block of the window
goes through ``save(..., snapshot=True)`` and ``wait()`` (durable
manifest), then ``restore()`` onto the device, and is compared on the
device, bit for bit, with the block that was saved, outside the timed
spans.  The window ends when the block in flight at the deadline
completes.

The manager is built as ``repro.launch.train`` builds it, with the
program's defaults, so that a change to those defaults shows here.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, system


def block_plan(shapes: dict, traffic: dict) -> list[list[tuple]]:
    """Blocks of the state ``shapes`` ({tree: {leaf path: (shape, itemsize)}}).

    Each leaf is viewed as rows of its last axis and cut into ``K`` equal
    row slices, ``K`` the least power of two that keeps a block within
    ``block_bytes``; block ``k`` holds the ``k``-th slice of every leaf, of
    every tree (params, m and v alike), as pieces ``(leaf, first row,
    rows)``.  So every block is a like cross-section of the whole state,
    and the window's mix, and what it stores per raw byte, does not depend
    on how many blocks it completes.  The plan depends on the shapes
    alone, the same for every seed."""
    trees = traffic["trees"]
    lead = shapes[trees[0]]
    rows = {}
    for leaf, (shape, item) in lead.items():
        last = shape[-1] if shape else 1
        rows[leaf] = (int(np.prod(shape[:-1])) if len(shape) > 1 else 1,
                      last * item)
    total = len(trees) * sum(r * rb for r, rb in rows.values())
    k_blocks = 1
    while total > k_blocks * traffic["block_bytes"]:
        k_blocks *= 2
    blocks = [[] for _ in range(k_blocks)]
    for leaf in sorted(lead):
        r = rows[leaf][0]
        for k in range(k_blocks):
            r0, r1 = k * r // k_blocks, (k + 1) * r // k_blocks
            if r1 > r0:
                blocks[k].append((leaf, r0, r1 - r0))
    return blocks


def _leaves(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {".".join(str(k.key) for k in p): x for p, x in flat}


def state_trees(state, trees: list) -> dict:
    """{tree name: {leaf path: array}} of the train state."""
    src = {"params": state.params, "opt.m": state.opt["m"],
           "opt.v": state.opt["v"]}
    return {t: _leaves(src[t]) for t in trees}


@jax.jit(static_argnums=2)
def _gather(xs, r0s, ns):
    """One flat array: rows ``r0`` to ``r0 + n`` of each ``x``, each viewed
    as rows of its last axis, one after another."""
    return jnp.concatenate([
        jax.lax.dynamic_slice_in_dim(x.reshape(-1, x.shape[-1] if x.ndim else 1),
                                     r0, n).reshape(-1)
        for x, r0, n in zip(xs, r0s, ns)])


def block_arrays(leaves: dict, block: list) -> dict:
    """{tree: flat array} of one block, made on the device."""
    r0s = tuple(r0 for _, r0, _ in block)
    ns = tuple(n for _, _, n in block)
    return {t: _gather(tuple(ls[leaf] for leaf, _, _ in block), r0s, ns)
            for t, ls in leaves.items()}


@jax.jit
def _differing(a, b):
    return jnp.sum(jax.lax.bitcast_convert_type(a, jnp.uint32)
                   != jax.lax.bitcast_convert_type(b, jnp.uint32))


def dense_state(cell: dict, seed: int, devices):
    """The train state from the seed after ``dense_steps`` seeded steps."""
    tr = cell["traffic"]
    B, S = tr["batch"], tr["seq_len"]
    sysm = system.TrainSystem(cell, seed, devices)
    sysm.compile((B, S))
    key = sysm.key
    for i in range(tr["dense_steps"]):
        tok = jax.random.randint(jax.random.fold_in(key, i), (B, S + 1), 0,
                                 cell["config"]["vocab_size"], jnp.int32)
        sysm.run_step({"tokens": tok[:, :-1], "targets": tok[:, 1:]})
    return sysm


def run(cell: dict, seed: int, seconds: float, trace: bool, spans,
        devices, t_start: float) -> dict:
    from repro import obs
    from repro.checkpoint import CheckpointManager

    tr = cell["traffic"]
    dev = devices[0]
    os.makedirs(bench.WORK, exist_ok=True)
    work = tempfile.mkdtemp(prefix="ckpt-", dir=bench.WORK)
    try:
        sysm = dense_state(cell, seed, devices)
        leaves = state_trees(sysm.state, tr["trees"])
        plan = block_plan({t: {k: (x.shape, x.dtype.itemsize)
                               for k, x in ls.items()}
                           for t, ls in leaves.items()}, tr)

        # every block's gather and comparison compiles here, not in the window
        for block in plan:
            for x in block_arrays(leaves, block).values():
                _differing(x, x)
        mgr = CheckpointManager(os.path.join(work, "ckpt"), keep=2)
        sharding = jax.sharding.SingleDeviceSharding(dev)
        step = 0

        def round_trip(block):
            nonlocal step
            step += 1
            tree = block_arrays(leaves, block)
            jax.block_until_ready(tree)
            raw = sum(x.nbytes for x in tree.values())
            t0 = time.perf_counter()
            with spans("ckpt.save"):
                mgr.save(step, tree, snapshot=True)
            t1 = time.perf_counter()
            with spans("ckpt.save_wait"):
                mgr.wait()
            t2 = time.perf_counter()
            stored = sum(os.path.getsize(os.path.join(mgr.dir, f))
                         for f in os.listdir(mgr.dir)
                         if f"{step:08d}" in f)
            obs.trace.drain()
            with spans("ckpt.restore"):
                got, _ = mgr.restore(step, template={k: 0 for k in tree},
                                     shardings={k: sharding for k in tree})
                jax.block_until_ready(got)
            t3 = time.perf_counter()
            decode = sum(ev["dur"] for ev in obs.trace.drain()
                         if ev.get("name") == "ckpt.read_branch") / 1e6
            # one count per array, read after the window: no program of its own
            diff = [_differing(tree[k], got[k]) for k in tree]
            return {"raw": raw, "stored": stored, "save": t2 - t0,
                    "stall": t1 - t0, "host": t2 - t1, "restore": t3 - t2,
                    "decode": decode, "diff": diff}

        round_trip(plan[-1])            # first-call costs of the save path
        setup_s = time.perf_counter() - t_start

        done, failed = [], 0
        t_w = time.perf_counter()
        deadline = t_w + seconds
        i = 0
        while time.perf_counter() < deadline:
            try:
                done.append(round_trip(plan[i % len(plan)]))
            except Exception as e:          # a block that never comes back
                print(f"chipbench: block {i} failed: {type(e).__name__}: {e}",
                      file=sys.stderr, flush=True)
                failed += 1
            i += 1
        window_s = time.perf_counter() - t_w
        out = {"setup_s": setup_s, "window_t0": t_w, "window_s": window_s,
               "blocks": done,
               "memory_peak_bytes": max(bench.memory_peak(devices),
                                        sysm.footprint_bytes())}
        if trace:
            out["trace"] = _traced_block(round_trip, plan, i, spans, work,
                                         devices)
        for b in done:
            b["diff"] = sum(int(d) for d in b["diff"])
        bits = sum(b["diff"] for b in done)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    raw = sum(b["raw"] for b in done)
    if done:
        def few(key):
            v = sorted(b[key] for b in done)
            return f"{key} {v[0]:.3f}/{v[len(v) // 2]:.3f}/{v[-1]:.3f}"
        print(f"chipbench: window {len(done)} blocks of {raw / len(done) / 1e6:.2f} MB"
              f" in {out['window_s']:.3f} s; per block s min/median/max: "
              + ", ".join(few(k) for k in ("stall", "host", "restore", "decode")),
              file=sys.stderr)
    out.update({
        "attempted": len(done) + failed, "failed": failed + sum(
            1 for b in done if b["diff"]),
        "raw_bytes": raw,
        "ckpt_save_MBps": raw / sum(b["save"] for b in done) / 1e6,
        "ckpt_restore_MBps": raw / sum(b["restore"] for b in done) / 1e6,
        "ckpt_stored_per_raw": sum(b["stored"] for b in done) / raw,
        "checks": {"bits_differing": bits, "blocks_failed": failed},
    })
    return out


def _traced_block(round_trip, plan, i, spans, work, devices) -> dict:
    """One more block round trip under the profiler."""
    from chipbench import tracing
    trace_dir = os.path.join(work, "trace")
    spans.annotate = True
    try:
        with jax.profiler.trace(trace_dir):
            with spans("trace_window"):
                round_trip(plan[i % len(plan)])
    finally:
        spans.annotate = False
    return tracing.reduce(tracing.events_from_xplane(trace_dir),
                          [d.id for d in devices])
