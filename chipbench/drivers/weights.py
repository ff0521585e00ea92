"""Weights window: the bfloat16 weights of a serving model, laid out as the
serving mesh holds them, saved block by block through the program's
``CheckpointManager`` and restored through the serve entry point's
restore onto the devices that hold each piece.

Set-up makes the weights from the seed on the mesh ``data=1,
model=<chips>`` in the program's param shardings
(``repro.launch.serve.serving_layout``), then checks the program's
serving path against the family's float32 reference: a prefill of
``prompts`` prompts of ``prompt_len`` tokens and ``decode_steps`` decode
steps through the cache, teacher-forced with tokens from the seed, whose
logits must match the reference's full forward at the same positions
(``logits_gap``: the median over the compared rows of logits of each row's
norm of the difference over the norm of the reference row).  The median
and not the pooled norm: in bfloat16 a token whose second and third
router probabilities nearly tie can take another expert than in float32,
and such a row reads far off alone; rounding that runs through every
layer, as a lower precision does, moves every row.  The worst row is
printed as a reading beside it.

The weights are cut into blocks with :func:`ckpt.block_plan` (``trees``
``["params"]``), each a like cross-section of every leaf, the same for
every seed.  A block's pieces stay separate arrays, one branch each, and
each piece lives where the serving layout keeps its rows: sharded like
its leaf where the leaf's sharded axis lies inside the piece, else on the
one device whose shard holds those rows.  Each block of the window goes
through ``save(..., snapshot=True)`` and ``wait()`` (durable manifest),
then the serve entry point's ``restore_weights`` with the pieces'
shardings, and is compared on the devices, bit for bit, with the block
that was saved, outside the timed spans.  The window ends when the block
in flight at the deadline completes.
"""

from __future__ import annotations

import functools
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import bench
from chipbench.drivers import ckpt
from repro.launch.serve import restore_weights, serving_layout


# ---------------------------------------------------------------------------
# the model check
# ---------------------------------------------------------------------------

def check_tokens(cell: dict, seed: int):
    """(prompts, prompt_len + decode_steps) token ids from the seed."""
    tr, fam = cell["traffic"], bench.family(cell)
    key = jax.random.fold_in(fam.seed_key(seed), 1)
    return jax.random.randint(key, (tr["prompts"], tr["prompt_len"] + tr["decode_steps"]),
                              0, cell["config"]["vocab_size"], jnp.int32)


def serve_logits(model, params, mesh, tokens, prompt_len: int):
    """The program's serving path: prefill ``prompt_len`` tokens, then one
    decode step per remaining token.  Float32 logits (B, S - prompt_len + 1,
    V) of positions ``prompt_len - 1`` .. S - 1."""
    from repro.parallel.actctx import activation_context
    S = tokens.shape[1]
    with mesh, activation_context(mesh):
        prefill = jax.jit(functools.partial(model.prefill, max_len=S))
        decode = jax.jit(model.decode_step)
        logits, cache = prefill(params, {"tokens": tokens[:, :prompt_len]})
        out = [logits]
        for t in range(prompt_len, S):
            logits, cache = decode(params, cache, tokens[:, t:t + 1],
                                   jnp.asarray(t, jnp.int32))
            out.append(logits)
    return jnp.stack([x.astype(jnp.float32) for x in out], 1)


@jax.jit
def row_gaps(got, ref):
    """Per row of logits: norm of the difference over the norm of the
    reference row."""
    return jnp.linalg.norm(got - ref, axis=-1) / jnp.linalg.norm(ref, axis=-1)


def logits_gap(cell: dict, model, params, mesh, tokens, control=False) -> dict:
    """``logits_gap`` and the worst row's gap, of the program's serving path
    or, for the control, of the reference computed with float8 weights."""
    fam, tr = bench.family(cell), cell["traffic"]
    first = tr["prompt_len"] - 1
    ref = fam.ref_logits(cell["config"], params, tokens, first)
    got = (fam.ref_logits(cell["config"], params, tokens, first, weights_fp8=True)
           if control else serve_logits(model, params, mesh, tokens, tr["prompt_len"]))
    rows = np.asarray(row_gaps(got, ref)).ravel()
    return {"logits_gap": float(np.median(rows)),
            "logits_gap_worst_row": float(rows.max())}


# ---------------------------------------------------------------------------
# pieces in the serving layout
# ---------------------------------------------------------------------------

def piece_layout(shape: tuple, spec: tuple, mesh: Mesh, r0: int, n: int):
    """Where rows ``r0 .. r0+n`` of a leaf of ``shape`` and PartitionSpec
    ``spec`` (viewed as rows of its last axis) live in the serving layout.

    The piece keeps as many trailing axes of the leaf as its rows cover
    whole.  Returns ``(piece shape, sharding, local)``.  Where none of the
    axes the piece does not keep is split over devices, the piece is
    sharded as its leaf is, and ``local`` is None.  Else its rows lie in
    the shard of one device, and ``local`` is ``(device, first row of the
    piece in that shard)``; a piece whose rows span several shards
    raises."""
    spec = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    if len(shape) <= 1:
        return tuple(shape), NamedSharding(mesh, P(*spec)), None
    j, q = len(shape) - 1, 1                    # keep axes j.. ; q rows each
    while j > 1 and n % (q * shape[j - 1]) == 0 and r0 % (q * shape[j - 1]) == 0:
        q *= shape[j - 1]
        j -= 1
    lead, lead_spec = shape[:j], spec[:j]
    pshape = (n // q,) + tuple(shape[j:])
    split = [(i, ax) for i, ax in enumerate(lead_spec)
             if ax is not None and mesh.shape[ax] > 1]
    if not split:
        return pshape, NamedSharding(mesh, P(None, *spec[j:])), None
    (i, ax), = split
    u = np.arange(r0 // q, (r0 + n) // q)
    idx = list(np.unravel_index(u, lead))
    per = lead[i] // mesh.shape[ax]
    owner = idx[i] // per
    if (owner != owner[0]).any():
        raise ValueError(f"rows {r0}..{r0 + n} of a leaf {shape} {spec} span "
                         "several devices")
    idx[i] = idx[i] - owner[0] * per
    local = np.ravel_multi_index(idx, lead[:i] + (per,) + lead[i + 1:])
    coord = [0] * len(mesh.axis_names)
    coord[mesh.axis_names.index(ax)] = int(owner[0])
    dev = mesh.devices[tuple(coord)]
    return pshape, jax.sharding.SingleDeviceSharding(dev), (dev, int(local[0]) * q)


def _rows_of(x, r0, n: int, shape: tuple):
    """Rows ``r0 .. r0+n`` of ``x`` viewed as rows of its last axis."""
    flat = x.reshape(-1, x.shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
    return jax.lax.dynamic_slice_in_dim(flat, r0, n).reshape(shape)


_rows = jax.jit(_rows_of, static_argnums=(2, 3))


@functools.lru_cache(maxsize=None)
def _placed(sharding):
    """``_rows`` with its result in ``sharding``."""
    return jax.jit(_rows_of, static_argnums=(2, 3), out_shardings=sharding)


def make_piece(leaf, layout, r0: int, n: int):
    """One piece, made on the devices that hold it."""
    pshape, sharding, local = layout
    if local is None:
        return _placed(sharding)(leaf, r0, n, pshape)
    dev, lr0 = local
    shard = next(s.data for s in leaf.addressable_shards if s.device == dev)
    return _rows(shard, lr0, n, pshape)


@jax.jit
def _differing(a, b):
    return jnp.sum(jax.lax.bitcast_convert_type(a, jnp.uint16)
                   != jax.lax.bitcast_convert_type(b, jnp.uint16))


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def setup_weights(cell: dict, seed: int, devices):
    """(model, mesh, param shardings, params) of the cell, made from the
    seed on the serving layout."""
    fam = bench.family(cell)
    model = fam.program_model(cell["config"])
    tp = cell["config"]["mesh"]["model"]
    if len(devices) != tp:
        raise ValueError(f"the serving mesh takes {tp} devices, given {len(devices)}")
    mesh, psh = serving_layout(model, tp)
    params = fam.make_init(cell["config"], psh)(fam.seed_key(seed))
    jax.block_until_ready(params)
    return model, mesh, psh, params


def run(cell: dict, seed: int, seconds: float, trace: bool, spans,
        devices, t_start: float) -> dict:
    from repro import obs
    from repro.checkpoint import CheckpointManager

    tr = cell["traffic"]
    os.makedirs(bench.WORK, exist_ok=True)
    work = tempfile.mkdtemp(prefix="weights-", dir=bench.WORK)
    try:
        model, mesh, psh, params = setup_weights(cell, seed, devices)
        with spans("model_check"):
            gaps = logits_gap(cell, model, params, mesh, check_tokens(cell, seed))
        leaves = ckpt._leaves(params)
        specs = {k: s.spec for k, s in ckpt._leaves(psh).items()}
        plan = ckpt.block_plan({"params": {k: (x.shape, x.dtype.itemsize)
                                           for k, x in leaves.items()}}, tr)
        layouts = {(leaf, r0): piece_layout(leaves[leaf].shape, specs[leaf], mesh, r0, n)
                   for block in plan for leaf, r0, n in block}

        def block_arrays(block):
            return {f"{leaf}@{r0}": make_piece(leaves[leaf], layouts[leaf, r0], r0, n)
                    for leaf, r0, n in block}

        # every piece's slice and comparison compiles here, not in the
        # window: one block of each set of piece shapes and placements
        seen = set()
        for block in plan:
            sig = tuple((leaf, n, layouts[leaf, r0][1]) for leaf, r0, n in block)
            if sig not in seen:
                seen.add(sig)
                for x in block_arrays(block).values():
                    _differing(x, x)
        mgr = CheckpointManager(os.path.join(work, "ckpt"), keep=2)
        step = 0

        def round_trip(block):
            nonlocal step
            step += 1
            tree = block_arrays(block)
            jax.block_until_ready(tree)
            shardings = {name: x.sharding for name, x in tree.items()}
            raw = sum(x.nbytes for x in tree.values())
            t0 = time.perf_counter()
            with spans("ckpt.save"):
                mgr.save(step, tree, snapshot=True)
            t1 = time.perf_counter()
            with spans("ckpt.save_wait"):
                mgr.wait()
            t2 = time.perf_counter()
            stored = sum(os.path.getsize(os.path.join(mgr.dir, f))
                         for f in os.listdir(mgr.dir) if f"{step:08d}" in f)
            obs.trace.drain()
            with spans("ckpt.restore"):
                got = restore_weights(mgr, shardings, step)
                jax.block_until_ready(got)
            t3 = time.perf_counter()
            decode = sum(ev["dur"] for ev in obs.trace.drain()
                         if ev.get("name") == "ckpt.read_branch") / 1e6
            # one count per piece, read after the window: no program of its own
            diff = [_differing(tree[name], got[name]) for name in tree]
            return {"raw": raw, "stored": stored, "save": t2 - t0,
                    "stall": t1 - t0, "host": t2 - t1, "restore": t3 - t2,
                    "decode": decode, "diff": diff}

        round_trip(plan[-1])                # first-call costs of the save path
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
               "blocks": done, "memory_peak_bytes": bench.memory_peak(devices)}
        if trace:
            out["trace"] = ckpt._traced_block(round_trip, plan, i, spans, work,
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
        "checks": {"bits_differing": bits, "blocks_failed": failed, **gaps},
    })
    return out


def control_readings(cell: dict, seed: int, devices) -> dict:
    """The readings that set the upper end of ``logits_gap``: the reference
    with its weights rounded to float8 (e4m3), one step below the
    configuration's bfloat16, against the float32 reference.  The program
    is not run."""
    model, mesh, _, params = setup_weights(cell, seed, devices)
    gaps = logits_gap(cell, model, params, mesh, check_tokens(cell, seed), control=True)
    return {"control_fp8": {"bits_differing": 0, "blocks_failed": 0, **gaps}}
