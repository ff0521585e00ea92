"""Train window: the program's donated step fed by ``TokenPipeline`` from
local basket shards that the benchmark writes from the seed.

Set-up makes the weights on the device, writes the shards, compiles the
step and drives it through its first ``checked_steps`` steps from the
window's own feed.  Those steps are what the reference checks: each step's
loss, every leaf's norm of the first gradient as the optimizer got it
(Adam's first moment after one step, over 1 - b1), and every leaf's norm
of the change after the checked steps.  The same state then runs the
window.  With ``--trace 1`` a few more steps run under the profiler after
the window.
"""

from __future__ import annotations

import os
import shutil
import statistics
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, system, tracing


RESERVED_IDS = 2          # 0 = pad and 1 = eos are never drawn, as in the program


def token_stream(seed: int, shard: int, n_tokens: int, traffic: dict,
                 vocab: int) -> np.ndarray:
    """Zipf-distributed token ids of one shard, drawn as the program's
    ``write_token_shards`` draws them, from the seed and the shard."""
    rng = np.random.default_rng([seed, shard])
    toks = rng.zipf(traffic["zipf_a"], n_tokens).astype(np.int64)
    return ((toks % (vocab - RESERVED_IDS)) + RESERVED_IDS).astype(np.int32)


def write_shards(directory: str, seed: int, traffic: dict, vocab: int) -> tuple:
    """The shards as the program's writer stores token shards; returns their
    paths and the token streams they hold."""
    from repro.core.bfile import BasketWriter
    from repro.core.policy import choose
    n = traffic["windows_per_shard"] * (traffic["seq_len"] + 1)
    paths, streams = [], []
    for i in range(traffic["shards"]):
        toks = token_stream(seed, i, n, traffic, vocab)
        path = os.path.join(directory, f"shard-{i:03d}.bskt")
        with BasketWriter(path) as w:
            w.write_branch("tokens", toks, choose("tokens", toks, "analysis"))
        paths.append(path)
        streams.append(toks)
    return paths, streams


def window_index(streams: list, seq_len: int) -> dict:
    """{window bytes: (shard, window)} of every (seq_len + 1)-token window."""
    w = seq_len + 1
    out = {}
    for i, s in enumerate(streams):
        for j, row in enumerate(s[: (s.size // w) * w].reshape(-1, w)):
            out[row.tobytes()] = (i, j)
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, spans,
        devices, t_start: float) -> dict:
    tr = cell["traffic"]
    os.makedirs(bench.WORK, exist_ok=True)
    work = tempfile.mkdtemp(prefix="train-", dir=bench.WORK)
    try:
        paths, streams = write_shards(work, seed, tr, cell["config"]["vocab_size"])
        out, fed = _program_run(cell, seed, seconds, trace, spans, devices,
                                t_start, paths, work)
        # the program's state is freed before the reference runs
        out["checks"].update(compare(cell, seed, fed, streams,
                                     out.pop("steps_seen"), devices))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return out


def _program_run(cell, seed, seconds, trace, spans, devices, t_start, paths,
                 work):
    tr, cfg = cell["traffic"], cell["config"]
    B, S = tr["batch"] * cfg["mesh"]["data"], tr["seq_len"]
    sysm = system.TrainSystem(cell, seed, devices)
    sysm.compile((B, S))
    norms = jax.jit(sysm.fam.leaf_norms)
    change = sysm.fam.change_norms(sysm.init)
    pipe = system.open_pipeline(paths, B, S, seed)
    try:
        # -- the checked steps, through the window's own call and feed
        fed, losses = [], []
        for i in range(tr["checked_steps"]):
            raw = next(pipe)
            fed.append({k: v.copy() for k, v in raw.items()})
            metrics = sysm.run_step(sysm.put_batch(raw))
            losses.append(float(metrics["loss"]))
            if i == 0:
                global_gnorm = float(metrics["grad_norm"])
                b1 = sysm.fam.ADAM_B1
                gnorm = {k: float(v) / (1 - b1)
                         for k, v in norms(sysm.state.opt["m"]).items()}
        dnorm = {k: float(v) for k, v in change(sysm.state.params, sysm.key).items()}
        jax.block_until_ready(sysm.state)
        setup_s = time.perf_counter() - t_start

        # -- the window: at most two steps in flight
        steps, losses_w, prev = 0, [], None
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            with spans("pipeline.wait"):
                batch = sysm.put_batch(next(pipe))
            with spans("step.dispatch"):
                metrics = sysm.run_step(batch)
            if prev is not None:
                with spans("step.wait"):
                    prev.block_until_ready()
            prev = metrics["loss"]
            losses_w.append(prev)
            steps += 1
        jax.block_until_ready(sysm.state)
        window_s = time.perf_counter() - t0
        ms = {n: 1e3 * spans.total(n, t0, t0 + window_s) / max(steps, 1)
              for n in ("pipeline.wait", "step.dispatch", "step.wait")}
        print(f"chipbench: window {steps} steps in {window_s:.3f} s; per step "
              + ", ".join(f"{n} {v:.2f} ms" for n, v in ms.items()),
              file=sys.stderr)
        finite = bool(np.isfinite(np.asarray(jax.device_get(losses_w))).all())
        out = {"setup_s": setup_s, "steps": steps, "window_t0": t0,
               "window_s": window_s,
               "batch_tokens": B * S,
               "train_tokens_per_s": steps * B * S / window_s,
               "flops_per_token": sysm.fam.flops_per_token(cfg),
               "footprint_bytes": sysm.footprint_bytes()}
        if trace:
            out["trace"] = traced_steps(sysm, pipe, spans, tr["trace_steps"],
                                        work, devices)
        out["memory_peak_bytes"] = max(bench.memory_peak(devices),
                                       out["footprint_bytes"])
    finally:
        pipe.close()
    out["checks"] = {"window_loss_not_finite": 0 if finite else 1}
    out["steps_seen"] = (losses, gnorm, dnorm, global_gnorm)
    out["attempted"] = steps + tr["checked_steps"]
    out["failed"] = 0 if finite else steps
    return out, fed


def traced_steps(sysm, pipe, spans, n: int, work: str, devices) -> dict:
    """``n`` more steps under the profiler, reduced to busy and idle time."""
    trace_dir = os.path.join(work, "trace")
    spans.annotate = True
    try:
        with jax.profiler.trace(trace_dir):
            with spans("trace_window"):
                for _ in range(n):
                    with spans("pipeline.wait"):
                        batch = sysm.put_batch(next(pipe))
                    with spans("step.dispatch"):
                        sysm.run_step(batch)
                jax.block_until_ready(sysm.state)
    finally:
        spans.annotate = False
    red = tracing.reduce(tracing.events_from_xplane(trace_dir),
                         [d.id for d in devices])
    red["steps"] = n
    print(f"chipbench: traced {n} steps: busy {red['busy_s']:.4f} s of "
          f"{red['window_s']:.4f} s, collectives "
          f"{red.get('collective_s', 0.0):.4f} s, "
          f"{red.get('collective_exposed_s', 0.0):.4f} s of them exposed",
          file=sys.stderr)
    return red


def compare(cell: dict, seed: int, fed: list, streams: list, seen_steps,
            devices) -> dict:
    """The checked steps against the plain reference, run from the same seed
    on the stream's own windows."""
    losses, gnorm, dnorm, global_gnorm = seen_steps
    tr = cell["traffic"]
    index = window_index(streams, tr["seq_len"])
    w = tr["seq_len"] + 1
    rows_off, used, batches = 0, set(), []
    for raw in fed:
        tok, tgt = raw["tokens"], raw["targets"]
        wins = np.concatenate([tok, tgt[:, -1:]], axis=1)
        rows = []
        for r in range(tok.shape[0]):
            where = index.get(wins[r].tobytes())
            if (where is None or where in used
                    or not np.array_equal(tgt[r, :-1], tok[r, 1:])):
                rows_off += 1
                rows.append(wins[r])
                continue
            used.add(where)
            i, j = where
            rows.append(streams[i][j * w:(j + 1) * w])
        win = np.stack(rows)
        batches.append((jnp.asarray(win[:, :-1]), jnp.asarray(win[:, 1:])))
    fam = bench.family(cell)
    shardings, batch_sharding = fam.ref_placement(cell["config"], devices)
    ref = fam.ref_train(cell["config"], seed, batches, tr["optimizer"],
                        shardings=shardings, batch_sharding=batch_sharding)
    return gaps(losses, gnorm, dnorm, ref, rows_off, global_gnorm)


def gaps(losses, gnorm, dnorm, ref, rows_off, global_gnorm=None) -> dict:
    """The numbers the check can compare, each the worst over steps or
    leaves, or the median leaf's.  A leaf's gradient gap is the gap between
    the program's norm and the reference's, over the reference's norm of
    that leaf or of the median leaf, whichever is larger, since some
    gradients are all but zero.  A leaf's change gap is over the
    reference's change of that leaf alone: Adam moves every leaf that has
    a gradient by about the learning rate per element and step, so no
    change norm is near zero, and an update dropped or doubled on the
    smallest leaf reads 1.  Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone and are left
    out of the change."""
    rg, rd = ref["grad_norm"], ref["change_norm"]
    med_g = statistics.median(rg.values())
    g = {k: abs(gnorm[k] - rg[k]) / max(rg[k], med_g) for k in rg}
    d = {k: abs(dnorm[k] - rd[k]) / rd[k]
         for k in rd if rg[k] >= 1e-3 * med_g}
    steps = [abs(a - b) / abs(b) for a, b in zip(losses, ref["loss"])]
    out = {"rows_not_in_stream": rows_off,
           "loss_gap": max(steps), "loss_gap_first": steps[0],
           "grad_norm_gap": max(g.values()),
           "grad_norm_gap_median": statistics.median(g.values()),
           "change_norm_gap": max(d.values()),
           "change_norm_gap_median": statistics.median(d.values()),
           "worst_grad_leaf": max(g, key=g.get),
           "worst_change_leaf": max(d, key=d.get)}
    if global_gnorm is not None:
        ref_gn = ref["global_grad_norm"]
        out["global_grad_norm_gap"] = abs(global_gnorm - ref_gn) / ref_gn
    return out
