"""Bring-up check: the train -> checkpoint -> resume -> serve path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded train step on four chips

One chip runs, in this one process (a chip belongs to one process), the
phases

1. device: the platform must be ``tpu``; there is no CPU fallback;
2. kernels: every entry point of ``repro.kernels.ops``, compiled, at basket
   sizes, against ``repro.kernels.ref``;
3. train: ``repro.launch.train.main`` on rwkv6-1.6b at published widths cut
   to 4 layers, preempted after the step-3 checkpoint and resumed to step 6;
   then one device state through ``CheckpointManager`` with a pure-Python
   codec, so the save runs in the codec process pool while this process
   holds the chip, restored bit for bit;
4. serve: ``repro.launch.serve.main`` at full depth, 8 requests on 4 slots.

``--chips 4`` runs only the sharded train step of the same 4-layer model on
a (data=2, model=2) mesh and the same step on one chip, and compares them.

Times printed on the way (compile, phases) are set-up information, not
metrics.  The last line of standard output is the JSON result; a failed
phase exits non-zero without it.  Files go under
``artifacts/chip_smoke/``.  The compilation cache follows
``repro.launch.compile_cache``.
"""

from __future__ import annotations

import argparse
import ast
import contextlib
import io
import json
import math
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")
OUT = os.path.join(HERE, "artifacts", "chip_smoke")

ARCH = "rwkv6-1.6b"
SEED = 0
TRAIN_LAYERS = 4
TRAIN_ARGS = ["--arch", ARCH, "--n-layers", str(TRAIN_LAYERS),
              "--batch", "4", "--seq-len", "2048", "--steps", "6",
              "--ckpt-every", "3", "--log-every", "1"]
PREEMPT_AT = 3
SERVE_ARGS = ["--arch", ARCH, "--requests", "8", "--slots", "4",
              "--prompt-len", "64", "--max-new", "32", "--max-len", "256"]
KERNEL_N = 1 << 20                 # elements of a basket-sized tensor
QPACK_SHAPE = (4096, 2048)
CKPT_STATE_SHAPE = (2048, 4096)    # one device state for the pool round trip
SHARDED_BATCH = (4, 2048)


class PhaseError(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseError(msg)


def log(msg):
    print(msg, flush=True)


class _Tee(io.TextIOBase):
    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)
        return len(s)

    def flush(self):
        for st in self.streams:
            st.flush()


def run_main(main, argv):
    """Call an entry point's ``main(argv)`` in this process; returns
    (exit code, what it printed)."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(_Tee(sys.stdout, buf)):
        try:
            rc = main(argv)
        except SystemExit as e:       # argparse errors and explicit exits
            rc = e.code
    return rc, buf.getvalue()


class CompileLog:
    """Sums JAX's own compile events: backend compile-or-load seconds, and
    persistent-cache hits and misses (JAX writes an entry only for a miss
    whose compile passes its minimum time and size, so misses bound the
    entries written from above)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self, label):
        return (f"{label}: {self.programs} programs, {self.seconds:.1f}s "
                f"compiling or loading, persistent cache {self.hits} hits, "
                f"{self.misses} misses")


def _mem(dev):
    st = dev.memory_stats() or {}
    gb = lambda k: st.get(k, 0) / 1e9
    return (f"in use {gb('bytes_in_use'):.2f} GB, peak "
            f"{gb('peak_bytes_in_use'):.2f} GB of {gb('bytes_limit'):.2f} GB")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(want_chips):
    import jax
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"no TPU: JAX's first device is {d.platform!r} ({d.device_kind})")
    check(len(devs) >= want_chips,
          f"need {want_chips} chips, JAX found {len(devs)}")
    log(f"device: {d.platform} {d.device_kind!r} x{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _bits_equal(a, b):
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and \
        a.tobytes() == b.tobytes()


def phase_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    k1, k2, k3 = jax.random.split(jax.random.key(SEED), 3)
    n = KERNEL_N
    x = jax.random.normal(k1, (n,), jnp.float32)
    mat = jax.lax.bitcast_convert_type(x, jnp.uint8)          # (n, 4)

    def timed(name, fn, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        log(f"  {name}: first call {time.perf_counter() - t0:.2f}s")
        return out

    y = timed("bitshuffle", ops.bitshuffle_bytes, x)
    check(_bits_equal(y, ref.bitshuffle_ref(mat)), "bitshuffle != ref")
    back = timed("bitunshuffle", ops.bitunshuffle_bytes, y, jnp.float32, n)
    check(_bits_equal(back, x), "bitunshuffle does not invert bitshuffle")

    y = timed("byteshuffle", ops.byteshuffle_bytes, x)
    check(_bits_equal(y, ref.byteshuffle_ref(mat)), "byteshuffle != ref")
    back = timed("byteunshuffle", ops.byteunshuffle_bytes, y, jnp.float32, n)
    check(_bits_equal(back, x), "byteunshuffle does not invert byteshuffle")

    u = jax.random.bits(k2, (n,), jnp.uint32)                 # wraps mod 2^32
    d = timed("delta", ops.delta_u32, u)
    check(_bits_equal(d, ref.delta_ref(u)), "delta != ref")
    back = timed("undelta", ops.undelta_u32, d)
    check(_bits_equal(back, ref.undelta_ref(d)), "undelta != ref")
    check(_bits_equal(back, u), "undelta does not invert delta")

    g = jax.random.normal(k3, QPACK_SHAPE, jnp.float32).astype(jnp.bfloat16)
    qd, sd, shape = timed("quantize_int8", ops.quantize_int8, g)
    qr, sr = ref.qpack_ref(g)
    q, s, qr, sr = map(np.asarray, (qd, sd, qr, sr))
    # ref's contract: same per-row scale (f32 rounding), codes that differ
    # by at most a rounding tie, and dequantization within half a step
    np.testing.assert_allclose(s, sr, rtol=1e-6)
    off = np.abs(q.astype(np.int32) - qr.astype(np.int32))
    check(off.max() <= 1, f"qpack codes differ from ref by {off.max()}")
    out = timed("dequantize_int8", ops.dequantize_int8, qd, sd, shape,
                jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.qunpack_ref(q, s)),
                               rtol=1e-6)
    err = np.abs(np.asarray(out) - np.asarray(g, np.float32))
    check((err <= sr * 0.5 * (1 + 1e-6) + 1e-7).all(),
          "dequantized values further than half a step from the input")
    log(f"  qpack codes off by one (rounding ties): {int((off == 1).sum())} "
        f"of {off.size}")


def phase_train():
    import jax
    import numpy as np
    from repro.launch import train

    dev = jax.devices()[0]
    wd = os.path.join(OUT, "train")
    shutil.rmtree(wd, ignore_errors=True)
    args = TRAIN_ARGS + ["--workdir", wd]
    t0 = time.perf_counter()
    rc, _ = run_main(train.main, args + ["--simulate-preempt", str(PREEMPT_AT)])
    check(rc == 17, f"preempted run exited {rc}, expected 17")
    log(f"  preempted run: {time.perf_counter() - t0:.1f}s; device 0 {_mem(dev)}")
    t0 = time.perf_counter()
    rc, out = run_main(train.main, args)
    check(rc == 0, f"resumed run exited {rc}")
    log(f"  resumed run: {time.perf_counter() - t0:.1f}s; device 0 {_mem(dev)}")
    m = re.search(r"resumed from step (\d+) \(cursor (\{.*\})\)", out)
    check(m and int(m.group(1)) == PREEMPT_AT,
          f"no 'resumed from step {PREEMPT_AT}' line")
    cursor = ast.literal_eval(m.group(2))
    check((cursor["epoch"], cursor["file_idx"], cursor["window_idx"]) != (0, 0, 0),
          f"resumed data cursor {cursor} is the initial one")
    steps = [json.loads(line) for line in
             open(os.path.join(wd, "train_log.jsonl"))]
    total = int(TRAIN_ARGS[TRAIN_ARGS.index("--steps") + 1])
    check([s["step"] for s in steps] == list(range(1, total + 1)),
          f"logged steps {[s['step'] for s in steps]}")
    check(all(np.isfinite(s["loss"]) for s in steps),
          f"non-finite loss: {[s['loss'] for s in steps]}")
    log(f"  losses: {[round(s['loss'], 4) for s in steps]}")


def phase_checkpoint():
    """A device state through a pure-Python codec: the save runs in the
    forkserver process pool while this process holds the chip."""
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import CheckpointManager
    from repro.core import CompressionConfig
    from repro.core.bfile import BasketWriter
    from repro.core.codec import is_pure_python
    from repro.io import CompressionEngine

    k1, k2 = jax.random.split(jax.random.key(SEED + 1))
    state = {"w": jax.random.normal(k1, CKPT_STATE_SHAPE, jnp.bfloat16),
             "m": jax.random.normal(k2, CKPT_STATE_SHAPE, jnp.float32) * 1e-3,
             "step": jnp.asarray(7, jnp.int32)}
    want = jax.device_get(state)
    d = os.path.join(OUT, "ckpt")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)

    algo = "lz4"
    check(is_pure_python(algo), f"{algo} no longer routes to the process pool")
    with CompressionEngine(2) as eng:
        eng.warmup(algo)
        with BasketWriter(os.path.join(d, "pool.bskt"), engine=eng) as w:
            w.write_branch("m", want["m"], CompressionConfig(algo, 1, "bitshuffle4"))
        pids = list(eng._proc_pool._processes)
        check(pids, "no codec worker processes")
        for pid in pids:
            maps = open(f"/proc/{pid}/maps").read()
            check("libtpu" not in maps and "jaxlib" not in maps,
                  f"codec worker {pid} has JAX loaded")
    log(f"  codec pool workers {pids}: no JAX loaded")

    t0 = time.perf_counter()
    mgr = CheckpointManager(d, profile="analysis", workers=2)
    mgr.save(1, state, wait=True)
    got, _ = mgr.restore(template=state)
    for k in want:
        check(_bits_equal(got[k], want[k]), f"restored {k!r} differs")
    log(f"  CheckpointManager round trip ({algo}, process pool): "
        f"{time.perf_counter() - t0:.1f}s, bit-exact")


def phase_serve():
    from repro.launch import serve

    rc, out = run_main(serve.main, SERVE_ARGS)
    check(rc == 0, f"serve exited {rc}")
    m = re.search(r"(\d+) requests, (\d+) tokens", out)
    nreq = int(SERVE_ARGS[SERVE_ARGS.index("--requests") + 1])
    nnew = int(SERVE_ARGS[SERVE_ARGS.index("--max-new") + 1])
    check(m and int(m.group(1)) == nreq and int(m.group(2)) == nreq * nnew,
          f"expected {nreq} requests x {nnew} tokens, got {m and m.groups()}")


def phase_sharded():
    """Sharded train step on (data=2, model=2) vs the same step on one chip."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import Model
    from repro.parallel import (ParallelismConfig, batch_shardings,
                                opt_shardings, param_shardings)
    from repro.parallel.actctx import activation_context
    from repro.train import init_train_state, make_train_step
    from repro.train.step import TrainState

    cfg = dataclasses.replace(get_config(ARCH), n_layers=TRAIN_LAYERS)
    model = Model(cfg)
    mesh = make_host_mesh(data=2, model=2)
    pcfg = ParallelismConfig(zero3=True)
    psh = param_shardings(model, mesh, pcfg)
    osh = opt_shardings(model, mesh, pcfg)
    rep = NamedSharding(mesh, P())
    ssh = TrainState(params=psh, opt={"m": osh, "v": osh, "count": rep},
                     step=rep, err=None)
    B, S = SHARDED_BATCH
    tok = jax.random.randint(jax.random.key(SEED + 1), (B, S), 0, cfg.vocab)
    batch = {"tokens": tok, "targets": jnp.roll(tok, -1, 1)}
    bsh = batch_shardings(mesh, batch)
    # lr is nonzero at step 0, so the step moves every parameter
    step = make_train_step(model, peak_lr=1e-3, warmup=0, total_steps=10)

    state = jax.jit(lambda k: init_train_state(model, k),
                    out_shardings=ssh)(jax.random.key(SEED))
    before = jax.device_get(state.params)
    one = SingleDeviceSharding(jax.devices()[0])
    t0 = time.perf_counter()
    # a private one-chip copy, donated to the step: device_put alone may
    # hand back device 0's buffer of a replicated leaf, which the sharded
    # step still needs
    new1, m1 = jax.jit(step, donate_argnums=0)(
        jax.device_put(jax.tree.map(jnp.copy, state), one),
        jax.device_put(batch, one))
    upd1 = jax.tree.map(lambda a, b: np.asarray(a) - b,
                        jax.device_get(new1.params), before)
    m1 = {k: float(v) for k, v in m1.items()}
    del new1
    log(f"  one-chip step: {time.perf_counter() - t0:.1f}s (with compile)")

    t0 = time.perf_counter()
    with mesh, activation_context(mesh):
        f = jax.jit(step, in_shardings=(ssh, bsh), out_shardings=(ssh, rep))
        compiled = f.lower(state, batch).compile()
        new4, m4 = compiled(jax.device_put(state, ssh),
                            jax.device_put(batch, bsh))
        jax.block_until_ready(new4)
    log(f"  sharded step: {time.perf_counter() - t0:.1f}s (with compile)")
    ma = compiled.memory_analysis()
    log(f"  sharded step per device (memory_analysis): args "
        f"{ma.argument_size_in_bytes / 1e9:.2f} GB, out "
        f"{ma.output_size_in_bytes / 1e9:.2f} GB, temp "
        f"{ma.temp_size_in_bytes / 1e9:.2f} GB")
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(new4))
    shard_bytes = {d.id: 0 for d in mesh.devices.flat}
    for x in jax.tree.leaves(new4):
        for sh in x.addressable_shards:
            shard_bytes[sh.device.id] += sh.data.nbytes
    for d in mesh.devices.flat:
        log(f"  device {d.id}: holds {shard_bytes[d.id] / 1e9:.2f} GB of the "
            f"{state_bytes / 1e9:.2f} GB train state; {_mem(d)}")
    check(max(shard_bytes.values()) < 0.5 * state_bytes,
          "the sharded train state is not spread across the chips")

    m4 = {k: float(v) for k, v in m4.items()}
    upd4 = jax.tree.map(lambda a, b: np.asarray(a) - b,
                        jax.device_get(new4.params), before)
    compare_steps((m1, upd1), (m4, upd4))


def compare_steps(want, got):
    """Checks a step's (metrics, parameter update) against the one-chip
    step's on the same state and batch; raises PhaseError on a mismatch."""
    import jax
    import numpy as np
    (m1, upd1), (m4, upd4) = want, got
    # bf16 compute: bf16 keeps 8 significant bits, so one ulp is up to
    # 2^-7 relative.  The loss is reduced in f32 from bf16 logits: one ulp.
    # The gradient norm sums bf16 matmul partials that the two layouts
    # round in different places: four ulps.
    for k, tol in (("loss", 2 ** -7), ("grad_norm", 2 ** -5)):
        rel = abs(m4[k] - m1[k]) / abs(m1[k])
        log(f"  {k}: one chip {m1[k]:.6f}, sharded {m4[k]:.6f}, rel {rel:.2e}")
        check(rel <= tol, f"{k} differs by {rel:.2e}")
    # Adam's first step is lr * sign(g) where |g| >> eps, so the updates
    # differ only where a gradient element is near zero and its sign flips:
    # 0.2 allows 1% of the elements to flip
    num = sum(float(np.sum((a - b) ** 2)) for a, b in
              zip(jax.tree.leaves(upd4), jax.tree.leaves(upd1)))
    den = sum(float(np.sum(b ** 2)) for b in jax.tree.leaves(upd1))
    rel = math.sqrt(num / den)
    log(f"  parameter update: relative L2 difference {rel:.2e}")
    check(rel <= 0.2, f"sharded parameter update differs by {rel:.2e}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    compiles = CompileLog()
    os.makedirs(OUT, exist_ok=True)
    phases = [("sharded", phase_sharded)] if args.chips == 4 else [
        ("kernels", phase_kernels), ("train", phase_train),
        ("checkpoint", phase_checkpoint), ("serve", phase_serve)]
    t_all = time.perf_counter()
    try:
        device = phase_device(args.chips)
        log(f"compile cache: {cache_dir}")
        import jax
        for name, fn in phases:
            t0 = time.perf_counter()
            log(f"phase {name}")
            fn()
            log(f"phase {name}: ok in {time.perf_counter() - t0:.1f}s; "
                f"device 0 {_mem(jax.devices()[0])}")
    except Exception as e:                      # report, then fail the run
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    log(compiles.line("compile"))
    log(f"all phases ok in {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
