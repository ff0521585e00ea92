"""End-to-end training driver.

Wires every subsystem together: config registry -> model -> data pipeline
(compressed BasketFile shards) -> sharded train step -> checkpoint manager
(async, atomic, compressed) -> restart/resume.  ``--reduced`` shrinks
every dimension (CPU tests); ``--n-layers`` cuts depth only and keeps the
published widths (one chip).

Fault-tolerance drill (exercised by tests/test_serve_driver.py):
  * kill the process at any step; re-running resumes from the latest
    checkpoint INCLUDING the data-pipeline cursor — no token skew;
  * --simulate-preempt N exits abruptly after N steps to make that drill
    reproducible.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
        --steps 200 --workdir /tmp/run1
    PYTHONPATH=src python -m repro.launch.train --arch rwkv6-1.6b \
        --n-layers 4 --batch 4 --seq-len 2048 --steps 6 --workdir run1
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, list_archs, reduced
from repro.data import TokenPipeline, write_token_shards
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.train import abstract_train_state, init_train_state, make_train_step
from repro.train.step import TrainState


def build_batch(cfg, raw, accum: int):
    """numpy pipeline batch -> model batch (adds modality stubs)."""
    b = {k: jnp.asarray(v) for k, v in raw.items()}
    B, S = b["tokens"].shape
    if cfg.is_encdec:
        b["frames"] = jnp.ones((B, min(S, 64), cfg.d_model), jnp.float32) * 0.01
    if cfg.n_img_tokens:
        b["patches"] = jnp.ones((B, cfg.n_img_tokens, cfg.d_model), jnp.float32) * 0.01
    if accum > 1:
        b = {k: v.reshape((accum, B // accum) + v.shape[1:]) for k, v in b.items()}
    return b


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale config (same structure)")
    ap.add_argument("--n-layers", type=int, default=0,
                    help="depth cut: keep this many layers (a multiple of "
                         "the layer pattern) at the published widths")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--n-shards", type=int, default=4)
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("--n-hosts", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--simulate-preempt", type=int, default=0,
                    help="exit(17) after N steps (fault-tolerance drill)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.n_layers:
        if args.n_layers % len(cfg.pattern):
            ap.error(f"--n-layers {args.n_layers} is not a multiple of the "
                     f"{len(cfg.pattern)}-layer pattern of {cfg.name}")
        print(f"cut: {cfg.name} n_layers {cfg.n_layers} -> {args.n_layers} "
              "(depth only; widths as configured)", flush=True)
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)
    enable_compile_cache()
    model = Model(cfg)

    # ---- data: write shards once, stream with restart cursor
    os.makedirs(args.workdir, exist_ok=True)
    shard_dir = os.path.join(args.workdir, "data")
    shards = [os.path.join(shard_dir, f"shard-{i:03d}.bskt")
              for i in range(args.n_shards)]
    if not all(os.path.exists(p) for p in shards):
        write_token_shards(
            shards, vocab=cfg.vocab,
            tokens_per_shard=max((args.seq_len + 1) * args.batch * 32, 20000))
    pipe = TokenPipeline(shards, batch=args.batch, seq_len=args.seq_len,
                         host_id=args.host_id, n_hosts=args.n_hosts)

    # ---- state: fresh or resumed.  A resume builds its template from
    # shapes alone and puts each tensor on the device as it decodes, so the
    # device never holds a second (freshly initialised) train state.
    mgr = CheckpointManager(os.path.join(args.workdir, "ckpt"), keep=2)
    start_step = 0
    if mgr.latest_step() is not None:
        abstract = abstract_train_state(model,
                                        compress_grads=args.compress_grads)
        tmpl = dict(vars(abstract))
        dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        tree, meta = mgr.restore(template=tmpl,
                                 shardings=jax.tree.map(lambda _: dev, tmpl))
        state = TrainState(**tree)
        del tree          # a second name would keep the restored state alive
        if "data_cursor" in meta:
            pipe.load_state_dict(meta["data_cursor"])
        start_step = int(state.step)
        print(f"resumed from step {start_step} (cursor {meta.get('data_cursor')})")
    else:
        state = init_train_state(model, jax.random.key(0),
                                 compress_grads=args.compress_grads)

    # the step donates the state: it updates in place, so the device holds
    # one train state (plus temporaries), never the step's input and output
    step_fn = jax.jit(make_train_step(
        model, peak_lr=args.lr, warmup=max(args.steps // 20, 5),
        total_steps=args.steps, accum=args.accum,
        compress_grads=args.compress_grads), donate_argnums=0)

    log_path = os.path.join(args.workdir, "train_log.jsonl")
    t0 = time.monotonic()
    toks_done = 0
    with open(log_path, "a") as logf:
        for i in range(start_step, args.steps):
            raw = next(pipe)
            batch = build_batch(cfg, raw, args.accum)
            state, metrics = step_fn(state, batch)
            toks_done += args.batch * args.seq_len
            if (i + 1) % args.log_every == 0 or i + 1 == args.steps:
                m = {k: float(v) for k, v in metrics.items()}
                m.update(step=i + 1,
                         tok_per_s=toks_done / (time.monotonic() - t0))
                logf.write(json.dumps(m) + "\n")
                logf.flush()
                print(f"step {i+1:5d} loss={m['loss']:.4f} "
                      f"acc={m['accuracy']:.3f} {m['tok_per_s']:.0f} tok/s")
            if (i + 1) % args.ckpt_every == 0 or i + 1 == args.steps:
                # the next step donates these buffers, so the save copies
                # the state to the host first and compresses it from there
                mgr.save(i + 1, dict(vars(state)), snapshot=True,
                         extra_meta={"data_cursor": pipe.state_dict(),
                                     "arch": cfg.name})
            if args.simulate_preempt and (i + 1) >= args.simulate_preempt \
                    and i + 1 < args.steps:
                mgr.wait()
                print(f"simulated preemption at step {i+1}", flush=True)
                pipe.close()
                return 17
    stats = mgr.wait()
    if stats:
        print(f"final ckpt: {stats['branches']} branches "
              f"ratio={stats['raw']/max(stats['comp'],1):.2f}x")
    pipe.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
