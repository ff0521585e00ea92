"""Serving driver: restore a checkpoint, serve batched requests.

The paper's "analysis" operating point: prompts stream from a compressed
BasketFile (decompression-speed-bound read path), the engine continuously
batches into cache slots, and generation statistics print at the end.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --requests 32 --max-new 16
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, list_archs, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serve import ServeEngine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.is_encdec or cfg.n_img_tokens:
        print(f"note: {cfg.name} serving uses the LM decoder path with "
              "stub modality inputs omitted")
    model = Model(cfg)

    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        flat, _ = mgr.restore()
        raise SystemExit("checkpoint serving wired via examples/serve_lm.py")
    params = jax.tree.map(
        lambda p: p.astype(jnp.bfloat16) if p.dtype == jnp.float32 else p,
        model.init(jax.random.key(0)))

    eng = ServeEngine(model, params, batch_slots=args.slots,
                      max_len=args.max_len, eos_id=-1,
                      temperature=args.temperature)
    rng = np.random.default_rng(0)
    t0 = time.monotonic()
    for r in range(args.requests):
        eng.submit(rng.integers(2, cfg.vocab, args.prompt_len), args.max_new)
    out = eng.run()
    dt = time.monotonic() - t0
    n_tok = sum(len(v) for v in out.values())
    print(f"{len(out)} requests, {n_tok} tokens in {dt:.1f}s "
          f"({n_tok/dt:.1f} tok/s, slots={args.slots})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
