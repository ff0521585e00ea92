"""Serving driver: restore a checkpoint, serve batched requests.

The paper's "analysis" operating point: prompts stream from a compressed
BasketFile (decompression-speed-bound read path), the engine continuously
batches into cache slots, and generation statistics print at the end.

The serving mesh is ``data=1, model=--tp``: every layer is split over
``--tp`` chips by the program's sharding rules (heads, Mamba channels,
MLP and expert widths).  ``--ckpt-dir`` restores the params of the newest
checkpoint there (a train checkpoint's optimizer state stays on disk)
straight into that layout, each device receiving only its own shard.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --requests 32 --max-new 16
    PYTHONPATH=src python -m repro.launch.serve --arch jamba2-mini --reduced \
        --ckpt-dir <dir> --tp 4
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.configs import get_config, list_archs, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import Model
from repro.parallel import ParallelismConfig, param_shardings
from repro.parallel.actctx import activation_context
from repro.serve import ServeEngine


def serving_layout(model, tp: int):
    """The serving mesh (``data=1, model=tp``) and the params' shardings
    on it."""
    mesh = make_host_mesh(data=1, model=tp)
    return mesh, param_shardings(model, mesh, ParallelismConfig())


def restore_weights(mgr: CheckpointManager, shardings, step=None):
    """The arrays that ``shardings`` names, from checkpoint ``step`` of
    ``mgr`` (default: the newest), each put on the devices that hold it
    under its sharding as it decodes."""
    with obs.trace.span("serve.restore", cat="serve", step=step):
        tree, _ = mgr.restore(step, template=shardings, shardings=shardings)
    return tree


def _bf16(params):
    return jax.tree.map(
        lambda p: p.astype(jnp.bfloat16) if p.dtype == jnp.float32 else p, params)


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
    ap.add_argument("--tp", type=int, default=1,
                    help="chips each layer is split over (mesh data=1, model=tp)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.is_encdec or cfg.n_img_tokens:
        print(f"note: {cfg.name} serving uses the LM decoder path with "
              "stub modality inputs omitted")
    model = Model(cfg)
    mesh, psh = serving_layout(model, args.tp)

    if args.ckpt_dir:
        t0 = time.monotonic()
        params = _bf16(restore_weights(CheckpointManager(args.ckpt_dir),
                                       {"params": psh})["params"])
        jax.block_until_ready(params)
        print(f"restored {sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9:.3f}"
              f" GB of params onto {args.tp} device(s) in "
              f"{time.monotonic() - t0:.1f}s")
    else:
        params = jax.jit(lambda k: _bf16(model.init(k)),
                         out_shardings=psh)(jax.random.key(0))

    with mesh, activation_context(mesh):
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
