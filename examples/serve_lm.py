"""Serve batched requests against a trained checkpoint (continuous
batching) — the paper's decompression-speed-bound "analysis" side.

Trains briefly if no checkpoint exists, then restores and serves.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""

import os
import sys
import time

sys.path.insert(0, "src")

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402
import numpy as np                           # noqa: E402

from repro.checkpoint import CheckpointManager      # noqa: E402
from repro.configs import get_config, reduced       # noqa: E402
from repro.launch.serve import restore_weights, serving_layout  # noqa: E402
from repro.launch.train import main as train_main   # noqa: E402
from repro.models import Model                      # noqa: E402
from repro.serve import ServeEngine                 # noqa: E402

WORKDIR = "/tmp/repro_serve_lm"


def main():
    cfg = reduced(get_config("qwen3-8b"))
    model = Model(cfg)
    mgr = CheckpointManager(os.path.join(WORKDIR, "ckpt"))
    if mgr.latest_step() is None:
        print("no checkpoint — training 60 quick steps first...")
        train_main(["--arch", "qwen3-8b", "--reduced", "--steps", "60",
                    "--batch", "4", "--seq-len", "64", "--ckpt-every", "60",
                    "--workdir", WORKDIR])
    _, psh = serving_layout(model, tp=1)
    params = restore_weights(mgr, {"params": psh})["params"]
    print(f"restored the params of step {mgr.latest_step()}")
    params = jax.tree.map(
        lambda p: p.astype(jnp.bfloat16) if p.dtype == jnp.float32 else p,
        params)

    eng = ServeEngine(model, params, batch_slots=4, max_len=96, eos_id=-1,
                      temperature=0.7, seed=1)
    rng = np.random.default_rng(0)
    t0 = time.monotonic()
    for i in range(12):
        eng.submit(rng.integers(2, cfg.vocab, 8), max_new=12)
    out = eng.run()
    dt = time.monotonic() - t0
    tok = sum(len(v) for v in out.values())
    print(f"served {len(out)} requests / {tok} tokens in {dt:.1f}s "
          f"({tok/dt:.1f} tok/s)")
    for rid in sorted(out)[:3]:
        print(f"  req {rid}: {out[rid].tolist()}")


if __name__ == "__main__":
    main()
