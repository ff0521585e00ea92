"""Cores-vs-throughput scaling per codec — the companion papers' headline
figure (*Increasing Parallelism in the ROOT I/O Subsystem*, arXiv:1804.03326
Fig. 3-style): basket-granular task parallelism lifts every codec's wall-
clock compression AND decompression throughput until the machine runs out
of cores.

For each codec we write the paper's artificial-tree-like float column
through ``BasketWriter(workers=N)`` and read it back with
``read_branch(workers=N)``, N in ``workers_list``; the ``speedup`` column
is vs N=1.  C-backed codecs scale on the thread pool (GIL released);
pure-Python codecs go through the engine's process pool, so they scale
too — at higher per-task overhead (visible as a lower speedup intercept).
"""

from __future__ import annotations

import os
import tempfile
import time

import numpy as np

from repro.core import CompressionConfig
from repro.core.bfile import BasketFile, BasketWriter
from repro.core.codec import is_pure_python
from repro.io import CompressionEngine, PrefetchReader

from .common import emit

_LEVEL = {"zlib": 6, "lzma": 2, "zstd": 3, "lz4": 1, "repro-deflate": 1}


def _payload(algo: str) -> np.ndarray:
    rng = np.random.default_rng(11)
    n_bytes = (2 << 20) if is_pure_python(algo) else (16 << 20)
    # low-entropy physics-like floats: compressible under bitshuffle
    return (rng.standard_normal(n_bytes // 4) * 0.001).astype(np.float32)


def run(out_csv: str | None = None,
        codecs=("zlib", "lzma", "zstd", "lz4"),
        workers_list=(1, 2, 4, 8)) -> list[dict]:
    rows = []
    with tempfile.TemporaryDirectory() as td:
        for algo in codecs:
            arr = _payload(algo)
            nbytes = arr.nbytes
            cfg = CompressionConfig(algo, _LEVEL.get(algo, 3), "bitshuffle4")
            base_w = base_r = None
            for workers in workers_list:
                path = os.path.join(td, f"{algo}_{workers}.bskt")
                # steady-state: pool pre-forked, shared by writer and reader;
                # process decompression opted in (pool amortized over the scan)
                with CompressionEngine(workers, unpack_processes=True) as eng:
                    eng.warmup(algo)
                    t0 = time.perf_counter()
                    with BasketWriter(path, engine=eng) as w:
                        w.write_branch("x", arr, cfg, 256 * 1024)
                    dt_w = time.perf_counter() - t0
                    reader = PrefetchReader(BasketFile(path), "x",
                                            ahead=4, engine=eng)
                    t0 = time.perf_counter()
                    reader.read_all()
                    dt_r = time.perf_counter() - t0
                    reader.close()
                base_w = base_w or dt_w
                base_r = base_r or dt_r
                rows.append({
                    "bench": "fig_parallel", "algo": algo,
                    "pure_python": int(is_pure_python(algo)),
                    "workers": workers,
                    "comp_MBps": round(nbytes / dt_w / 1e6, 1),
                    "decomp_MBps": round(nbytes / dt_r / 1e6, 1),
                    "comp_speedup": round(base_w / dt_w, 2),
                    "decomp_speedup": round(base_r / dt_r, 2),
                })
    emit(rows, out_csv)
    return rows


if __name__ == "__main__":
    run("artifacts/bench/fig_parallel.csv")
