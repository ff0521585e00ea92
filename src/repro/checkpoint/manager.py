"""Checkpointing through the paper's compression engine.

Every tensor in the train state is a *branch* in a BasketFile; the codec
policy (repro.core.policy) picks algo/level/preconditioner per tensor —
BitShuffle+zstd for float weights/moments, Delta+Shuffle for integer
step counters and offset-like tensors.  This is the paper's per-use-case
codec choice ("checkpoint" profile) applied at production scale.

Fault-tolerance invariants:
  * **atomic**: BasketWriter writes tmp-then-rename; a crash mid-save can
    never leave a loadable-but-wrong file, and the manifest (named
    ``MANIFEST-<step>.json``) is written only after the data file commits.
  * **async + streamed**: ``save()`` compresses/writes on a background
    thread while training continues.  Tensors are staged device→host in
    chunked, double-buffered ``copy_to_host_async`` slices that feed the
    basket compressor as they land (``staging="stream"``) — D2H transfer
    overlaps compression and peak host memory drops from ~2× state size
    (the old whole-tree snapshot) to ~``stage_depth`` baskets per
    producer.  jax arrays are immutable, so the background stream reads
    the live state safely; a training step that *donates* its state
    buffers must pass ``snapshot=True`` (or use ``staging="gather"``),
    which restores the old copy-then-write behavior.
  * **resumable**: ``latest_step()`` scans manifests, ignoring any step
    whose data file is missing/truncated.
  * **elastic re-shard**: tensors are saved *unsharded* (gathered to host);
    ``restore(shardings=...)`` device_puts each tensor with the target
    mesh's NamedSharding — restoring a 256-chip checkpoint onto 512 chips
    (or 8) is the same call with a different mesh.  ``load_pytree``
    device_puts each branch as it decodes, so the full host dict never
    materializes alongside the device copy, and each device receives only
    its own shard from the host: no whole tensor is assembled on one chip.
    ``ckpt.h2d_bytes`` counts what the puts send, once per device.
  * **retention**: ``keep`` most recent checkpoints are kept, the rest
    garbage-collected after a successful save.

Phases: every stage of a save and a restore is a ``ckpt.<phase>`` span
(:func:`_phase`) whose seconds also land in ``ckpt.phase_s{phase=...}``:
``save`` (around ``write_branch`` per tensor and ``commit``: TOC, fsync,
rename, directory fsync), ``snapshot`` (the host copy of
``save(snapshot=True)``), ``manifest``, ``gc``; ``load`` (around ``open``:
container, TOC and ``__meta__``; ``read_branch`` and ``device_put`` per
tensor).  Each basket's precondition, codec, checksum and I/O stages are
timed underneath them (``basket.stage_s``, :mod:`repro.core.basket`).
"""

from __future__ import annotations

import contextlib
import itertools
import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Optional

import jax
import numpy as np

from repro import obs
from repro.core.basket import basket_rows, split_array
from repro.core.bfile import (BasketFile, BasketWriter, CorruptBasketError,
                              TruncatedContainerError, _fsync_dir)
from repro.core.policy import choose

_LOG = logging.getLogger("repro.checkpoint")

__all__ = ["CheckpointManager", "save_pytree", "load_pytree"]

_TARGET_BASKET_BYTES = 1 << 20


@contextlib.contextmanager
def _phase(name: str, **args):
    """The span ``ckpt.<name>``, its seconds also in the histogram
    ``ckpt.phase_s{phase=<name>}``: the ring is drained by its readers,
    the histogram's sums stay."""
    with obs.trace.span("ckpt." + name, cat="ckpt", **args), \
            obs.histogram("ckpt.phase_s", phase=name).time():
        yield


def _flatten_with_paths(tree) -> dict[str, Any]:
    flat = {}

    def rec(node, prefix):
        if isinstance(node, dict):
            for k in sorted(node):
                rec(node[k], f"{prefix}{k}.")
        elif node is None:
            flat[prefix.rstrip(".") + "#none"] = None
        else:
            flat[prefix.rstrip(".")] = node

    rec(tree, "")
    return flat


def _is_bf16(x) -> bool:
    return str(getattr(x, "dtype", "")) == "bfloat16"


def _np_view(x) -> np.ndarray:
    arr = np.asarray(jax.device_get(x))
    if arr.dtype.name == "bfloat16":        # store as raw uint16 bit pattern
        arr = arr.view(np.uint16)
    return arr


def _h2d_bytes(arr: np.ndarray, sharding) -> int:
    """Bytes a put of host ``arr`` under ``sharding`` sends: each device's
    shard, counted once for every device it lands on."""
    shards = sharding.addressable_devices_indices_map(arr.shape).values()
    return sum(arr[idx].nbytes for idx in shards)


def _entry_stats(stats: dict, entry: dict) -> None:
    stats["branches"] += 1
    stats["raw"] += sum(b["meta"]["orig_len"] for b in entry["baskets"])
    stats["comp"] += sum(b["meta"]["comp_len"] for b in entry["baskets"])


# ---------------------------------------------------------------------------
# device→host staging
# ---------------------------------------------------------------------------

def _device_chunk_stream(x, rows_per: int, bf16: bool, stage_depth: int = 2):
    """Yield (start, count, host buffer) row-slices of a device array.

    Up to ``stage_depth`` slices are in flight: each is sliced on device
    and started toward the host with ``copy_to_host_async`` before the
    previous one is consumed, so D2H transfer overlaps the caller's
    compression.  Chunk boundaries equal :func:`split_array`'s
    (``basket_rows``), keeping the container byte-identical to the
    gather-then-split path."""
    n = x.shape[0]
    pending: deque = deque()
    starts = range(0, n, rows_per)
    it = iter(starts)
    exhausted = False
    while pending or not exhausted:
        while not exhausted and len(pending) < max(stage_depth, 1):
            try:
                s = next(it)
            except StopIteration:
                exhausted = True
                break
            sl = x[s:min(s + rows_per, n)]
            sl.copy_to_host_async()
            pending.append((s, sl))
        if pending:
            s, sl = pending.popleft()
            arr = np.asarray(sl)
            if bf16:
                arr = arr.view(np.uint16)
            arr = np.ascontiguousarray(arr)
            yield s, arr.shape[0], memoryview(arr).cast("B")


def _branch_cfg(name: str, probe: np.ndarray, profile: str, tuner,
                bf16: bool = False):
    """Static policy or measured tuner decision for one branch probe.  A
    bfloat16 tensor is stored as its uint16 bit pattern; the static policy
    still sees it as bfloat16, and so picks a float preconditioner."""
    if tuner is not None:
        return tuner.config_for(name, probe)
    return choose(name, probe.view(jax.numpy.bfloat16) if bf16 else probe,
                  profile)


def _branch_stream(name: str, val, profile: str,
                   target_basket_bytes: int = _TARGET_BASKET_BYTES,
                   stage_depth: int = 2, tuner=None):
    """(dtype_str, shape, chunk_iter, cfg) for one tensor.

    Device arrays stream through :func:`_device_chunk_stream`; host arrays
    split into zero-copy views.  The codec policy (or tuner) probes only
    the first staged chunk — stratified windows of that chunk — so no
    full-tensor host copy is ever made.  The gather path probes the whole
    array, so a device tensor whose statistics differ between its first
    basket and the rest may pick a different (still correct) config than
    the gather path; contents always round-trip."""
    if not isinstance(val, jax.Array) or val.ndim == 0 or val.shape[0] == 0:
        arr = _np_view(val)
        return (arr.dtype.str, arr.shape,
                split_array(arr, target_basket_bytes),
                _branch_cfg(name, arr, profile, tuner, _is_bf16(val)))
    bf16 = _is_bf16(val)
    np_dtype = np.dtype(np.uint16) if bf16 else np.dtype(val.dtype)
    shape = tuple(val.shape)
    rows_per = basket_rows(shape, np_dtype.itemsize, target_basket_bytes)
    chunks = _device_chunk_stream(val, rows_per, bf16, stage_depth)
    first = next(chunks)
    probe = np.frombuffer(first[2], dtype=np_dtype)
    cfg = _branch_cfg(name, probe, profile, tuner, bf16)
    return (np_dtype.str, shape, itertools.chain([first], chunks), cfg)


def save_pytree(path: str, tree, profile: str = "checkpoint",
                extra_meta: Optional[dict] = None,
                workers: int = 0, producers: int = 1,
                staging: str = "stream", stage_depth: int = 2,
                tuner=None, objective=None, parity: int = 0) -> dict:
    """Write a pytree of (host or device) arrays as one BasketFile.

    ``workers>0`` compresses each tensor's baskets in parallel through the
    I/O engine.  ``producers>1`` additionally shards the *tensor list*
    across producer threads, each compressing its shard into an in-memory
    BasketBuffer drained by a BufferMerger (ROOT's TBufferMerger pattern) —
    one output file, no recompression, no serialized compression.  Note:
    with ``producers>1`` branch order (hence container bytes) depends on
    thread timing; contents still round-trip identically (restore is
    name-keyed).  Byte-determinism holds for ``producers<=1`` at any
    ``workers`` and either ``staging`` mode (identical basket boundaries).

    ``staging="stream"`` (default) never materializes a tensor on host:
    device arrays stage down in ≤``stage_depth`` in-flight basket-sized
    ``copy_to_host_async`` slices that feed the compressor as they land —
    peak extra host memory is ~``stage_depth`` baskets per producer
    instead of the whole tree.  ``staging="gather"`` is the old behavior
    (full ``device_get`` per tensor before compression).

    ``objective=`` (or an explicit ``tuner=``) switches per-branch codec
    selection from the static ``profile`` heuristic to measurement-driven
    tuning (repro.tune): each tensor's config is chosen from trial
    compressions on sampled payloads, decisions persist in the file
    header, and a manager-held tuner reuses them across steps.

    ``parity=k`` additionally writes a ``<path>.parity`` XOR sidecar
    (DESIGN.md §15) so a later bit-rotted basket heals in place on
    restore — the container bytes themselves are unchanged."""
    if staging not in ("stream", "gather"):
        raise ValueError(f"staging must be 'stream' or 'gather', got {staging!r}")
    if tuner is None and objective is not None:
        from repro.tune import Tuner
        tuner = Tuner(objective, fallback_profile=profile)
    flat = {n: v for n, v in _flatten_with_paths(tree).items() if v is not None}
    stats = {"branches": 0, "raw": 0, "comp": 0}
    bf16_paths = [n for n, v in flat.items() if _is_bf16(v)]
    meta = {"bf16": bf16_paths}
    if extra_meta:
        meta.update(extra_meta)
    meta_blob = json.dumps(meta).encode()

    def branch_args(name):
        if staging == "stream":
            return _branch_stream(name, flat[name], profile,
                                  stage_depth=stage_depth, tuner=tuner)
        arr = _np_view(flat[name])
        return (arr.dtype.str, arr.shape,
                split_array(arr, _TARGET_BASKET_BYTES),
                _branch_cfg(name, arr, profile, tuner, _is_bf16(flat[name])))

    def lend_engine(engine):
        # trial matrices fan out through the write's own engine (C-codec
        # pools); returns a restore callback — a manager-held tuner must
        # not keep a reference to an engine that closes with this save
        if tuner is not None and tuner.engine is None and engine is not None:
            tuner.engine = engine
            return lambda: setattr(tuner, "engine", None)
        return lambda: None

    if producers <= 1:
        with _phase("save", path=path, branches=len(flat)), \
                obs.profile.mem_phase("ckpt.save"), \
                BasketWriter(path, workers=workers, tuner=tuner,
                             parity=parity) as w:
            unlend = lend_engine(w._engine)
            try:
                for name in flat:
                    dtype, shape, chunks, cfg = branch_args(name)
                    with _phase("write_branch", branch=name):
                        _entry_stats(stats, w.write_branch_chunks(
                            name, dtype=dtype, shape=shape, chunks=chunks,
                            cfg=cfg))
                w.write_blob("__meta__", meta_blob)
                with _phase("commit"):
                    w.close()
            finally:
                unlend()
        return stats

    from repro.io.merger import BufferMerger
    names = list(flat)
    shards = [names[i::producers] for i in range(producers)]
    errors: list = []
    lock = threading.Lock()
    with _phase("save", path=path, branches=len(flat)), \
            obs.profile.mem_phase("ckpt.save"), \
            BufferMerger(path, workers=workers, tuner=tuner,
                         parity=parity) as m:
        unlend = lend_engine(m._engine)

        def produce(shard):
            try:
                for name in shard:
                    buf = m.buffer()
                    dtype, shape, chunks, cfg = branch_args(name)
                    with _phase("write_branch", branch=name):
                        entry = buf.write_branch_chunks(
                            name, dtype=dtype, shape=shape, chunks=chunks,
                            cfg=cfg)
                    m.merge(buf)
                    with lock:
                        _entry_stats(stats, entry)
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        threads = [threading.Thread(target=produce, args=(s,), daemon=True)
                   for s in shards if s]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            unlend()
        if errors:
            raise errors[0]
        buf = m.buffer()
        buf.write_blob("__meta__", meta_blob)
        m.merge(buf)
        with _phase("commit"):
            m.close()
    return stats


def load_pytree(path: str, template=None, shardings=None, workers: int = 4,
                prefetch: int = 0, heal: Optional[str] = None):
    """Read a BasketFile back into a pytree.

    ``template``: pytree whose structure/leaf-Nones define the output (leaf
    values unused); only its branches are read, so a serving process can
    take the params of a train checkpoint and leave the optimizer state on
    disk.  Without it, a flat {dotted-path: array} dict of every branch
    returns.
    ``shardings``: matching pytree of NamedShardings -> device_put per leaf
    (elastic re-shard).  ``prefetch>0`` = decompress-ahead reads.

    Branches are ``device_put`` *as they decode* (when a sharding is
    given), so the host copy of each tensor is dropped immediately instead
    of the whole host dict coexisting with the device tree.

    ``heal="auto"``: a checksum-failing basket is reconstructed in place
    from the ``<path>.parity`` sidecar (when one exists) before the read
    fails — the restore-side half of ``save_pytree(parity=k)``."""
    flat_s = _flatten_with_paths(shardings) if shardings is not None else {}
    with _phase("load", path=path), obs.profile.mem_phase("ckpt.load"), \
            contextlib.ExitStack() as files:
        with _phase("open"):
            f = files.enter_context(BasketFile(
                path, workers=workers, prefetch=prefetch, heal=heal))
            meta = json.loads(bytes(f.read_branch("__meta__")).decode())
        bf16 = set(meta.get("bf16", []))

        def read(name):
            with _phase("read_branch", branch=name):
                arr = f.read_branch(name, workers=workers)
            if name in bf16:
                arr = arr.view(jax.numpy.bfloat16.dtype)
            sh = flat_s.get(name)
            if sh is None:
                return arr
            # staging symmetry: put each branch on device now, free host
            with _phase("device_put"):
                out = jax.device_put(arr, sh)
            obs.counter("ckpt.h2d_bytes").inc(_h2d_bytes(arr, sh))
            return out

        names = [n for n in f.branch_names() if n != "__meta__"]
        if template is not None:
            flat_t = _flatten_with_paths(template)
            names = [n for n in names if n in flat_t]
        flat = {n: read(n) for n in names}
    if template is None:
        return flat, meta

    def rebuild(node, prefix):
        if isinstance(node, dict):
            return {k: rebuild(node[k], f"{prefix}{k}.") for k in sorted(node)}
        key = prefix.rstrip(".")
        if node is None or key + "#none" in flat_t:
            return None
        return flat[key]

    return rebuild(template, ""), meta


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, profile: str = "checkpoint",
                 workers: int = 0, producers: int = 1,
                 tune: bool = False, objective=None, parity: int = 0):
        self.dir = str(directory)
        os.makedirs(self.dir, exist_ok=True)
        self.keep = keep
        self.profile = profile
        self.workers = workers        # basket-parallel compression width
        self.producers = producers    # tensor-parallel producer threads (merger)
        self.parity = int(parity)     # XOR parity sidecar stripe width (0 = off)
        # measurement-driven codec selection: one tuner lives for the
        # manager's lifetime, so step N+1 reuses step N's decisions (zero
        # re-measurement) and the drift detector spans steps
        self._tuner = None
        if tune or objective is not None:
            from repro.tune import OBJECTIVES, Tuner
            obj = objective if objective is not None else (
                profile if profile in OBJECTIVES else "checkpoint")
            self._tuner = Tuner(obj, fallback_profile=profile)
        self._worker: Optional[threading.Thread] = None
        self._last_stats: Optional[dict] = None
        self._error: Optional[BaseException] = None

    # -- paths -----------------------------------------------------------

    def _data_path(self, step: int) -> str:
        return os.path.join(self.dir, f"ckpt-{step:08d}.bskt")

    def _manifest_path(self, step: int) -> str:
        return os.path.join(self.dir, f"MANIFEST-{step:08d}.json")

    # -- save ------------------------------------------------------------

    def save(self, step: int, tree, extra_meta: Optional[dict] = None,
             wait: bool = False, snapshot: bool = False) -> None:
        """Compress+write in the background; training continues.

        By default no host snapshot is taken: the background thread stages
        each (immutable) device tensor down in basket-sized double-buffered
        slices, overlapping D2H with compression and bounding peak host
        memory at a few baskets instead of a full state copy.
        ``snapshot=True`` restores the old gather-everything-first behavior
        — required when the training step *donates* the state buffers (a
        donated array must not be read after the next step dispatches; a
        donated-away array makes the background save fail, and that
        failure re-raises from the next ``save()``/``wait()``)."""
        self.wait()                                   # one in flight at a time
        if self._tuner is not None and not self._tuner.decisions:
            # re-open: seed the tuner from the latest checkpoint's header
            # so resumed runs never re-measure what a prior run decided
            last = self.latest_step()
            if last is not None:
                from repro.tune import load_decisions
                try:
                    self._tuner.load(load_decisions(self._data_path(last)))
                except Exception:
                    pass            # unreadable/malformed header: just re-tune
        if snapshot:
            with _phase("snapshot"):
                src = jax.tree.map(
                    lambda x: None if x is None
                    else np.asarray(jax.device_get(x)),
                    tree, is_leaf=lambda x: x is None)
        else:
            src = tree

        def work():
            try:
                t0 = time.monotonic()
                stats = save_pytree(self._data_path(step), src,
                                    self.profile, extra_meta,
                                    workers=self.workers,
                                    producers=self.producers,
                                    staging="stream",
                                    tuner=self._tuner,
                                    parity=self.parity)
                manifest = {"step": step, "time": time.time(),
                            "wall_s": time.monotonic() - t0, **stats}
                with _phase("manifest"):
                    self._write_manifest(step, manifest)
                self._last_stats = manifest
                with _phase("gc"):
                    self._gc()
            except BaseException as e:   # surfaced by the next save()/wait()
                self._error = e

        self._worker = threading.Thread(target=work, daemon=True)
        self._worker.start()
        if wait:
            self.wait()

    def _write_manifest(self, step: int, manifest: dict) -> None:
        # atomic commit: tmp + fsync + rename + fsync dir — the manifest
        # is the "this step exists" marker, so it must never be observable
        # half-written (or survive a crash pointing at a container the
        # kernel never flushed)
        tmp = self._manifest_path(step) + ".tmp"
        try:
            with open(tmp, "w") as fh:
                json.dump(manifest, fh)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, self._manifest_path(step))
        except BaseException:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise
        _fsync_dir(self.dir)

    def wait(self) -> Optional[dict]:
        """Join any in-flight save; re-raises a background-save failure (a
        silently lost checkpoint must not look like a successful one)."""
        if self._worker is not None:
            self._worker.join()
            self._worker = None
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError("background checkpoint save failed "
                               "(state donated before the save finished? "
                               "pass save(..., snapshot=True))") from err
        return self._last_stats

    # -- restore ---------------------------------------------------------

    def steps(self) -> list[int]:
        out = []
        for fn in os.listdir(self.dir):
            if fn.startswith("MANIFEST-") and fn.endswith(".json"):
                step = int(fn[len("MANIFEST-"):-len(".json")])
                if os.path.exists(self._data_path(step)):
                    out.append(step)
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        st = self.steps()
        return st[-1] if st else None

    def restore(self, step: Optional[int] = None, template=None,
                shardings=None):
        """Load a step (default latest).  Returns (tree, meta).

        Every load opens with ``heal="auto"``, so a bit-rotted basket in a
        ``parity=k``-saved checkpoint is first repaired in place.  With
        ``step=None`` the manager additionally walks known steps newest →
        oldest: a checkpoint that is torn or corrupt *beyond healing*
        is skipped (logged, ``repair.ckpt.skipped``) and the previous
        known-good step loads instead — a rotted latest checkpoint costs a
        few steps of retraining, never the run.  An explicit ``step=``
        means "this step or nothing": the heal is still attempted but the
        failure surfaces to the caller."""
        if step is not None:
            return load_pytree(self._data_path(step), template, shardings,
                               heal="auto")
        candidates = sorted(self.steps(), reverse=True)
        if not candidates:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        skipped: list[tuple[int, str]] = []
        for s in candidates:
            try:
                return load_pytree(self._data_path(s), template, shardings,
                                   heal="auto")
            except (CorruptBasketError, TruncatedContainerError) as e:
                _LOG.warning("checkpoint step %d unloadable (%s); "
                             "falling back to previous step", s, e)
                obs.counter("repair.ckpt.skipped").inc()
                skipped.append((s, str(e)))
        from repro.core.basket import ChecksumError
        raise ChecksumError(
            "every checkpoint in %s is corrupt beyond healing; skipped %s"
            % (self.dir, "; ".join(f"step {s}: {m}" for s, m in skipped)))

    # -- retention -------------------------------------------------------

    def _gc(self):
        from repro.io import fdcache
        steps = self.steps()
        for s in steps[: max(len(steps) - self.keep, 0)]:
            for p in (self._data_path(s), self._manifest_path(s),
                      self._data_path(s) + ".parity",
                      self._data_path(s) + ".scrub"):
                fdcache.invalidate(p)   # a cached fd would pin the inode
                try:
                    os.remove(p)
                except FileNotFoundError:
                    pass
