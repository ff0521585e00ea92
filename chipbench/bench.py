"""The harness: finds a cell's files by name, holds the run to the chip,
records spans, and assembles the result line.

A cell is ``workloads/<cell>.json`` (configuration, traffic, chips and the
limits of its correctness check).  The configuration is
``configs/<config>.json`` and its ``family`` names ``models/<family>.py``;
the traffic is ``traffic/<traffic>.json`` and its ``driver`` names
``drivers/<driver>.py``.  A per-layer metric is ``metrics/<metric>.py``
with a ``read(ctx)`` that returns a number, or None where the run has
nothing for it to read.  Which metrics a cell reports is what
``BENCHMARK.json`` lists for it.  Nothing here names a cell, a
configuration or a metric.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".chipbench_work")


class NoChip(RuntimeError):
    """JAX found no accelerator, too few chips, or a chip the peak table
    does not list."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, base: str) -> dict:
    """Everything a run of cell ``name`` needs, read from files under
    ``base``."""
    wl = load_json(os.path.join(base, "workloads", f"{name}.json"))
    cfg = load_json(os.path.join(base, "configs", f"{wl['config']}.json"))
    traffic = load_json(os.path.join(base, "traffic", f"{wl['traffic']}.json"))
    return {"name": name, "workload": wl, "config": cfg, "traffic": traffic,
            "chips": wl["chips"], "limits": wl["limits"], "base": base}


def family(cell: dict):
    base = cell["base"]
    fam = cell["config"]["family"]
    return load_module(os.path.join(base, "models", f"{fam}.py"),
                       f"chipbench_family_{fam}")


def driver(cell: dict):
    drv = cell["traffic"]["driver"]
    return load_module(os.path.join(cell["base"], "drivers", f"{drv}.py"),
                       f"chipbench_driver_{drv}")


def metric_reader(name: str, base: str):
    path = os.path.join(base, "metrics", f"{name}.py")
    return load_module(path, "chipbench_metric_" + name.replace(".", "_"))


def cell_metrics(bench: dict, cell: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries of ``BENCHMARK.json`` that
    cell ``cell`` reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (cell in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    return e2e, per


def peaks(kind: str, base: str) -> dict:
    table = load_json(os.path.join(base, "peaks.json"))["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in peaks.json "
                     f"(known: {sorted(table)})")
    return table[kind]


def find_chips(chips: int, base: str) -> dict:
    """The devices the cell runs on; raises NoChip rather than fall back."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {d.platform!r} "
                     f"({d.device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks(d.device_kind, base)
    return {"platform": d.platform, "kind": d.device_kind, "count": chips,
            "devices": devs[:chips]}


def enable_compile_cache() -> str:
    """The program's compile-cache rule (a fixed directory in the
    checkout), with every program cached however short its compile, so
    that only the first run of a cell compiles."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache as program_rule
    path = program_rule()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileLog:
    """JAX's own compile events: backend compile-or-load seconds and
    persistent-cache hits and misses, so a compile inside the window shows."""

    def __init__(self):
        import jax
        self.programs = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1
            self.times.append(time.perf_counter())

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Spans:
    """The benchmark's own host spans around its calls into each layer.
    Under a profiler each span is also a ``TraceAnnotation``, so the trace
    can name what the host did in a device idle gap."""

    def __init__(self):
        self.events: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        ann = (jax.profiler.TraceAnnotation("chipbench." + name) if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.events.append((name, t0, time.perf_counter()))

    def total(self, name: str, lo: float, hi: float) -> float:
        """Seconds in spans ``name`` that started in [lo, hi)."""
        return sum(t1 - t0 for n, t0, t1 in self.events
                   if n == name and lo <= t0 < hi)


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def compared(values: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} of every number the cell's limits name."""
    return {k: {"value": values[k], "limit": lim} for k, lim in limits.items()}


def judge(checks: dict) -> bool:
    """Every compared number is a finite reading at or under its limit."""
    import math
    return all(isinstance(v["value"], (int, float)) and math.isfinite(v["value"])
               and v["value"] <= v["limit"] for v in checks.values())
