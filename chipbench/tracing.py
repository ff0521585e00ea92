"""The profiler trace, reduced to what the result line reports.

A trace is first flattened to events ``(plane, line, name, start_ns,
dur_ns)`` (:func:`events_from_xplane`), so that the reduction
(:func:`reduce`) can be checked on a small recorded trace.  Per chip:

* busy: the union of the intervals in which an operation ran on the chip
  (the ``XLA Ops`` line of its device plane), inside the traced window;
* collective time: operations whose name is a collective, and the exposed
  part of it: the part that no other operation on that chip overlaps;
* idle gaps: the stretches of the window that no operation covers, each
  named by the benchmark's host span (a ``TraceAnnotation``) that was open
  at its middle.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"allgather|allreduce|reducescatter|alltoall", re.I)
SPAN_PREFIX = "chipbench."


def events_from_xplane(trace_dir: str) -> list[tuple]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged):
    return sum(e - s for s, e in merged)


def _subtract(a, b):
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce(events, chips: list[int], top: int = 10) -> dict:
    """Busy, collective and idle time of ``chips`` (device ids) over the
    traced window: the benchmark's ``trace_window`` span, or where it is
    missing, the first to the last device operation."""
    ops = {c: [] for c in chips}
    spans = []
    for plane, line, name, start, dur in events:
        m = DEVICE_PLANE.match(plane)
        if m and line == OPS_LINE and int(m.group(1)) in ops:
            # "%fusion.12 = bf16[...] fusion(...)": the instruction's name
            op = name.split(" = ", 1)[0].lstrip("%")
            ops[int(m.group(1))].append((op, start, start + dur))
        elif name.startswith(SPAN_PREFIX):
            spans.append((name[len(SPAN_PREFIX):], start, start + dur))
    every = [(s, e) for c in chips for _, s, e in ops[c]]
    if not every:
        return {"busy_s": 0.0, "window_s": 0.0}
    win = [(s, e) for n, s, e in spans if n == "trace_window"]
    lo, hi = win[-1] if win else (min(s for s, _ in every),
                                  max(e for _, e in every))
    busy = coll = exposed = 0.0
    op_time: dict[str, float] = {}
    gaps = []
    for c in chips:
        all_iv = _union(_clip([(s, e) for _, s, e in ops[c]], lo, hi))
        busy += _length(all_iv)
        coll_iv = _union(_clip([(s, e) for n, s, e in ops[c]
                                if COLLECTIVE.search(n)], lo, hi))
        comp_iv = _union(_clip([(s, e) for n, s, e in ops[c]
                                if not COLLECTIVE.search(n)], lo, hi))
        coll += _length(coll_iv)
        exposed += _length(_subtract(coll_iv, comp_iv))
        for n, s, e in ops[c]:
            op_time[n] = op_time.get(n, 0.0) + max(min(e, hi) - max(s, lo), 0.0)
        for s, e in _subtract([[lo, hi]], all_iv):
            mid = (s + e) / 2
            # the innermost open span: the one that started last
            name = max(((a, n) for n, a, b in spans
                        if a <= mid < b and n != "trace_window"),
                       default=(0.0, "no benchmark span"))[1]
            gaps.append((name, (e - s) / 1e9))
    n = len(chips)
    ranked = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "collective_s": coll / n / 1e9,
        "collective_exposed_s": exposed / n / 1e9,
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in ranked],
            "idle_gaps": [[g[0], g[1]] for g in gaps[:top]],
        },
    }
