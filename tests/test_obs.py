"""repro.obs: registry semantics, snapshot folding, tracing, STATS verb.

What's pinned here:

* counters are exact under thread contention (per-metric locks);
* histogram bucket edges (the fixed log2 layout every snapshot shares);
* ``snapshot(reset=True)`` is a *delta* — merging two consecutive deltas
  equals one total (the worker-folding idempotence property);
* ``CompressionEngine`` pack telemetry survives all three transports
  (thread pool, process pool over pickle, process pool over shm slabs)
  via :meth:`collect_obs`;
* the Chrome trace export byte-layout (golden file) and span semantics;
* the RBSP ``STATS`` verb round-trip: generation stamp, server stats,
  per-branch read counters, canonical-JSON metrics, trace drain;
* the ``REPRO_OBS`` off path costs a no-op instrument, and a loose
  on-vs-off overhead smoke (the tight 2% gate is benchmarks/fig_obs.py,
  which measures best-of-reps; here we only catch gross regressions).
"""

from __future__ import annotations

import json
import os
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs import metrics as M
from repro.obs import trace as T

GOLDEN_TRACE = os.path.join(os.path.dirname(__file__), "golden",
                            "trace_pr6.json")


@pytest.fixture
def reg():
    return M.Registry()


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

def test_key_roundtrip():
    key = M.format_key("server.reads", {"path": "f.bskt", "branch": "x"})
    assert key == "server.reads{branch=x,path=f.bskt}"   # sorted labels
    name, labels = M.parse_key(key)
    assert name == "server.reads"
    assert labels == {"branch": "x", "path": "f.bskt"}
    assert M.parse_key("plain") == ("plain", {})
    assert M.format_key("plain") == "plain"


def test_kind_mismatch_raises(reg):
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


# ---------------------------------------------------------------------------
# counters / gauges under contention
# ---------------------------------------------------------------------------

def test_concurrent_counter_exact(reg):
    c = reg.counter("hits", worker="t")
    threads = [threading.Thread(target=lambda: [c.inc() for _ in range(10_000)])
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 80_000
    assert reg.snapshot()["counters"]["hits{worker=t}"] == 80_000


def test_gauge_inc_dec(reg):
    g = reg.gauge("depth")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value == 6
    snap = reg.snapshot(reset=True)
    assert snap["gauges"]["depth"] == 6
    # gauges are levels, not deltas: reset keeps them
    assert reg.snapshot()["gauges"]["depth"] == 6


# ---------------------------------------------------------------------------
# histogram bucket layout
# ---------------------------------------------------------------------------

def test_bucket_edges():
    assert M.bucket_index(0.0) == 0
    assert M.bucket_index(-3.0) == 0
    assert M.bucket_index(2.0 ** -33) == 0       # underflow
    assert M.bucket_index(2.0 ** -32) == 1       # first finite bucket
    assert M.bucket_index(1.0) == 33
    assert M.bucket_index(1.999) == 33
    assert M.bucket_index(2.0) == 34
    assert M.bucket_index(2.0 ** 62) == 95
    assert M.bucket_index(2.0 ** 63) == 95       # overflow clamps
    assert M.bucket_index(float("1e300")) == 95
    lo, hi = M.bucket_bounds(33)
    assert (lo, hi) == (1.0, 2.0)
    assert M.bucket_bounds(0)[0] == 0.0
    # every positive double lands in the bucket whose bounds contain it
    for v in (1e-9, 0.37, 1.0, 7.0, 1e6):
        i = M.bucket_index(v)
        lo, hi = M.bucket_bounds(i)
        assert lo <= v < hi or i in (0, M.N_BUCKETS - 1)


def test_histogram_observe_and_quantile(reg):
    h = reg.histogram("lat_s")
    for v in [0.001] * 98 + [4.0] * 2:
        h.observe(v)
    assert h.count == 100
    assert h.sum == pytest.approx(0.098 + 8.0)
    p50, p99 = h.quantile(0.50), h.quantile(0.99)
    lo, hi = M.bucket_bounds(M.bucket_index(0.001))
    assert lo <= p50 <= hi
    assert p99 >= 2.0                            # lands in the 4.0 bucket
    assert h.quantile(0.0) >= 0.0
    assert M.quantile_from_buckets({}, 0.5) == 0.0


def test_histogram_timer(reg):
    h = reg.histogram("t_s")
    with h.time():
        pass
    assert h.count == 1 and h.sum >= 0.0


# ---------------------------------------------------------------------------
# snapshot / merge: the worker-folding protocol
# ---------------------------------------------------------------------------

def test_snapshot_reset_is_delta_and_merge_is_idempotent(reg):
    parent = M.Registry()
    reg.counter("n").inc(7)
    reg.histogram("h").observe(1.5)
    d1 = reg.snapshot(reset=True)
    reg.counter("n").inc(3)
    d2 = reg.snapshot(reset=True)
    d3 = reg.snapshot(reset=True)                # nothing new
    for d in (d1, d2, d3):
        parent.merge(d)
    snap = parent.snapshot()
    assert snap["counters"]["n"] == 10           # 7 + 3, nothing twice
    assert snap["hists"]["h"]["count"] == 1
    assert d3["counters"]["n"] == 0


def test_merge_through_json(reg):
    """Snapshots survive the wire (canonical JSON) byte-exactly."""
    reg.counter("c", a="1").inc(5)
    reg.gauge("g").set(2.5)
    reg.histogram("h").observe(0.25)
    snap = json.loads(json.dumps(reg.snapshot(), sort_keys=True))
    other = M.Registry()
    other.merge(snap)
    assert other.snapshot() == reg.snapshot()


# ---------------------------------------------------------------------------
# enable gate
# ---------------------------------------------------------------------------

def test_disabled_returns_null_instrument():
    prev = obs.set_enabled(False)
    try:
        assert obs.counter("nope") is M.NULL
        assert obs.gauge("nope") is M.NULL
        assert obs.histogram("nope") is M.NULL
        obs.counter("nope").inc()                # all no-ops
        with obs.histogram("nope").time():
            pass
        with obs.trace.span("nope"):
            pass
    finally:
        obs.set_enabled(prev)
    assert obs.enabled() == prev


def _stage_delta(fn) -> tuple[dict, dict]:
    """What ``fn`` adds to the basket stage telemetry: {(op, stage):
    (count, seconds)} and {op: raw bytes}."""
    before = obs.snapshot()
    fn()
    after = obs.snapshot()
    stages, nbytes = {}, {}
    for key, h in after["hists"].items():
        name, lab = M.parse_key(key)
        old = before["hists"].get(key, {"count": 0, "sum": 0.0})
        if name == "basket.stage_s" and h["count"] > old["count"]:
            stages[(lab["op"], lab["stage"])] = (h["count"] - old["count"],
                                                 h["sum"] - old["sum"])
    for key, n in after["counters"].items():
        name, lab = M.parse_key(key)
        if name == "basket.stage_bytes" and n > before["counters"].get(key, 0):
            nbytes[lab["op"]] = n - before["counters"].get(key, 0)
    return stages, nbytes


@pytest.mark.parametrize("enabled", [True, False])
def test_basket_stage_timers_label_each_stage(enabled):
    """One pack_basket / unpack_basket_into round trip times each stage
    once under its op/stage labels and counts its raw bytes once per op;
    with obs disabled it records nothing."""
    from repro.core.basket import pack_basket, unpack_basket_into
    from repro.core.codec import CompressionConfig

    raw = np.linspace(0.0, 1.0, 65_536, dtype=np.float32)
    out = np.empty_like(raw)

    def round_trip():
        payload, meta = pack_basket(raw, CompressionConfig("zstd", 2,
                                                           "bitshuffle4"))
        unpack_basket_into(payload, meta, out)

    prev = obs.set_enabled(enabled)
    try:
        stages, nbytes = _stage_delta(round_trip)
    finally:
        obs.set_enabled(prev)
    assert np.array_equal(out, raw)
    if not enabled:
        assert stages == {} and nbytes == {}
        return
    assert set(stages) == {(op, st) for op in ("pack", "unpack")
                           for st in ("precond", "codec", "checksum")}
    assert all(n == 1 and t > 0 for n, t in stages.values())
    assert nbytes == {"pack": raw.nbytes, "unpack": raw.nbytes}


def test_container_times_basket_io_stages(tmp_path):
    """A BasketWriter write and a BasketFile read time each basket's file
    I/O as its pack/io and unpack/io stage."""
    from repro.core.bfile import BasketFile, BasketWriter

    arr = np.arange(3 * 65_536, dtype=np.float32)
    path = str(tmp_path / "io.bskt")

    def write():
        with BasketWriter(path) as w:
            w.write_branch("x", arr, target_basket_bytes=65_536 * 4)

    def read():
        with BasketFile(path) as f:
            assert np.array_equal(f.read_branch("x"), arr)

    stages, nbytes = _stage_delta(write)
    assert stages[("pack", "io")][0] == 3 and nbytes == {"pack": arr.nbytes}
    stages, nbytes = _stage_delta(read)
    assert stages[("unpack", "io")][0] == 3 and nbytes == {"unpack": arr.nbytes}


# ---------------------------------------------------------------------------
# engine transports: thread pool, process+pickle, process+shm
# ---------------------------------------------------------------------------

def _pack_some(algo: str, **engine_kw):
    """Pack a >inline_bytes buffer through an engine and return the delta
    of this process's registry counters for that algo."""
    from repro.core.codec import CompressionConfig
    from repro.io.engine import CompressionEngine

    raw = np.arange(32_768, dtype=np.int64).tobytes()    # 256 KiB
    key = M.format_key("engine.pack.bytes_in", {"algo": algo})
    before = obs.snapshot()["counters"].get(key, 0)
    with CompressionEngine(**engine_kw) as eng:
        cfg = CompressionConfig(algo, 1, "none")
        out = list(eng.pack_stream([(0, 32_768, raw)], cfg))
        assert len(out) == 1
        # close() folds process-pool workers' deltas via collect_obs()
    return obs.snapshot()["counters"].get(key, 0) - before


def test_engine_obs_thread_transport():
    assert _pack_some("zlib", workers=2) >= 262_144


def test_engine_obs_process_pickle_transport():
    assert _pack_some("repro-deflate", workers=1, shm=False) >= 262_144


def test_engine_obs_process_shm_transport():
    # shm="auto" uses the slab transport where available and falls back to
    # pickle otherwise — the telemetry must fold back either way
    assert _pack_some("repro-deflate", workers=1, shm="auto") >= 262_144


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_span_records_event_and_error():
    T.clear()
    with T.span("ok.op", cat="test", k=1):
        pass
    with pytest.raises(ValueError):
        with T.span("bad.op", cat="test"):
            raise ValueError("boom")
    evs = {e["name"]: e for e in T.drain()}
    assert evs["ok.op"]["ph"] == "X" and evs["ok.op"]["args"] == {"k": 1}
    assert evs["ok.op"]["dur"] >= 0.0
    assert evs["bad.op"]["args"]["error"] == "ValueError"
    assert T.drain() == []                       # drain popped everything


def test_ring_is_bounded():
    T.clear()
    T.set_capacity(8)
    try:
        for i in range(20):
            T.instant(f"e{i}")
        names = [e["name"] for e in T.events()]
        assert names == [f"e{i}" for i in range(12, 20)]   # newest kept
    finally:
        T.set_capacity(65536)
        T.clear()


def test_chrome_trace_golden(tmp_path):
    """The export byte-layout is pinned: a fixed synthetic event list must
    serialize identically forever (Perfetto compatibility contract)."""
    evs = [
        {"name": "ckpt.save", "cat": "ckpt", "ph": "X", "ts": 10.0,
         "dur": 120.5, "pid": 4242, "tid": 101,
         "args": {"path": "a.bskt", "branches": 3}},
        {"name": "server.pread", "cat": "server", "ph": "X", "ts": 40.0,
         "dur": 15.25, "pid": 4242, "tid": 102},
        {"name": "mark", "cat": "repro", "ph": "i", "s": "t", "ts": 200.0,
         "pid": 4242, "tid": 101},
    ]
    out = str(tmp_path / "trace.json")
    n = T.export_chrome(out, events=evs)
    assert n == 3
    got = open(out).read()
    doc = json.loads(got)
    assert [e["ph"] for e in doc["traceEvents"]] == ["M", "M", "X", "X", "i"]
    assert doc["displayTimeUnit"] == "ms"
    if not os.path.exists(GOLDEN_TRACE):         # first run: write golden
        with open(GOLDEN_TRACE, "w") as f:
            f.write(got)
    assert got == open(GOLDEN_TRACE).read(), (
        "Chrome trace export drifted from tests/golden/trace_pr6.json; "
        "if the change is intentional, delete the golden and rerun")


def test_export_drains_live_ring(tmp_path):
    T.clear()
    with T.span("live.op"):
        pass
    out = str(tmp_path / "live.json")
    assert T.export_chrome(out) == 1
    assert T.events() == []                      # export consumed the ring
    doc = json.loads(open(out).read())
    assert any(e["name"] == "live.op" for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# RBSP STATS round-trip
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stats_server(tmp_path_factory):
    from repro.core.bfile import write_arrays
    from repro.core.codec import CompressionConfig
    from repro.remote import BasketServer

    td = tmp_path_factory.mktemp("obs_remote")
    rng = np.random.default_rng(3)
    write_arrays(str(td / "f.bskt"),
                 {"energy": rng.standard_normal(60_000).astype(np.float32),
                  "pid": rng.integers(0, 9, 60_000).astype(np.int32)},
                 cfg_for=lambda n, a: CompressionConfig("zlib", 1, "shuffle"),
                 target_basket_bytes=16 * 1024)
    with BasketServer(str(td), workers=2) as srv:
        srv.start()
        yield srv


def test_stats_verb_roundtrip(stats_server):
    from repro.remote import RemoteBasketFile
    from repro.remote.client import fetch_stats

    srv = stats_server
    with RemoteBasketFile(srv.url("f.bskt"), wire=None) as rf:
        rf.read_branch("energy")
        rf.read_branch("energy")
        rf.read_branch("pid")
        body = rf.server_stats()
    assert body["pid"] > 0 and body["uptime_s"] >= 0.0
    assert body["server"]["requests"] >= 1
    gen0 = body["gen"]

    body2 = fetch_stats(srv.host, srv.port)
    assert body2["gen"] > gen0                   # generation-stamped
    counters = body2["metrics"]["counters"]
    reads = {M.parse_key(k)[1]["branch"]: v for k, v in counters.items()
             if M.parse_key(k)[0] == "server.reads"}
    assert reads.get("energy", 0) >= 2 * reads.get("pid", 1)
    hists = body2["metrics"]["hists"]
    readv = hists.get("server.request_s{verb=readv}")
    assert readv and readv["count"] >= 1
    # the whole body is canonical-JSON serializable (the wire contract)
    json.dumps(body2, sort_keys=True)


def test_stats_verb_trace_drain(stats_server):
    from repro.remote.client import fetch_stats

    srv = stats_server
    with T.span("marker.op", cat="test"):
        pass
    body = fetch_stats(srv.host, srv.port, trace=True)
    names = {e["name"] for e in body["trace_events"]}
    assert "marker.op" in names                  # loopback: shared ring
    body2 = fetch_stats(srv.host, srv.port, trace=True)
    # each event crosses the wire exactly once (drain, not copy)
    assert "marker.op" not in {e["name"] for e in body2.get("trace_events",
                                                            [])}


def test_stats_errors_labeled_by_verb(stats_server):
    import socket

    from repro.remote import protocol as P

    srv = stats_server
    key = "server.errors{verb=readv}"
    before = obs.snapshot()["counters"].get(key, 0)
    with socket.create_connection((srv.host, srv.port), timeout=5) as s:
        rfile = s.makefile("rb")
        s.sendall(P.pack_frame(P.REQ_READV, {"path": "no/such.bskt"}))
        t, _body, _payload = P.read_frame(rfile)
        assert t == P.RESP_ERROR
    assert obs.snapshot()["counters"].get(key, 0) == before + 1


# ---------------------------------------------------------------------------
# overhead smoke (loose; the tight 2% gate is benchmarks/fig_obs.py)
# ---------------------------------------------------------------------------

def test_overhead_smoke(tmp_path):
    import time

    from repro.checkpoint.manager import load_pytree, save_pytree

    tree = {"w": np.arange(200_000, dtype=np.float32)}
    path = str(tmp_path / "t.bskt")

    def workload():
        save_pytree(path, tree, workers=0)
        load_pytree(path, workers=0)

    workload()                                   # warm
    def best(fn, reps=3):
        t = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            t = min(t, time.perf_counter() - t0)
        return t

    prev = obs.set_enabled(False)
    try:
        t_off = best(workload)
    finally:
        obs.set_enabled(prev)
    t_on = best(workload)
    # gross-regression guard only: CI machines are noisy, so the budget
    # here is 1.5x + 200ms, not the benchmark's 2%
    assert t_on <= t_off * 1.5 + 0.2, (t_on, t_off)
