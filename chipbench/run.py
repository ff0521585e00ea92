"""Run one benchmark cell on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the result reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, as ``BENCHMARK.json`` lists them.  The
last line of standard output is the JSON result; the last lines of
standard error are the numbers the correctness check compared, each beside
its limit.  Without a TPU, with fewer chips than the cell asks for, or
outside a checkout of the program, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chipbench: no program (src/repro) in {ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import bench

    base = os.path.join(ROOT, "chipbench")
    spec = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = bench.load_cell(args.workload, base)
    e2e, per = bench.cell_metrics(spec, args.workload)
    try:
        chips = bench.find_chips(cell["chips"], base)
    except bench.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    bench.enable_compile_cache()
    compiles = bench.CompileLog()
    spans = bench.Spans()
    devices = chips.pop("devices")
    out = bench.driver(cell).run(cell, args.seed, args.seconds, bool(args.trace),
                                 spans, devices, T_START)
    result = assemble(cell, out, e2e, per, chips, spans, bool(args.trace))
    lo = out["window_t0"]
    in_window = sum(1 for t in compiles.times if lo <= t < lo + out["window_s"])
    print(f"chipbench: {compiles.programs} programs compiled or loaded in "
          f"{compiles.seconds:.2f} s ({compiles.hits} cache hits, "
          f"{compiles.misses} misses), {in_window} inside the window",
          file=sys.stderr)
    for name, v in out["checks"].items():
        if name not in result["checks"]:
            print(f"reading {name}: {v!r}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def assemble(cell, out, e2e, per, chips, spans, trace) -> dict:
    """The result line: the contract's keys, the compared numbers last."""
    from chipbench import bench
    checks = bench.compared(out["checks"], cell["limits"])
    metrics = {}
    if not trace:
        for m in e2e:
            metrics[m["name"]] = {"value": out[m["name"]], "unit": m["unit"]}
    else:
        ctx = {"out": out, "spans": spans, "chips": chips["count"],
               "peak": bench.peaks(chips["kind"], cell["base"]), "cell": cell}
        for m in per:
            v = bench.metric_reader(m["name"], cell["base"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(chips, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": bench.judge(checks) and out["failed"] == 0,
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if trace:
        tr = out["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        if "breakdown" in tr:
            result["breakdown"] = tr["breakdown"]
    result["checks"] = checks
    return result


if __name__ == "__main__":
    sys.exit(main())
