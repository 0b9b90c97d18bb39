"""One traced run of a cell, read through the engine's own telemetry.

    python3 bench/engine_report.py --workload <cell> --seed <n> \\
        --seconds <s> [--keep-trace DIR]

The run is ``run.run_cell``'s traced run, unchanged: the same build, warm
traffic, window, telemetry, check and result line. This script prints one
JSON line of what that run reads: the result line's ``correct``, check
numbers and per-layer metrics, every engine metric (``METRICS``) whichever
cell it is listed for, the counter deltas over the window, the engine span
table (count, total and self seconds), the idle gaps named by the innermost
engine or ``gc`` span, each program's device seconds by named scope, and
where each op's scope was read (``op_name_source``: the trace's stats, or
the compiled steps' HLO text where no stat holds it). It adds what
``run.py`` does not do: the cost of one span with the profiler off and on
(``span_cost_us``), and with ``--keep-trace`` the trace and each program's
op names (``op_names.json``) kept for reading by hand.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time

import jax

import readers
import run
import spec

SPAN_COST_N = 20_000

METRICS = ("admit_wait_p50_ms", "decode_active_share",
           "engine_host_ms.decode", "engine_host_ms.chat",
           "kv_cache_ms.decode", "kv_cache_ms.chat")


def span_cost_us() -> float:
    """Host microseconds of one ``TraceAnnotation`` entered and left."""
    t0 = time.perf_counter()
    for _ in range(SPAN_COST_N):
        with jax.profiler.TraceAnnotation("span_cost"):
            pass
    return 1e6 * (time.perf_counter() - t0) / SPAN_COST_N


def span_costs() -> dict:
    """One span's cost with the profiler off, and on (a trace of its own,
    deleted)."""
    cost = {"off": span_cost_us()}
    tmp = tempfile.mkdtemp(prefix="bench_span_cost_")
    jax.profiler.start_trace(tmp)
    try:
        cost["on"] = span_cost_us()
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(tmp, ignore_errors=True)
    return cost


def report(cell: spec.Cell, seed: int, seconds: float,
           keep_trace: str | None = None, require_tpu: bool = True) -> dict:
    cost = span_costs()
    got = {}
    result = run.run_cell(cell, seed, seconds, True, require_tpu=require_tpu,
                          keep_trace=keep_trace, t_start=time.perf_counter(),
                          on_trace=lambda data, ev: got.update(data=data,
                                                               ev=ev))
    data, ev = got["data"], got["ev"]
    if keep_trace:
        with open(f"{keep_trace}/op_names.json", "w") as f:
            json.dump({d: v["op_names"] for d, v in ev["devices"].items()},
                      f)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    metrics.update({n: readers.load(n, cell.bench)(data) for n in METRICS})
    return {
        "workload": cell.name, "seed": seed, "correct": result["correct"],
        "check": {k: v["value"] for k, v in result["check"].items()},
        "metrics": metrics, "counters": data.counters,
        "busy_s": result["device"].get("busy_s"),
        "window_s": result["device"].get("window_s"),
        "op_name_source": ev["op_name_source"], "span_cost_us": cost,
        # each step's host time is a long list: its mean is the metric
        **{k: v for k, v in data.engine.items() if k != "step_host_s"},
        "device": {k: result["device"][k] for k in ("platform", "kind")},
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    run.setup_program(spec.ROOT)
    run.use_checkout_cache()
    t0 = time.perf_counter()
    out = report(cell, args.seed, args.seconds, args.keep_trace)
    print(f"engine_report: {time.perf_counter() - t0:.1f} s",
          file=sys.stderr)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
