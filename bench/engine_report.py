"""One traced run of a cell, read through the engine's own telemetry.

    python3 bench/engine_report.py --workload <cell> --seed <n> \\
        --seconds <s> [--keep-trace DIR]

The run is ``run.run_cell``'s traced run, unchanged: the same build, warm
traffic, window, check and result line. Around it this script keeps what
the engine records and ``run.py`` does not read: the engine's counters
read on both sides of the window, its requests' times, the HLO text of the
steps it compiled in set-up, and the kept trace. It prints one JSON line:
the result line's ``correct``, check numbers and per-layer metrics, the
engine's metrics (``METRICS``), the counter deltas over the window, the
engine span table (count, total and self seconds), the idle gaps named by
the innermost engine or ``gc`` span, each program's device seconds by
named scope, and the cost of one span with the profiler off and on.
``op_name_source`` says where each op's scope was read: the trace's stats,
or the compiled steps' HLO text where no stat holds it. The benchmark's
own runs read none of this (PERF.md, open questions).
"""
from __future__ import annotations

import argparse
import json
import re
import shutil
import sys
import tempfile
import time

import jax

import engine_trace
import readers
import run
import serve_loop
import spec
import trace_reduce

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


class Telemetry:
    """What the engine records of one ``run_cell`` run. ``hook`` is the
    run's ``engine_hook``: it keeps the engine and the HLO text of each
    step set-up compiles. ``window`` takes ``serve_loop.Runner.window``'s
    place for the run: the harness's window, with the engine's counters
    read on both sides of it and one span's cost timed while the profiler
    still records."""

    def __init__(self):
        self.engine = self.rec = None
        self.hlo, self.counters, self.cost = {}, {}, {}

    def hook(self, engine):
        self.engine = engine
        compile_step = engine.compile_step

        def compile_and_keep(name):
            compiled = compile_step(name)
            self.hlo[name] = compiled.as_text()
            return compiled

        engine.compile_step = compile_and_keep

    def window(self, runner, seconds):
        before = dict(runner.engine.counters)
        self._window(runner, seconds)
        after = runner.engine.counters
        self.counters = {k: after[k] - before[k] for k in after}
        self.cost["on"] = span_cost_us()
        self.rec = runner.rec

    def run(self, cell: spec.Cell, seed: int, seconds: float,
            trace_dir: str, require_tpu: bool = True) -> dict:
        self.cost["off"] = span_cost_us()
        self._window = serve_loop.Runner.window
        serve_loop.Runner.window = lambda r, s: self.window(r, s)
        try:
            return run.run_cell(cell, seed, seconds, True,
                                require_tpu=require_tpu,
                                keep_trace=trace_dir,
                                t_start=time.perf_counter(),
                                engine_hook=self.hook)
        finally:
            serve_loop.Runner.window = self._window


def op_names(ev: dict, hlo: dict) -> str:
    """Fill each device's ``op_names`` from the compiled steps' HLO text
    where the trace's stats hold none; where they were read."""
    source = "trace_stats"
    for dev in ev["devices"].values():
        if any(dev["op_names"].values()):
            continue
        source = "hlo_text"
        for text in hlo.values():
            mod = re.search(r"HloModule (\w+)", text).group(1)
            dev["op_names"][mod] = engine_trace.hlo_op_names(text)
    return source


def report(cell: spec.Cell, seed: int, seconds: float,
           keep_trace: str | None = None, require_tpu: bool = True) -> dict:
    tel = Telemetry()
    trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench_engine_")
    result = tel.run(cell, seed, seconds, trace_dir, require_tpu)
    ev = engine_trace.read(trace_reduce.find_xplane(trace_dir),
                           serve_loop.SPAN_NAMES)
    source = op_names(ev, tel.hlo)
    if keep_trace:
        with open(f"{keep_trace}/op_names.json", "w") as f:
            json.dump({d: v["op_names"] for d, v in ev["devices"].items()},
                      f)
    else:
        shutil.rmtree(trace_dir, ignore_errors=True)
    eng = engine_trace.summarize(ev, ev["spans"])
    rec = tel.rec
    data = engine_trace.EngineRunData(
        stats={}, rec=rec, trace={}, sizes=None, peaks={},
        max_slots=tel.engine.scfg.max_slots, counters=tel.counters,
        request_times={u: dict(t)
                       for u, t in tel.engine.request_times.items()},
        window=(rec.t_w0, max(rec.t_end, rec.t_w1)), engine=eng)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    metrics.update({n: readers.load(n)(data) for n in METRICS})
    return {
        "workload": cell.name, "seed": seed, "correct": result["correct"],
        "check": {k: v["value"] for k, v in result["check"].items()},
        "metrics": metrics, "counters": tel.counters,
        "busy_s": result["device"].get("busy_s"),
        "window_s": result["device"].get("window_s"),
        "op_name_source": source, "span_cost_us": tel.cost,
        # each step's host time is a long list: its mean is the metric
        **{k: v for k, v in eng.items() if k != "step_host_s"},
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
