"""Run one benchmark cell once on the chip; the last stdout line is the
result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Set-up (timed as ``setup_s``): import, random bfloat16 weights made on the
device from the seed, the engine at the configuration's published widths,
every program the window drives compiled (persistent compilation cache in
the checkout), then the cell's own warm traffic: a closed loop's first wave
prefilled, or an open loop's warm phase. The window then runs ``--seconds``
of host clock. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the window on the device, keeps the engine's own
telemetry around it (its counters read on both sides of the window, its
requests' times, the HLO text of the steps set-up compiled), and reports
the per-layer metrics. After the window the engine's state is freed and the
served tokens are compared with the configuration's float32 reference (its
model module's ``logit_gaps``, by ``check.py``); each number compared is
printed beside its limit, last on stderr and last in the result line.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero without a result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse       # noqa: E402
import functools      # noqa: E402
import gc             # noqa: E402
import json           # noqa: E402
import os             # noqa: E402
import shutil         # noqa: E402
import sys            # noqa: E402
import tempfile       # noqa: E402
from pathlib import Path  # noqa: E402

import jax            # noqa: E402

import check          # noqa: E402
import costs          # noqa: E402
import engine_trace   # noqa: E402
import loadgen        # noqa: E402
import readers        # noqa: E402
import serve_loop     # noqa: E402
import spec           # noqa: E402
import trace_reduce   # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the trace directory here (reading it by "
                         "hand); the default deletes it")
    return ap.parse_args(argv)


def seed_word(seed: int) -> int:
    return int(loadgen.seed_seq(seed, 0).generate_state(1)[0] & 0x7FFFFFFF)


def check_device(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: JAX found no TPU (platform {devs[0].platform!r}); "
            "the benchmark runs on the chip only")
        sys.exit(3)
    if len(devs) < chips:
        log(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
        sys.exit(3)
    return devs


def setup_program(root: Path):
    src = root / "src"
    if not (src / "repro").is_dir():
        log(f"bench: no src/repro under {root}: run from a checkout of "
            "the repository")
        sys.exit(2)
    sys.path.insert(0, str(src))


class CompileCounter:
    """Backend compilations seen by JAX while ``on``."""

    def __init__(self):
        self.on, self.names = False, []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == COMPILE_EVENT:
            self.names.append(kw.get("fun_name", "?"))


def build(cell: spec.Cell, seed: int, engine_hook=None,
          hlo: dict | None = None):
    """Weights from the seed and a warmed engine (set-up, less traffic);
    ``hlo``, where given, receives each compiled step's HLO text."""
    t0 = time.perf_counter()
    params = cell.model.make_params(cell.config, seed_word(seed))
    jax.block_until_ready(params)
    log(f"setup: weights {time.perf_counter() - t0:.3f} s")
    engine = serve_loop.build_engine(cell.config, cell.model.PUBLISHED,
                                     params)
    if engine_hook is not None:
        engine_hook(engine)
    compile_s = serve_loop.warm_engine(engine, hlo)
    log("setup: compile " + ", ".join(f"{k} {v:.3f} s"
                                      for k, v in compile_s.items()))
    return params, engine


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, keep_trace: str | None = None,
             t_start: float | None = None, engine_hook=None,
             after_check=None, on_trace=None) -> dict:
    """One run; returns the result line as a dict. ``engine_hook(engine)``
    (tests only) may break the timed path underneath; ``after_check(gaps,
    finished, req_of, vocab)`` (the control's script) reads the same sample
    after the check; ``on_trace(run, ev)`` (``engine_report.py``) gets a
    traced run's ``EngineRunData`` and trace events."""
    t_start = T_START if t_start is None else t_start
    devs = check_device(cell.chips) if require_tpu else jax.devices()
    dev = devs[0]
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__}; compile cache {cache_dir}")

    config, mix = cell.config, cell.traffic
    counter = CompileCounter()
    hlo = {} if trace else None
    params, engine = build(cell, seed, engine_hook, hlo)
    runner = serve_loop.Runner(engine, mix, cell.cell, seed)
    runner.warm(float(mix.get("warm_seconds", 0)))
    # what set-up made lives to the end of the run: keep the collector's
    # full passes in the window off it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    trace_dir = None
    if trace:
        runner.traced = True
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        before = dict(engine.counters)
    counter.on = True
    runner.window(seconds)
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
        counters = {k: v - before[k] for k, v in engine.counters.items()}
        times = {u: dict(t) for u, t in engine.request_times.items()}
    stats = serve_loop.window_stats(runner)
    sizes_ok = (engine.prefill_cache_size, engine.decode_cache_size)
    if sizes_ok != (1, 1):
        raise RuntimeError(f"the window recompiled a step: prefill/decode "
                           f"compiled variants {sizes_ok}")
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"window: {stats['window_s']:.3f} s, {stats['window_steps']} steps, "
        f"{stats['window_tokens']} tokens, {stats['requests_due']} due, "
        f"compiles in window {len(counter.names)} {counter.names[:5]}; "
        f"peak bytes in use {mem}")
    log(f"generator: late p50 {stats['generator_late_p50_ms']} ms, max "
        f"{stats['generator_late_max_ms']} ms; backlog at end "
        f"{stats['backlog_at_end']}")

    finished = {u: list(t) for u, t in engine.results.items()}
    req_of = dict(runner.req_of)
    rec = runner.rec
    max_slots = engine.scfg.max_slots
    vocab = engine.cfg.vocab_size
    engine.caches = None                  # free the KV pool before the check
    del engine, runner
    gc.unfreeze()
    gc.collect()

    summary = {}
    if trace:
        t0 = time.perf_counter()
        ev = engine_trace.read(trace_reduce.find_xplane(trace_dir),
                               serve_loop.SPAN_NAMES)
        engine_trace.fill_op_names(ev, hlo)
        summary = trace_reduce.summarize(ev)
        eng = engine_trace.summarize(ev, ev["spans"])
        log(f"trace: read in {time.perf_counter() - t0:.3f} s, op names "
            f"from {ev['op_name_source']}")
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)

    sz = cell.model.sizes(config)
    gaps = functools.partial(cell.model.logit_gaps, params, sz)
    t0 = time.perf_counter()
    correct, numbers, facts = check.compare(
        gaps, finished, req_of, seed, vocab, cell.cell["limits"])
    log(f"check: {facts}, {time.perf_counter() - t0:.3f} s")
    if after_check is not None:
        after_check(gaps, finished, req_of, vocab)

    metrics = {}
    if not trace:
        e2e = dict(stats, setup_s=setup_s)
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        # a CPU run (the tests) has no device trace, so no reader that
        # needs the chip's peaks finds anything to read
        peaks = costs.peaks(dev.device_kind) if dev.platform == "tpu" else {}
        run = engine_trace.EngineRunData(
            stats=stats, rec=rec, trace=summary, sizes=sz, peaks=peaks,
            max_slots=max_slots, model=cell.model, counters=counters,
            request_times=times, window=(rec.t_w0, max(rec.t_end, rec.t_w1)),
            engine=eng)
        for m in cell.per_layer:
            v = readers.load(m["name"], cell.bench)(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if on_trace is not None:
            on_trace(run, ev)
    for k in ("ttft_p50_ms", "ttft_p95_ms", "ttft_mean_ms", "itl_p50_ms",
              "itl_p95_ms", "itl_p99_ms", "itl_mean_ms", "itl_max_ms",
              "output_tok_s", "requests_due", "requests_first_token",
              "gaps", "window_steps"):
        log(f"stat: {k} {stats[k]}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": correct,
              "attempted": stats["requests_due"],
              "failed": rec.submit_failures,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.get("busy_s")
        device["window_s"] = summary.get("window_s")
        result["breakdown"] = {"device_ops": summary.get("device_ops", []),
                               "idle_gaps": summary.get("idle_gaps", [])}
    result["check"] = numbers
    for k, v in numbers.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    return result


def use_checkout_cache():
    """JAX's compilation cache in this checkout, at a fixed path, so that
    only a cell's first run here compiles and two checkouts share nothing.
    Set in the environment, where the program's ``enable_compile_cache``
    takes it from, and in JAX's config, which read the environment when
    JAX was imported. A cell's programs take some tens of MB, so the cache
    is unbounded: JAX's eviction, on when a size limit is set, reads an
    access-time file for every entry and stops writing altogether at one
    entry that lacks it."""
    cache = spec.ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None):
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    setup_program(spec.ROOT)
    use_checkout_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      keep_trace=args.keep_trace)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
