"""Find an open-loop cell's knee once, by a sweep of fixed rates on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1 2 4 8

One process, one set of weights; a fresh engine for each rate (the
compiled steps come from the persistent cache), the cell's warm phase,
then a window of ``--seconds`` that offers ``round(rate * seconds)``
requests. Prints one JSON line per rate:
requests due, first tokens, completions, the tails, the backlog left at
the window's end. The knee is the highest rate whose backlog stays bounded
and whose tails do not grow with the window; the cell's fixed rate is set
at about 0.8 of it. The benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import gc
import json
import time

import run
import serve_loop
import spec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("only an open-loop cell has a rate to sweep")
    run.setup_program(spec.ROOT)
    run.use_checkout_cache()
    run.check_device(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    params, engine = run.build(cell, args.seed)
    warm = float(cell.traffic["warm_seconds"])
    for rate in args.rates:
        if engine is None:
            engine = serve_loop.build_engine(
                cell.config, cell.model.PUBLISHED, params)
            serve_loop.warm_engine(engine)
        r = serve_loop.Runner(engine, cell.traffic, cell.cell, args.seed,
                              rate_per_s=rate)
        r.warm(warm)
        r.window(args.seconds)
        st = serve_loop.window_stats(r)
        st["completed_in_window"] = sum(
            1 for u in r.finished if r.rec.last[u] >= r.rec.t_w0)
        keep = ("requests_due", "requests_first_token", "completed_in_window",
                "ttft_p50_ms", "ttft_p95_ms", "ttft_mean_ms", "itl_p50_ms",
                "itl_p95_ms", "queue_wait_p50_ms", "output_tok_s",
                "backlog_at_end", "generator_late_max_ms", "window_steps")
        print(json.dumps({"rate_per_s": rate,
                          **{k: st[k] for k in keep}}), flush=True)
        engine.caches = None
        del engine, r
        engine = None
        gc.collect()
        time.sleep(0.5)


if __name__ == "__main__":
    main()
