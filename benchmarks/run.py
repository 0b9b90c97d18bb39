"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV."""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: table1,attn,decode,fig6,fig7,fig8,"
                         "roofline")
    ap.add_argument("--steps", type=int, default=50)
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    def want(name):
        return only is None or name in only

    print("name,us_per_call,derived")
    if want("table1"):
        from benchmarks import table1_ops
        table1_ops.run()
    if want("attn"):
        from benchmarks import attn_kernels
        attn_kernels.run()
    if want("decode"):
        from benchmarks import decode_throughput
        decode_throughput.run()
    if want("fig6"):
        from benchmarks import fig6_convergence
        fig6_convergence.run(steps=args.steps)
    if want("fig7"):
        from benchmarks import fig7_beta_gamma
        fig7_beta_gamma.run(steps=args.steps)
    if want("fig8"):
        from benchmarks import fig8_init_sweep
        fig8_init_sweep.run(steps=max(args.steps // 2, 10))
    if want("roofline"):
        from benchmarks import roofline_table
        from benchmarks.common import emit
        emit(roofline_table.run())


if __name__ == "__main__":
    main()
