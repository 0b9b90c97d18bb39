"""Serving launcher CLI: batched generation on any assigned arch (the smoke
config by default, for the CPU; ``--published`` serves the arch's published
config, for a chip).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --steps 16
    PYTHONPATH=src python -m repro.launch.serve --published --engine \
        continuous --paged --page-size 256 --prefill-chunk 512 \
        --max-seq 4096 --max-slots 8 --decode-kernel --prefill-kernel
    PYTHONPATH=src python -m repro.launch.serve --engine continuous \
        --requests 12 --max-slots 4 --decode-kernel
    PYTHONPATH=src python -m repro.launch.serve --engine continuous \
        --temperature 0.8 --top-k 50 --top-p 0.95 --seed 7
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --engine continuous \
        --paged --decode-kernel --mesh 2x4

``--engine static`` runs the lockstep ServeSession; ``--engine continuous``
runs the slot-recycling ContinuousBatchingEngine over a queue of requests
with heterogeneous prompt/generation lengths — prompts enter the KV cache
in fixed ``--prefill-chunk`` appends at the slot index (one compiled prefill
shape for the whole run), with at most ``--prefill-budget`` prefill tokens
per engine iteration so long prompts cannot stall decode.

Sampling (``--temperature``/``--top-k``/``--top-p``/``--min-p``/``--seed``)
runs fused inside the jitted steps: per-slot SamplingParams banks, tokens
sampled device-side, no per-token logits transfer (``--host-sampling``
switches to the legacy host path — same streams, measurably more host
traffic). In continuous mode request i draws from seed ``--seed + i``, so
every request's stream is reproducible regardless of scheduling.

``--decode-kernel`` requires a consmax arch; requesting it on a softmax/
softermax config raises at construction instead of silently serving the
jnp row path.

Weights are random, from ``lm_init`` keyed by ``--seed``. ``build_engine``
is the one place a continuous engine is built from these flags
(``chip_smoke.py`` builds its engines through it too). ``main`` turns on
the persistent compilation cache (``launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import time


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--published", action="store_true",
                    help="serve the arch's published config (full widths "
                         "and depth) instead of the smoke config; for a "
                         "chip, not the CPU")
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="override n_kv_heads (0 = arch default). Smoke "
                         "configs default to 1 KV head, which --tp > 1 "
                         "cannot divide — pass e.g. 4 for mesh runs")
    ap.add_argument("--engine", choices=("static", "continuous"),
                    default="static")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=16)
    # sampling knobs -> per-request SamplingParams
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples with the masks below")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep the k highest-score tokens (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus mass cutoff in (0, 1] (1 = disabled)")
    ap.add_argument("--min-p", type=float, default=0.0,
                    help="min prob relative to the max, [0, 1) "
                         "(0 = disabled)")
    ap.add_argument("--seed", type=int, default=0,
                    help="weight seed and sampling seed; continuous "
                         "requests sample with seed + i")
    ap.add_argument("--host-sampling", action="store_true",
                    help="legacy host-side sampling (logits shipped per "
                         "token) instead of the fused in-step epilogue")
    # continuous-engine knobs
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=0,
                    help="KV rows per slot (0 = 2 * (prompt-len + steps) "
                         "+ 8)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="append-at-index prefill chunk (ONE compiled shape)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="max prefill tokens per engine iteration "
                         "(0 = one chunk)")
    ap.add_argument("--decode-kernel", action="store_true",
                    help="split-KV consmax decode Pallas kernel "
                         "(consmax archs only; errors otherwise)")
    ap.add_argument("--prefill-kernel", action="store_true",
                    help="fused consmax prefill/append Pallas kernel for "
                         "prompt chunks, contiguous and paged (consmax "
                         "archs only; errors otherwise)")
    ap.add_argument("--prefill-kv-block", type=int, default=512,
                    help="KV shard size for the prefill kernel grid")
    ap.add_argument("--kv-dtype", default="bfloat16",
                    choices=("bfloat16", "bf16", "int8", "fp8_e4m3"),
                    help="KV-cache storage dtype. int8/fp8_e4m3 store "
                         "quantized K/V with per-row fp32 scales; the "
                         "serving kernels dequantize per-block in VMEM "
                         "(~2x less cache HBM traffic for int8)")
    ap.add_argument("--no-fill-bound", action="store_true",
                    help="disable fill-bounded kernel grids (capacity-swept "
                         "KV walks — the pre-bounding A/B baseline)")
    ap.add_argument("--paged", action="store_true",
                    help="shared page-pool KV cache (continuous engine "
                         "only): slots map rows onto pool pages instead of "
                         "owning max_seq contiguous rows")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV rows per pool page (must divide "
                         "--prefill-chunk)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="pool capacity; 0 = max_slots * "
                         "ceil(max_seq / page_size), i.e. no sharing gain — "
                         "set lower to oversubscribe slots onto fewer cells")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the prefix-sharing page cache (paged "
                         "engine only): every admission prefills from row "
                         "0 even when an identical prompt prefix already "
                         "sits in pool pages")
    ap.add_argument("--prefix-evict", choices=("lru", "fifo"), default="lru",
                    help="reclaim order for refcount-0 cached pages when "
                         "the free list runs dry: lru = release order, "
                         "fifo = registration order")
    # mesh knobs (continuous engine only)
    ap.add_argument("--mesh", default="",
                    help="device mesh as TPxNS, e.g. 2x4 = tp 2, seq-shards "
                         "4 (shorthand for --tp/--seq-shards; needs tp*ns "
                         "devices — on CPU export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards: attention heads (and the "
                         "KV caches' head axis) split across the 'model' "
                         "mesh axis; must divide n_heads and n_kv_heads")
    ap.add_argument("--seq-shards", type=int, default=1,
                    help="sequence shards: paged pool pages split across "
                         "the 'seq' mesh axis in per-position blocks "
                         "(requires --paged; num_pages must divide evenly)")
    args = ap.parse_args(argv)
    if args.mesh:
        try:
            args.tp, args.seq_shards = map(int, args.mesh.lower().split("x"))
        except ValueError:
            raise SystemExit(f"--mesh must be TPxNS, got {args.mesh!r}")
    return args


def build_model(args):
    """(cfg, params): the arch's smoke or published config and random
    weights from ``lm_init`` keyed by ``args.seed``."""
    from jax import random

    from repro.configs.registry import get_config
    from repro.models import transformer as T
    from repro.nn.module import Ctx

    cfg = get_config(args.arch, smoke=not args.published,
                     **({"n_kv_heads": args.kv_heads} if args.kv_heads
                        else {}))
    if cfg.frontend != "tokens":
        raise SystemExit(f"{args.arch}: embedding-frontend serving demo is "
                         "exercised by the dry-run decode cells")
    return cfg, T.lm_init(Ctx(random.key(args.seed)), cfg)


def build_engine(args):
    """The continuous-batching engine these flags describe."""
    from repro.configs.base import ServeConfig
    from repro.serve.engine import ContinuousBatchingEngine

    cfg, params = build_model(args)
    scfg = ServeConfig(max_seq=(args.max_seq
                                or 2 * (args.prompt_len + args.steps) + 8),
                       kv_cache_dtype=args.kv_dtype,
                       prefill_chunk=args.prefill_chunk,
                       prefill_budget=args.prefill_budget,
                       max_slots=args.max_slots,
                       decode_kernel=args.decode_kernel,
                       prefill_kernel=args.prefill_kernel,
                       prefill_kv_block=args.prefill_kv_block,
                       fill_bound=not args.no_fill_bound,
                       fused_sampling=not args.host_sampling,
                       score_norm=cfg.score_norm,
                       paged_kv=args.paged, page_size=args.page_size,
                       num_pages=args.num_pages,
                       prefix_cache=not args.no_prefix_cache,
                       prefix_evict=args.prefix_evict,
                       tp=args.tp, seq_shards=args.seq_shards)
    return ContinuousBatchingEngine(cfg, scfg, params)


def main(argv=None):
    args = parse_args(argv)

    import dataclasses

    from jax import random

    from repro.configs.base import ServeConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.engine import ServeSession
    from repro.serve.sampling import SamplingParams

    enable_compile_cache()
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        top_p=args.top_p, min_p=args.min_p, seed=args.seed)
    fused = not args.host_sampling

    if args.engine == "static":
        if args.tp * args.seq_shards > 1:
            raise SystemExit("--mesh/--tp/--seq-shards require --engine "
                             "continuous (the static session is the "
                             "single-device A/B reference)")
        cfg, params = build_model(args)
        sess = ServeSession(
            cfg, ServeConfig(max_seq=(args.max_seq
                                      or args.prompt_len + args.steps + 8),
                             kv_cache_dtype=args.kv_dtype,
                             decode_kernel=args.decode_kernel,
                             prefill_kernel=args.prefill_kernel,
                             prefill_kv_block=args.prefill_kv_block,
                             fill_bound=not args.no_fill_bound,
                             fused_sampling=fused,
                             score_norm=cfg.score_norm), params)
        prompts = random.randint(random.key(1),
                                 (args.batch, args.prompt_len),
                                 0, cfg.vocab_size)
        t0 = time.perf_counter()
        out = sess.generate(prompts, steps=args.steps, sampling=sp)
        dt = time.perf_counter() - t0
        n = args.batch * args.steps
        # report the session's ACTUAL mode: recurrent/embeds archs downgrade
        # to host-side sampling even when --host-sampling wasn't passed
        print(f"[serve] {n} tokens in {dt:.2f}s ({n/dt:.1f} tok/s), "
              f"sampling={sp}, fused={sess._fused}")
        print("[serve] sample:", out[0].tolist())
        return

    eng = build_engine(args)
    cfg, scfg = eng.cfg, eng.scfg
    if eng.plan is not None:
        print(f"[serve/continuous] mesh: tp={args.tp} x "
              f"seq_shards={args.seq_shards} over "
              f"{args.tp * args.seq_shards} devices "
              f"({eng.plan.cfg_local.n_heads} heads/shard"
              + (f", {eng.plan.pages_per_shard} pages/shard"
                 if args.paged else "") + ")")
    rng = random.key(1)
    uids = []
    for i in range(args.requests):
        rng, k1, k2 = random.split(rng, 3)
        plen = 1 + int(random.randint(k1, (), 0, args.prompt_len))
        steps = 1 + int(random.randint(k2, (), 0, args.steps))
        prompt = random.randint(rng, (plen,), 0, cfg.vocab_size).tolist()
        # per-request stream: seed + i, reproducible under any scheduling
        uids.append(eng.submit(prompt, steps, sampling=dataclasses.replace(
            sp, seed=args.seed + i)))
    t0 = time.perf_counter()
    results = eng.run()
    dt = time.perf_counter() - t0
    n = sum(len(v) for v in results.values())
    print(f"[serve/continuous] {len(results)} requests, {n} tokens in "
          f"{dt:.2f}s ({n/dt:.1f} tok/s) with {args.max_slots} slots, "
          f"decode_kernel={args.decode_kernel}, "
          f"prefill_kernel={args.prefill_kernel}, paged={args.paged}, "
          f"fused_sampling={fused}")
    if args.temperature > 0:
        print(f"[serve/continuous] sampling: temperature={args.temperature} "
              f"top_k={args.top_k} top_p={args.top_p} min_p={args.min_p} "
              f"seeds={args.seed}..{args.seed + args.requests - 1}")
    if args.paged:
        print(f"[serve/continuous] page pool: {scfg.num_pages} pages x "
              f"{scfg.page_size} rows "
              f"(peak in use {eng.pool.peak_in_use}) vs "
              f"{args.max_slots} x {scfg.max_seq} contiguous rows")
        if scfg.prefix_cache:
            print(f"[serve/continuous] prefix cache ({scfg.prefix_evict}): "
                  f"{eng.pool.prefix_hit_rows} prompt rows served from "
                  f"cached pages, {eng.pool.cow_copies} cow copies, "
                  f"{eng.pool.evictions} evictions")
    if uids:
        print("[serve/continuous] sample:", results[uids[0]])


if __name__ == "__main__":
    main()
