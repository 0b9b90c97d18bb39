"""Serving-path throughput: continuous batching vs the static batch, the
fused prefill/decode ConSmax kernels vs the jnp walks, and the paged KV
pool vs contiguous per-slot rows.

Four measurements:

* **engine** — a queue of heterogeneous requests (random prompt lengths and
  token budgets) served by (a) the static ``ServeSession`` (everyone padded
  to the longest prompt, decoded for the largest budget — the seed behaviour)
  and (b) the slot-recycling ``ContinuousBatchingEngine``, with fused
  in-step sampling (the default) AND the legacy host-sampling baseline
  (``fused_sampling=False``: a (max_slots, vocab) logits transfer plus a
  host sampling pass per token) — the fused-vs-host gap is the op-fusion
  claim, measured. Useful-token throughput counts only requested tokens,
  so static-batch padding waste shows up directly.
* **prefill** — prompt tokens/s of a prefill-only queue (one-token budgets:
  the first token samples from the final chunk's logits, so no decode step
  ever runs), jnp KV walk vs the fused ``consmax_prefill`` kernel, on
  contiguous rows and on the page pool.
* **step** — wall time of one jitted decode step at a pinned cache length,
  jnp row attention vs the split-KV Pallas kernel (interpret mode on CPU;
  the kernel numbers are architecture-mirrors, not CPU speedups), plus a
  **fill sweep** on the ``L4096_b8_splitkv`` acceptance shape: the
  fill-bounded kernel grid vs the capacity-swept baseline at quarter and
  full fill (``decode_step_fill_us``). The full-fill gap is the bounded
  kernel's batch-fold (per-program overhead amortized across slots); the
  extra quarter-fill gap on top of it is fill bounding proper.
* **paged** (``--paged``) — paged-vs-contiguous engine tok/s with peak page
  occupancy on the same queue, plus one decode step of the ``long_500k``
  shape served from a page pool holding FEWER total KV cells than
  ``max_slots x max_seq`` — the HBM claim of the paged design, measured.
* **prefix_share** (every mode) — prefill tok/s and mean TTFT of a
  shared-system-prompt workload at prefix-share ratios {0, 0.5, 0.9} on
  the paged engine with the prefix cache on: the cache is seeded by one
  request carrying the shared prefix, then a queue of requests sharing
  that prefix is timed — the production steady state, where every request
  after the first skips the shared rows' prefill entirely. Throughput
  counts *submitted* prompt tokens, so the warm speedup is user-visible
  tok/s, not an internal accounting trick.
* **sharded** (``--mesh``) — the device-mesh family: engine decode tok/s
  and one-chunk prefill tok/s at tp in {1, 2, 4} (tp=1 is the unsharded
  reference on identical work), the ``long_500k`` decode step served from
  a page pool spread over 4 sequence shards (each device resident for a
  quarter of the pool), and per-step collective bytes parsed from the
  compiled partitioned HLO — the measured form of the contract that
  sharded serving moves only output-sized ConSmax partials, never the
  cache. Needs tp * ns devices (forced host devices on CPU).
* **kv_bytes** (every mode) — the quantized-KV claim: static cache bytes
  per resident token for bf16 vs int8 (per-row fp32 scale leaves counted
  against the int8 side), with the bf16/int8 ratio **asserted >= 1.5x**,
  plus one decode step and engine tok/s on each cache dtype with the
  split-KV kernel on — the in-VMEM dequant path vs the bf16 baseline on
  identical work. In ``--paged`` the ``long_500k`` step also runs on an
  int8 pool (``long_500k_step_us_int8``).

Besides the CSV rows on stdout, the run writes ``BENCH_serve.json``
(``--json-out``) — decode tok/s (fused and host-sampling), prefill tok/s,
decode-step latencies, the ``long_500k`` step, and page occupancy in one
machine-readable dict — so the serving perf trajectory is recorded per
commit (CI uploads it as an artifact). A schema assertion runs before the
write: a refactor that drops an expected key fails the benchmark instead
of silently thinning the artifact.

    PYTHONPATH=src python benchmarks/decode_throughput.py            # quick
    PYTHONPATH=src python benchmarks/decode_throughput.py --paged    # page pool
    PYTHONPATH=src python benchmarks/decode_throughput.py --full     # paper axes
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import random
from jax.tree_util import tree_map_with_path

from benchmarks.common import bench_wall, emit
from repro.analysis.trace_guard import TraceGuard
from repro.configs.base import SHAPES, ServeConfig
from repro.configs.registry import get_config
from repro.kernels import cache_layout as CL
from repro.models import transformer as T
from repro.nn.module import Ctx
from repro.serve import sampling as S
from repro.serve.engine import (ContinuousBatchingEngine, ServeSession,
                                make_serve_fns)


def _workload(key, n_requests, vocab, max_prompt=24, max_steps=12):
    """Heterogeneous (prompt, budget) pairs; the spread is the point."""
    reqs = []
    for i in range(n_requests):
        k1, k2, k3 = random.split(random.fold_in(key, i), 3)
        plen = 1 + int(random.randint(k1, (), 0, max_prompt))
        steps = 1 + int(random.randint(k2, (), 0, max_steps))
        prompt = random.randint(k3, (plen,), 0, vocab).tolist()
        reqs.append((prompt, steps))
    return reqs


def _static_toks_per_s(cfg, params, reqs, max_seq):
    """Everyone padded to the longest prompt, decoded for the largest budget.

    Prompts are right-padded but prefilled with per-request length masking
    (``generate(lengths=...)``): pad K/V never enters the cache and each row
    decodes from its own real position — so the "useful tokens" the baseline
    is credited with are computed on each request's true context, not on
    pad-token context."""
    sess = ServeSession(cfg, ServeConfig(max_seq=max_seq), params)
    plen = max(len(p) for p, _ in reqs)
    steps = max(s for _, s in reqs)
    batch = jnp.asarray([p + [0] * (plen - len(p)) for p, _ in reqs],
                        jnp.int32)
    lengths = jnp.asarray([len(p) for p, _ in reqs], jnp.int32)
    sess.generate(batch, steps=steps, lengths=lengths)     # compile
    t0 = time.perf_counter()
    jax.block_until_ready(sess.generate(batch, steps=steps, lengths=lengths))
    dt = time.perf_counter() - t0
    useful = sum(s for _, s in reqs)
    return useful / dt


def _continuous_toks_per_s(cfg, params, reqs, max_seq, slots, decode_kernel,
                           paged=False, fused=True, kv_dtype="bfloat16",
                           tp=1, seq_shards=1):
    """``fused=False`` serves with the legacy host-sampling steps (logits
    shipped to the host per token) — the A/B baseline for the fused
    in-step epilogue. ``tp``/``seq_shards`` > 1 serve from the sharded
    engine (forced host devices on CPU)."""
    # prefix cache OFF: serve() runs the same queue twice (compile + timed),
    # so a warm second pass would measure the prefix cache instead of the
    # memory layout — the dedicated prefix_share rows measure that
    scfg = ServeConfig(max_seq=max_seq, prefill_chunk=8, max_slots=slots,
                       decode_kernel=decode_kernel, paged_kv=paged,
                       page_size=8 if paged else 256, fused_sampling=fused,
                       prefix_cache=False, kv_cache_dtype=kv_dtype,
                       tp=tp, seq_shards=seq_shards)
    eng = ContinuousBatchingEngine(cfg, scfg, params)
    # the analysis-layer trace guard replaces the old ad-hoc cache_size
    # asserts: the whole benchmark workload — ragged admissions, decode,
    # slot recycling — must leave ONE compiled shape per step, or the
    # throughput rows are measuring compile stalls
    guard = TraceGuard.for_engine(eng, limit=1)

    def serve():
        done = len(eng.results)
        for prompt, steps in reqs:
            eng.submit(prompt, steps)
        eng.run()
        return sum(len(v) for u, v in eng.results.items() if u >= done)

    serve()                                                # compile
    t0 = time.perf_counter()
    useful = serve()
    dt = time.perf_counter() - t0
    guard.assert_ok()
    occ = (eng.pool.peak_in_use / scfg.num_pages) if paged else 0.0
    # peak committed (reserved) pages: includes reserved-but-unmapped
    # pressure that occupancy can't see — the quantity gating admission
    resv = (eng.pool.peak_reserved / scfg.num_pages) if paged else 0.0
    return useful / dt, occ, resv


def _prefill_step_tok_s(cfg, params, prefill_kernel, paged=False, chunk=8,
                        max_seq=48, iters=20, tp=1):
    """Prompt tokens/s of ONE jitted append-prefill chunk step — the
    engine's actual compiled hot path (``ContinuousBatchingEngine._prefill``,
    jnp KV walk vs the fused consmax_prefill kernel), measured like the
    decode ``step`` rows so host-side queue scheduling doesn't drown the
    device-side difference. The slot's index is pinned to mid-fill before
    every timed call (outside the window): a prefill chunk's job is
    attending ``cache[0:index]`` + itself, so an empty cache would be the
    least representative state. Best-of-N, like any microbenchmark."""
    scfg = ServeConfig(max_seq=max_seq, prefill_chunk=chunk, max_slots=4,
                       prefill_kernel=prefill_kernel, paged_kv=paged,
                       page_size=chunk if paged else 256, tp=tp)
    eng = ContinuousBatchingEngine(cfg, scfg, params)
    slot_i = 1
    slot = jnp.asarray(slot_i, jnp.int32)
    toks = jnp.zeros((1, chunk), jnp.int32)
    lens = jnp.asarray([chunk], jnp.int32)
    fill = (max_seq // 2) // chunk * chunk                 # chunk-aligned
    pin = jax.jit(lambda c: _pin_index(c, fill, slot=slot_i))
    page_row = None
    if paged:
        eng.pool.reserve(slot_i, fill + 2 * chunk)
        eng.pool.ensure(slot_i, fill + chunk)
        page_row = eng._device_table()[slot_i:slot_i + 1]
    caches = pin(eng.caches)
    out, caches = eng._prefill(params, caches, slot, toks, lens,
                               eng.bank, page_row)         # compile
    ts = []
    for _ in range(iters):
        caches = pin(caches)                               # back to mid-fill
        t0 = time.perf_counter()
        out, caches = eng._prefill(params, caches, slot, toks, lens,
                                   eng.bank, page_row)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    best = float(np.min(ts))
    return chunk / best, best * 1e6


def _pin_index(caches, value, slot=None):
    """Set cache ``index`` leaves to ``value`` — every slot, or just one."""
    def pin(p, a):
        if getattr(p[-1], "key", None) != "index":
            return a
        return (jnp.full_like(a, value) if slot is None
                else a.at[:, slot].set(value))
    return tree_map_with_path(pin, caches)


def _step_us(cfg, params, batch, cache_len, decode_kernel, fused=False,
             fill=None, fill_bound=True, kv_dtype="bfloat16"):
    """One jitted decode step at a pinned cache length. ``fused=True``
    measures the production token-emitting step (sampling epilogue inside,
    (batch,) int32 out); ``fused=False`` the legacy logits-returning step —
    the pair isolates the epilogue's device cost from the engine-level
    host-transfer savings. ``fill`` pins the per-slot index below capacity
    (default: capacity) and ``fill_bound=False`` sweeps the full
    capacity-sized KV grid regardless of fill — the A/B pair behind the
    ``decode_step_fill_us`` rows."""
    scfg = ServeConfig(max_seq=cache_len, decode_kernel=decode_kernel,
                       fused_sampling=fused, fill_bound=fill_bound,
                       kv_cache_dtype=kv_dtype)
    init_caches, _, decode_step, _ = make_serve_fns(cfg, scfg)
    caches = _pin_index(init_caches(batch),
                        (cache_len if fill is None else fill) - 1)
    if fused:
        args = (params, caches, {"tokens": jnp.zeros((batch,), jnp.int32)},
                S.bank_init(batch))
    else:
        args = (params, caches,
                {"tokens": jnp.zeros((batch, 1), jnp.int32)})
    fn = jax.jit(decode_step)
    return bench_wall(fn, *args, iters=3, warmup=1)


def _paged_long_step(cfg, params, rows, report):
    """One decode step of the long_500k shape against a page pool that holds
    FEWER total KV cells than the contiguous max_slots x max_seq block —
    the acceptance shape of the paged design. Slot 0 sits at full 500k
    context; the other slots are idle, holding zero pages. Runs twice, on
    a bf16 and an int8 cache: long context is exactly where the quantized
    pool's smaller resident bytes matter, so the A/B is part of the
    artifact (``long_500k_step_us`` vs ``long_500k_step_us_int8``)."""
    L, _, _ = SHAPES["long_500k"]
    max_slots, page_size = 4, 1024
    num_pages = -(-L // page_size) + 8                     # thin headroom
    total_cells = num_pages * page_size
    contiguous_cells = max_slots * L
    assert total_cells < contiguous_cells, (total_cells, contiguous_cells)
    table = np.full((max_slots, -(-L // page_size)), -1, np.int32)
    table[0, :] = np.arange(-(-L // page_size))
    active = np.zeros((max_slots,), bool)
    active[0] = True
    inputs = {"tokens": jnp.zeros((max_slots, 1), jnp.int32),
              "active": jnp.asarray(active),
              "page_table": jnp.asarray(table)}
    for suffix, dt in (("", "bfloat16"), ("_int8", "int8")):
        # legacy logits step: this cell measures the (batch, vocab) surface
        scfg = ServeConfig(max_seq=L, max_slots=max_slots, paged_kv=True,
                           page_size=page_size, num_pages=num_pages,
                           fused_sampling=False, kv_cache_dtype=dt)
        caches = T.init_paged_caches(cfg, max_slots, num_pages, page_size,
                                     kv_dtype=CL.kv_cache_dtype(dt))
        caches = tree_map_with_path(
            lambda p, a: a.at[:, 0].set(L - 1)
            if getattr(p[-1], "key", None) == "index" else a, caches)
        _, _, decode_step, _ = make_serve_fns(cfg, scfg)
        us = bench_wall(jax.jit(decode_step), params, caches, inputs,
                        iters=2, warmup=1)
        rows.append((f"serve/paged_long500k_step{suffix}_us", f"{us:.0f}",
                     f"cells={total_cells};contiguous={contiguous_cells};"
                     f"saving={1 - total_cells/contiguous_cells:.2%}"))
        report[f"long_500k_step_us{suffix}"] = us
    report["long_500k_cells"] = {"paged": total_cells,
                                 "contiguous": contiguous_cells}


def _decode_collective_bytes(cfg, params, max_seq, slots, tp):
    """Per-step collective bytes of the sharded fused decode step, from the
    compiled partitioned HLO (trip counts included) — the traffic side of
    the tensor-parallel claim: one output-sized ConSmax-partial psum plus
    one head all_gather per layer, never anything cache-sized."""
    from repro.analysis.collective_contract import step_collective_bytes
    from repro.distributed.hlo_analysis import list_collectives
    scfg = ServeConfig(max_seq=max_seq, prefill_chunk=8, max_slots=slots,
                       decode_kernel=True, prefix_cache=False, tp=tp)
    eng = ContinuousBatchingEngine(cfg, scfg, params)
    inputs = {"tokens": jnp.zeros((slots,), jnp.int32),
              "active": jnp.ones((slots,), jnp.bool_)}
    hlo = (eng._decode.lower(eng.params, eng.caches, inputs, eng.bank)
           .compile().as_text())
    return step_collective_bytes(list_collectives(hlo, num_devices=tp))


def _sharded_long_step(cfg, params, seq_shards):
    """One decode step of the long_500k shape from a page pool spread over
    ``seq_shards`` devices — the memory point of sequence sharding: each
    device holds ``num_pages / seq_shards`` pages, so the resident pool
    can exceed one device's memory. Mirrors ``_paged_long_step`` (slot 0
    at full 500k context) but builds the step through the mesh plan, with
    in-step page-table localization, exactly as the engine wires it.
    Returns (step_us, per-step collective bytes)."""
    from repro.analysis.collective_contract import step_collective_bytes
    from repro.distributed import serve_mesh as SM
    from repro.distributed.hlo_analysis import list_collectives
    L, _, _ = SHAPES["long_500k"]
    max_slots, page_size = 4, 1024
    pages_used = -(-L // page_size)
    # thin headroom, rounded up so the pool splits evenly across shards
    num_pages = -(-(pages_used + 8) // seq_shards) * seq_shards
    assert num_pages * page_size < max_slots * L
    scfg = ServeConfig(max_seq=L, max_slots=max_slots, paged_kv=True,
                       page_size=page_size, num_pages=num_pages,
                       fused_sampling=False, seq_shards=seq_shards)
    plan = SM.plan_mesh(cfg, scfg)
    _, _, decode_fn, _ = make_serve_fns(plan.cfg_local, scfg,
                                        psum_axes=plan.psum_axes)

    def body(params, caches, inputs):
        inputs = dict(inputs, page_table=CL.localize_page_table(
            inputs["page_table"], jax.lax.axis_index(SM.SEQ_AXIS),
            plan.pages_per_shard))
        return decode_fn(params, caches, inputs)

    caches = T.init_paged_caches(cfg, max_slots, num_pages, page_size)
    caches = _pin_index(caches, L - 1, slot=0)
    pspec = plan.param_specs(params)
    cspec = plan.cache_specs(caches, paged=True, quantized=False)
    P0 = SM.P()
    step = jax.jit(plan.wrap(body, (pspec, cspec, P0), (P0, cspec)))
    params_s = plan.put(params, jax.tree.map(plan.named, pspec))
    caches = plan.put(caches, jax.tree.map(plan.named, cspec))
    table = np.full((max_slots, pages_used), -1, np.int32)
    table[0, :] = np.arange(pages_used)
    active = np.zeros((max_slots,), bool)
    active[0] = True
    inputs = {"tokens": jnp.zeros((max_slots, 1), jnp.int32),
              "active": jnp.asarray(active),
              "page_table": jnp.asarray(table)}
    us = bench_wall(step, params_s, caches, inputs, iters=2, warmup=1)
    hlo = step.lower(params_s, caches, inputs).compile().as_text()
    colls = step_collective_bytes(
        list_collectives(hlo, num_devices=plan.tp * plan.seq_shards))
    return us, colls, num_pages // seq_shards


def _sharded_rows(arch, rows, report):
    """The ``sharded`` family: decode/prefill tok/s at tp in {1, 2, 4}
    (the tp=1 row is the unsharded reference on identical work), the
    long_500k step on a sequence-sharded pool, and per-step collective
    bytes from the compiled partitioned programs."""
    if jax.device_count() < 4:
        raise SystemExit(
            f"--mesh needs 4 devices, have {jax.device_count()}. On CPU: "
            "export XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "before jax initializes.")
    # smoke configs default to one KV head, which tp > 1 cannot divide
    cfg = get_config(arch, smoke=True, n_kv_heads=4)
    params = T.lm_init(Ctx(random.key(0)), cfg)
    sh = report["sharded"] = {}
    reqs = _workload(random.key(21), 6, cfg.vocab_size)
    for tp in (1, 2, 4):
        tps, _, _ = _continuous_toks_per_s(cfg, params, reqs, 48, 4, True,
                                           tp=tp)
        pf, pf_us = _prefill_step_tok_s(cfg, params, True, chunk=128,
                                        max_seq=1024, iters=5, tp=tp)
        rows.append((f"serve/sharded_decode_tp{tp}_tok_s", f"{tps:.1f}",
                     "continuous;split_kv;fused_sampling"))
        rows.append((f"serve/sharded_prefill_tp{tp}_tok_s", f"{pf:.1f}",
                     f"chunk=128;L=1024;step={pf_us:.0f}us"))
        sh[f"decode_tok_s_tp{tp}"] = tps
        sh[f"prefill_tok_s_tp{tp}"] = pf
        if tp > 1:
            colls = _decode_collective_bytes(cfg, params, 48, 4, tp)
            rows.append((f"serve/sharded_decode_tp{tp}_collective_bytes",
                         f"{colls['total_bytes']}",
                         ";".join(f"{k}={v}" for k, v
                                  in sorted(colls["bytes_by_kind"].items()))
                         or "none"))
            sh[f"decode_collective_bytes_tp{tp}"] = colls["total_bytes"]
    ns = 4
    us, colls, per_shard = _sharded_long_step(cfg, params, ns)
    rows.append((f"serve/sharded_long500k_step_ns{ns}_us", f"{us:.0f}",
                 f"pages_per_shard={per_shard};"
                 f"collective_bytes={colls['total_bytes']}"))
    sh["long_500k_step_us_seqsharded"] = us
    sh["long_500k_collective_bytes"] = colls["total_bytes"]
    sh["long_500k_seq_shards"] = ns
    sh["long_500k_pages_per_shard"] = per_shard


def _kv_bytes_per_token(cfg, kv_dtype, batch=8, max_seq=4096):
    """Static cache bytes per resident token: every non-``index`` leaf of
    the contiguous cache tree — K/V data plus, in quantized modes, the
    per-row fp32 ``k_scale``/``v_scale`` leaves — over batch * max_seq
    token slots. Counted from the real ``init_caches`` tree, not a formula,
    so a layout change (extra leaves, wider scales) shows up here."""
    caches = jax.eval_shape(
        lambda: T.init_caches(cfg, batch, max_seq,
                              kv_dtype=CL.kv_cache_dtype(kv_dtype)))
    flat, _ = jax.tree_util.tree_flatten_with_path(caches)
    total = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                for path, leaf in flat
                if getattr(path[-1], "key", "") != "index")
    return total / (batch * max_seq)


def _kv_bytes_rows(cfg, params, rows, report):
    """The quantized-KV HBM claim, measured two ways: static cache bytes
    per resident token (bf16 vs int8, scale rows included) and the same
    decode workload served from both cache dtypes with the split-KV kernel
    on. The byte ratio is asserted >= 1.5x — the acceptance bar for the
    int8 mode: a layout regression that silently fattens the quantized
    cache (say, per-row scales becoming per-element) fails the benchmark
    run instead of shipping a thinner win."""
    per = {}
    for name, dt in (("bf16", "bfloat16"), ("int8", "int8")):
        bpt = _kv_bytes_per_token(cfg, dt)
        per[name] = bpt
        rows.append((f"serve/kv_bytes_per_token_{name}", f"{bpt:.1f}",
                     "cache_bytes_per_resident_token;scales_included"))
        report["kv_bytes"][f"per_token_{name}"] = bpt
    ratio = per["bf16"] / per["int8"]
    assert ratio >= 1.5, (
        f"int8 KV cache holds only {ratio:.2f}x fewer bytes per resident "
        "token than bf16 (acceptance bar: >= 1.5x) — the quantized layout "
        "or its scale rows regressed")
    rows.append(("serve/kv_bytes_ratio", f"{ratio:.2f}x",
                 "bf16_over_int8;acceptance>=1.5x"))
    report["kv_bytes"]["ratio_bf16_over_int8"] = ratio
    # the same decode work on each cache dtype, split-KV kernel on: one
    # jitted step at a pinned fill (the in-VMEM dequant's device cost) and
    # engine tok/s on a shared queue (the end-to-end serving surface)
    reqs = _workload(random.key(11), 4, cfg.vocab_size)
    for name, dt in (("bf16", "bfloat16"), ("int8", "int8")):
        us = _step_us(cfg, params, 8, 1024, True, kv_dtype=dt)
        tps, _, _ = _continuous_toks_per_s(cfg, params, reqs, 48, 4, True,
                                           kv_dtype=dt)
        rows.append((f"serve/kv_{name}_step_L1024_b8_us", f"{us:.0f}",
                     "splitkv;interpret_on_cpu"))
        rows.append((f"serve/kv_{name}_decode_tok_s", f"{tps:.1f}",
                     "continuous;decode_kernel"))
        report["kv_bytes"][f"step_L1024_b8_{name}_us"] = us
        report["kv_bytes"][f"decode_tok_s_{name}"] = tps


def _prefix_share_rows(cfg, params, rows, report):
    """Prefill tok/s + mean TTFT at prefix-share ratios {0, 0.5, 0.9}.

    One paged prefix-caching engine serves three rounds. Per round, N
    one-token-budget requests share the first ``share * P`` prompt tokens
    (page-aligned) with unique suffixes; a seed request carrying just the
    shared prefix runs to completion first, so the timed queue measures
    the steady-state warm path — the production shape, where every request
    after the first shares the system prompt. tok/s counts *submitted*
    prompt tokens over wall time: warm admissions prefill only the unique
    suffix, and the saved chunks are exactly the speedup. Token streams
    per round use distinct keys, so rounds cannot warm each other."""
    P, n_req, chunk = 80, 6, 8
    scfg = ServeConfig(max_seq=128, prefill_chunk=chunk, max_slots=2,
                       paged_kv=True, page_size=8, num_pages=48)
    eng = ContinuousBatchingEngine(cfg, scfg, params)
    guard = TraceGuard.for_engine(eng, limit=1)
    # compile the cold path, then the warm path (set_index + tail re-score)
    warm = random.randint(random.key(99), (P,), 0, cfg.vocab_size).tolist()
    eng.submit(warm, 1)
    eng.run()
    eng.submit(warm[:40], 1)
    eng.run()
    tok_s = {}
    for share, label in ((0.0, "0"), (0.5, "50"), (0.9, "90")):
        key = random.fold_in(random.key(3), int(share * 100))
        pre = int(P * share)                        # 0/40/72: page-aligned
        common = random.randint(random.fold_in(key, 0), (P,), 0,
                                cfg.vocab_size).tolist()
        if pre:
            eng.submit(common[:pre], 1)             # seed the prefix cache
            eng.run()
        prompts = [common[:pre]
                   + random.randint(random.fold_in(key, 1 + i), (P - pre,),
                                    0, cfg.vocab_size).tolist()
                   for i in range(n_req)]
        t0 = time.perf_counter()
        uids = [eng.submit(p, 1) for p in prompts]
        eng.run()
        dt = time.perf_counter() - t0
        tps = n_req * P / dt
        times = [eng.request_times[u] for u in uids]
        ttft_ms = 1e3 * float(np.mean([t["first_token"] - t["submitted"]
                                       for t in times]))
        tok_s[label] = tps
        rows.append((f"serve/prefix_share_{label}_prefill_tok_s",
                     f"{tps:.1f}", f"share={share};P={P};n={n_req}"))
        rows.append((f"serve/prefix_share_{label}_ttft_ms", f"{ttft_ms:.2f}",
                     "mean_submit_to_first_token"))
        report["prefix_share"][f"share{label}_prefill_tok_s"] = tps
        report["prefix_share"][f"share{label}_ttft_ms"] = ttft_ms
    guard.assert_ok()
    speedup = tok_s["90"] / tok_s["0"]
    rows.append(("serve/prefix_share_90_speedup", f"{speedup:.2f}x",
                 "submitted_prompt_tok_s_vs_share0"))
    report["prefix_share"]["share90_speedup_vs_share0"] = speedup


def _assert_sharded_schema(report):
    num = (int, float)
    sh = report.get("sharded")
    assert isinstance(sh, dict), (
        "BENCH_serve.json schema: 'sharded' family missing in --mesh")
    for tp in (1, 2, 4):
        for k in (f"decode_tok_s_tp{tp}", f"prefill_tok_s_tp{tp}"):
            assert isinstance(sh.get(k), num), (
                f"BENCH_serve.json schema: sharded[{k!r}] missing — the "
                "tp sweep is part of the artifact")
    for tp in (2, 4):
        assert isinstance(sh.get(f"decode_collective_bytes_tp{tp}"), int), (
            f"BENCH_serve.json schema: sharded decode collective bytes "
            f"missing for tp={tp}")
    for k in ("long_500k_step_us_seqsharded", "long_500k_collective_bytes",
              "long_500k_seq_shards", "long_500k_pages_per_shard"):
        assert isinstance(sh.get(k), num), (
            f"BENCH_serve.json schema: sharded[{k!r}] missing — the "
            "seq-sharded long_500k step is part of the artifact")


def _assert_schema(report, batches, cache_lens, step_batches, paged):
    """The CI artifact contract: a refactor that silently drops a key (or
    writes a non-numeric value) fails the benchmark run instead of
    producing a quietly thinner BENCH_serve.json."""
    for key, typ in (("arch", str), ("mode", str), ("paged", bool),
                     ("decode_tok_s", dict), ("prefill_tok_s", dict),
                     ("decode_step_us", dict), ("decode_step_fill_us", dict),
                     ("page_occupancy", dict), ("prefix_share", dict),
                     ("kv_bytes", dict)):
        assert isinstance(report.get(key), typ), (
            f"BENCH_serve.json schema: missing/mistyped {key!r}")
    num = (int, float)
    for n in batches:
        for k in (f"static_b{n}", f"continuous_b{n}",
                  f"continuous_kernel_b{n}", f"continuous_hostsample_b{n}"):
            assert isinstance(report["decode_tok_s"].get(k), num), (
                f"BENCH_serve.json schema: decode_tok_s[{k!r}] missing — "
                "fused-vs-host sampling rows are part of the artifact")
    labels = ("contiguous",) + (("paged",) if paged else ())
    for label in labels:
        for k in (f"{label}_jnp", f"{label}_kernel"):
            assert isinstance(report["prefill_tok_s"].get(k), num), (
                f"BENCH_serve.json schema: prefill_tok_s[{k!r}] missing")
    for L in cache_lens:
        for b in step_batches:
            for k in (f"L{L}_b{b}_row", f"L{L}_b{b}_splitkv",
                      f"L{L}_b{b}_fused"):
                assert isinstance(report["decode_step_us"].get(k), num), (
                    f"BENCH_serve.json schema: decode_step_us[{k!r}] missing")
    # prefix-share rows also run in every mode: the warm-admission path is
    # the tentpole claim, so the artifact must always carry it
    for lbl in ("0", "50", "90"):
        for k in (f"share{lbl}_prefill_tok_s", f"share{lbl}_ttft_ms"):
            assert isinstance(report["prefix_share"].get(k), num), (
                f"BENCH_serve.json schema: prefix_share[{k!r}] missing")
    assert isinstance(report["prefix_share"].get("share90_speedup_vs_share0"),
                      num), ("BENCH_serve.json schema: prefix_share speedup "
                             "row missing")
    # fill-sweep rows run in every mode on the acceptance shape: losing them
    # means the fill-bounded path silently stopped being measured
    for frac in ("25", "100"):
        for kind in ("capacity", "bounded", "speedup"):
            k = f"L4096_b8_fill{frac}_{kind}"
            assert isinstance(report["decode_step_fill_us"].get(k), num), (
                f"BENCH_serve.json schema: decode_step_fill_us[{k!r}] "
                "missing — the fill-bounded vs capacity-swept A/B is part "
                "of the artifact")
    # quantized-KV rows run in every mode: the byte ratio is the acceptance
    # claim of the int8 cache, so the artifact must always carry the family
    for k in ("per_token_bf16", "per_token_int8", "ratio_bf16_over_int8",
              "step_L1024_b8_bf16_us", "step_L1024_b8_int8_us",
              "decode_tok_s_bf16", "decode_tok_s_int8"):
        assert isinstance(report["kv_bytes"].get(k), num), (
            f"BENCH_serve.json schema: kv_bytes[{k!r}] missing — the "
            "bf16-vs-int8 cache A/B is part of the artifact")
    if paged:
        for k in ("long_500k_step_us", "long_500k_step_us_int8"):
            assert isinstance(report.get(k), num), (
                f"BENCH_serve.json schema: {k} missing in --paged")
        for n in batches:
            for k in (f"engine_b{n}_peak", f"engine_b{n}_peak_reserved"):
                assert isinstance(report["page_occupancy"].get(k), num), (
                    f"BENCH_serve.json schema: page_occupancy[{k!r}] missing")


def run(arch="qwen2-1.5b", *, full=False, paged=False, mesh=False,
        json_out="BENCH_serve.json"):
    cfg = get_config(arch, smoke=True)
    params = T.lm_init(Ctx(random.key(0)), cfg)
    rows = []
    report = {"arch": arch, "mode": "full" if full else "quick",
              "paged": paged, "decode_tok_s": {}, "prefill_tok_s": {},
              "decode_step_us": {}, "decode_step_fill_us": {},
              "page_occupancy": {}, "prefix_share": {}, "kv_bytes": {},
              "long_500k_step_us": None}

    # ---- engine: static vs continuous on the same request queue ----
    batches = (1, 8, 64) if full else (1, 4, 8)
    for n in batches:
        reqs = _workload(random.key(7), n, cfg.vocab_size)
        max_seq = 48
        slots = min(4, n)
        st = _static_toks_per_s(cfg, params, reqs, max_seq)
        co, _, _ = _continuous_toks_per_s(cfg, params, reqs, max_seq, slots,
                                          False)
        ck, _, _ = _continuous_toks_per_s(cfg, params, reqs, max_seq, slots,
                                          True)
        # host-sampling baseline: same engine, logits shipped per token and
        # sampled host-side (the pre-fused-epilogue serving path)
        ho, _, _ = _continuous_toks_per_s(cfg, params, reqs, max_seq, slots,
                                          False, fused=False)
        rows.append((f"serve/static_b{n}_tok_s", f"{st:.1f}", "useful_tokens"))
        rows.append((f"serve/continuous_b{n}_tok_s", f"{co:.1f}",
                     f"slots={slots};fused_sampling"))
        rows.append((f"serve/continuous_kernel_b{n}_tok_s", f"{ck:.1f}",
                     f"slots={slots};split_kv"))
        rows.append((f"serve/continuous_hostsample_b{n}_tok_s", f"{ho:.1f}",
                     f"slots={slots};per_token_logits_transfer"))
        rows.append((f"serve/continuous_b{n}_speedup", f"{co/st:.3f}x",
                     "vs_static_useful"))
        rows.append((f"serve/fused_sampling_b{n}_speedup", f"{co/ho:.3f}x",
                     "vs_host_sampling"))
        report["decode_tok_s"][f"static_b{n}"] = st
        report["decode_tok_s"][f"continuous_b{n}"] = co
        report["decode_tok_s"][f"continuous_kernel_b{n}"] = ck
        report["decode_tok_s"][f"continuous_hostsample_b{n}"] = ho
        if paged:
            pg, occ, resv = _continuous_toks_per_s(cfg, params, reqs,
                                                   max_seq, slots, False,
                                                   paged=True)
            rows.append((f"serve/paged_b{n}_tok_s", f"{pg:.1f}",
                         f"slots={slots};peak_occupancy={occ:.2f};"
                         f"peak_reserved={resv:.2f}"))
            rows.append((f"serve/paged_b{n}_vs_contiguous", f"{pg/co:.3f}x",
                         "same_queue"))
            report["decode_tok_s"][f"paged_b{n}"] = pg
            report["page_occupancy"][f"engine_b{n}_peak"] = occ
            report["page_occupancy"][f"engine_b{n}_peak_reserved"] = resv

    # ---- prefill: chunked append step tok/s, jnp KV walk vs fused kernel ----
    # chunk 128 against a 1024-row cache at mid-fill: big enough that the
    # attention walk (not the smoke model's MLP/unembed) dominates the step
    for label, pg in (("contiguous", False),) + ((("paged", True),)
                                                 if paged else ()):
        jn, jn_us = _prefill_step_tok_s(cfg, params, False, paged=pg,
                                        chunk=128, max_seq=1024)
        kr, kr_us = _prefill_step_tok_s(cfg, params, True, paged=pg,
                                        chunk=128, max_seq=1024)
        rows.append((f"serve/prefill_{label}_jnp_tok_s", f"{jn:.1f}",
                     f"chunk=128;L=1024;step={jn_us:.0f}us"))
        rows.append((f"serve/prefill_{label}_kernel_tok_s", f"{kr:.1f}",
                     f"step={kr_us:.0f}us;{kr/jn:.3f}x_vs_jnp_walk"))
        report["prefill_tok_s"][f"{label}_jnp"] = jn
        report["prefill_tok_s"][f"{label}_kernel"] = kr

    # ---- step: decode latency vs cache length, jnp row vs split-KV ----
    cache_lens = (1024, 8192, 32768) if full else (1024, 4096)
    step_batches = (1, 8, 64) if full else (1, 8)
    for L in cache_lens:
        for b in step_batches:
            us_row = _step_us(cfg, params, b, L, False)
            us_ker = _step_us(cfg, params, b, L, True)
            us_fus = _step_us(cfg, params, b, L, False, fused=True)
            rows.append((f"serve/step_L{L}_b{b}_row_us", f"{us_row:.0f}",
                         f"{1e6*b/us_row:.1f}tok_s"))
            rows.append((f"serve/step_L{L}_b{b}_splitkv_us", f"{us_ker:.0f}",
                         f"{1e6*b/us_ker:.1f}tok_s;interpret_on_cpu"))
            rows.append((f"serve/step_L{L}_b{b}_fused_us", f"{us_fus:.0f}",
                         f"{1e6*b/us_fus:.1f}tok_s;in_step_sampling"))
            report["decode_step_us"][f"L{L}_b{b}_row"] = us_row
            report["decode_step_us"][f"L{L}_b{b}_splitkv"] = us_ker
            report["decode_step_us"][f"L{L}_b{b}_fused"] = us_fus

    # ---- fill sweep: fill-bounded vs capacity-swept split-KV grids ----
    # the acceptance shape (L4096_b8_splitkv) at quarter and full fill,
    # run in EVERY mode: a capacity-sized grid pays the same no matter the
    # fill, a fill-bounded grid pays for live KV shards only (plus the
    # batch-fold's per-program amortization, which also shows at full fill)
    FL, FB = 4096, 8
    for frac, fill in (("25", FL // 4), ("100", FL)):
        cap = _step_us(cfg, params, FB, FL, True, fill=fill,
                       fill_bound=False)
        bnd = _step_us(cfg, params, FB, FL, True, fill=fill,
                       fill_bound=True)
        rows.append((f"serve/step_L{FL}_b{FB}_fill{frac}_capacity_us",
                     f"{cap:.0f}", "capacity_swept_grid"))
        rows.append((f"serve/step_L{FL}_b{FB}_fill{frac}_bounded_us",
                     f"{bnd:.0f}", f"{cap/bnd:.2f}x_vs_capacity"))
        report["decode_step_fill_us"][f"L{FL}_b{FB}_fill{frac}_capacity"] = cap
        report["decode_step_fill_us"][f"L{FL}_b{FB}_fill{frac}_bounded"] = bnd
        report["decode_step_fill_us"][f"L{FL}_b{FB}_fill{frac}_speedup"] = (
            cap / bnd)

    # ---- kv bytes: quantized vs bf16 cache, bytes + same-work latency ----
    _kv_bytes_rows(cfg, params, rows, report)

    # ---- paged: the long_500k shape on a sub-contiguous page pool ----
    if paged:
        _paged_long_step(cfg, params, rows, report)

    # ---- prefix sharing: warm-admission tok/s + TTFT, every mode ----
    _prefix_share_rows(cfg, params, rows, report)

    # ---- sharded: mesh tp sweep + seq-sharded long_500k (--mesh) ----
    if mesh:
        _sharded_rows(arch, rows, report)
    _assert_schema(report, batches, cache_lens, step_batches, paged)
    if mesh:
        _assert_sharded_schema(report)
    if json_out:
        with open(json_out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        rows.append(("serve/bench_json", json_out, "machine_readable"))
    emit(rows)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--full", action="store_true",
                    help="paper axes: batch 1-64, cache 1k-32k")
    ap.add_argument("--paged", action="store_true",
                    help="paged-KV rows: paged vs contiguous engine tok/s "
                         "+ occupancy, and one long_500k decode step on a "
                         "page pool smaller than max_slots x max_seq cells")
    ap.add_argument("--mesh", action="store_true",
                    help="sharded rows: decode/prefill tok/s at tp 1/2/4, "
                         "the long_500k step on a seq-sharded pool, and "
                         "per-step collective bytes from the partitioned "
                         "HLO (needs forced host devices on CPU)")
    ap.add_argument("--json-out", default="BENCH_serve.json",
                    help="machine-readable report path ('' disables)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    run(args.arch, full=args.full, paged=args.paged, mesh=args.mesh,
        json_out=args.json_out)
