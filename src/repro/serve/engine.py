"""Serving engines over per-layer KV caches.

Two drivers share the same jitted model steps:

* ``ServeSession`` — static batch: every request prefills and decodes in
  lockstep, so the batch runs as long as its longest member. Ragged prompt
  batches are supported via ``generate(..., lengths=...)``: the batch is
  prefilled with per-request masking (pad K/V zeroed, per-slot index pinned
  at the real length), so shorter requests' outputs are not corrupted by
  pad context.
* ``ContinuousBatchingEngine`` — slot-based continuous batching: a fixed
  pool of ``max_slots`` cache slots shares ONE compiled decode step; new
  requests are admitted into free slots from a FIFO queue and prefilled in
  fixed-size chunks appended directly at the slot's cache index (one
  compiled prefill shape ``(1, prefill_chunk)`` for the engine's whole
  lifetime — no per-bucket recompiles, no pad-token K/V in any slot row),
  decode steps advance all DECODING slots at their own per-slot positions
  (the cache's per-slot ``index`` vector drives masking and rope; an
  ``active`` mask keeps PREFILLING/free slots' rows untouched), and EOS /
  token-budget completion recycles the slot for the next queued request.

**Sampling is part of the jitted steps** (``ServeConfig.fused_sampling``,
the default): every request carries its own ``serve/sampling.SamplingParams``
(temperature, top-k/top-p/min-p, seed), mirrored into SoA ``(max_slots,)``
device banks that live next to the caches, and the steps end in the fused
``sample_tokens`` epilogue — so prefill and decode return ``(b,)`` int32
tokens, the decode loop feeds the last-token vector back device-side, and
the host only drains that small token array for EOS checks and recording.
No per-token ``(max_slots, vocab)`` logits transfer remains. Per-slot draw
keys are ``fold_in(seed_key, cache position)``, making a request's stream
reproducible regardless of co-resident traffic or slot placement. With
``fused_sampling=False`` the steps return logits as before and sampling
runs host-side through the SAME ``serve/sampling`` code — the dryrun cells
and the benchmark's fused-vs-host A/B baseline.

ConSmax serving uses the merged inference constant C = e^{-beta}/gamma
(paper Eq. 3) — ``merged=True`` throughout. ConSmax's sync-free
normalization is what makes the chunked prefill this simple: chunks
contribute independent ``exp(s-beta)/gamma @ v`` partials, so there is no
online-softmax rescale state to thread between admission chunks. With
``ServeConfig.decode_kernel=True`` the one-token decode path runs the
split-KV Pallas kernel (kernels/consmax_decode) instead of the jnp row
attention, and with ``ServeConfig.prefill_kernel=True`` every append-prefill
chunk (contiguous or paged) runs the fused kernel (kernels/consmax_prefill)
instead of the jnp KV walk — both consume the cache in its stored layout,
so no serving step ever transposes the cache (consmax archs only — anything
else raises at construction).
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig
from repro.distributed import serve_mesh as SM
from repro.kernels import cache_layout as CL
from repro.models import blocks as B
from repro.models import transformer as T
from repro.serve import sampling as S
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import PagePool, Scheduler

Span = jax.profiler.TraceAnnotation
_gc_open: list = []


def _gc_span(phase: str, info: dict):
    """``gc.callbacks`` hook: a ``gc`` span around every collection, on the
    profiler's clock, so that a host stall can be shown to be one or not.
    Registered once, when this module is imported."""
    if phase == "start":
        _gc_open.append(Span("gc", generation=info["generation"]))
        _gc_open[-1].__enter__()
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


gc.callbacks.append(_gc_span)


def _has_attention(cfg: ModelConfig) -> bool:
    return any(k in B.ATTN_KINDS for k in cfg.block_pattern)


def _attention_only(cfg: ModelConfig) -> bool:
    return all(k in B.ATTN_KINDS for k in cfg.block_pattern)


def _counts_experts(cfg: ModelConfig) -> bool:
    """Dropless expert layers: the fused decode step also returns their
    counts (``lm_apply``'s ``moe_counts``)."""
    return (cfg.moe is not None and cfg.moe.dropless
            and any(k in B.ATTN_MOE_KINDS for k in cfg.block_pattern))


def make_serve_fns(cfg: ModelConfig, scfg: ServeConfig, *, psum_axes=()):
    """Returns (init_caches, prefill_step, decode_step, prefill_ragged).

    ``psum_axes``: mesh axis names for sharded serving — the steps are
    then per-shard bodies meant to run under shard_map with ``cfg`` the
    per-shard view (serve_mesh.MeshPlan.cfg_local), and every attention
    block all-reduces its ConSmax output partial over these axes (one
    output-sized fp32 psum; see core.attention.attention_apply).

    With ``scfg.fused_sampling`` (the default) every step takes a trailing
    ``sampling`` argument — the SoA parameter bank from
    ``serve/sampling.bank_of``/``bank_init`` — and returns
    ``(tokens (b,) int32, caches)``: the logits→token epilogue runs inside
    the jitted step (per-slot keys from the post-step cache index), so no
    ``(b, vocab)`` array is ever produced as a step output. The fused
    decode step takes ``batch_inputs["tokens"]`` as the ``(b,)`` last-token
    vector (it reshapes internally) plus optional ``active`` (b,) bool —
    rows where False return their input token unchanged — and optional
    ``page_table``. Where the model has dropless expert layers
    (``_counts_experts``) the fused decode step returns ``((tokens,
    counts), caches)``, counts the (2,) int32 of ``lm_apply``'s
    ``moe_counts`` over the active slots.

    With ``fused_sampling=False`` the legacy logits-returning signatures
    are preserved exactly (decode tokens ``(b, 1)``; returns
    ``(logits (b, vocab), caches)``) for the dryrun cells and host-sampling
    baselines.
    """
    for flag, name, drop in ((scfg.decode_kernel, "decode_kernel",
                              "--decode-kernel"),
                             (scfg.prefill_kernel, "prefill_kernel",
                              "--prefill-kernel")):
        if flag and cfg.score_norm != "consmax":
            raise ValueError(
                f"ServeConfig.{name}=True requires score_norm='consmax' "
                f"(got {cfg.score_norm!r} for {cfg.arch_id}): the fused "
                f"serving kernels have no softmax/softermax path. Drop "
                f"{drop} or serve a consmax arch.")
    fused = scfg.fused_sampling
    if fused and cfg.frontend != "tokens":
        raise ValueError(
            f"ServeConfig.fused_sampling=True requires the token frontend "
            f"(got {cfg.frontend!r} for {cfg.arch_id}): the fused steps "
            "emit token ids. Pass fused_sampling=False for the logits-"
            "returning steps.")
    if fused and not _has_attention(cfg):
        raise ValueError(
            f"ServeConfig.fused_sampling=True requires at least one "
            f"attention block (got {cfg.block_pattern} for {cfg.arch_id}): "
            "the per-slot sample positions are derived from the attention "
            "cache index. Pass fused_sampling=False to sample host-side.")
    kv_dtype = CL.kv_cache_dtype(scfg.kv_cache_dtype)

    def init_caches(batch: int):
        return T.init_caches(cfg, batch, scfg.max_seq, kv_dtype=kv_dtype)

    def _epilogue(sampling):
        """Fused logits→token tail: sample the last kept row with per-slot
        keys folded on the POST-step cache index (= prompt + generated so
        far, a pure function of the request's own stream)."""
        def epi(logits, new_caches):
            return S.sample_tokens(logits[:, -1], sampling,
                                   T.cache_index(new_caches))
        return epi

    def prefill_step(params, caches, batch_inputs, sampling=None):
        """Whole-prompt prefill; returns (first sampled tokens | last-
        position logits, caches)."""
        kw = _model_inputs(cfg, batch_inputs)
        s = (kw.get("tokens") if "tokens" in kw else kw["embeds"]).shape[1]
        out, caches, _ = T.lm_apply(
            params, cfg, caches=caches, merged=True,
            positions=jnp.arange(s)[None, :], logits_slice=slice(-1, None),
            logits_epilogue=_epilogue(sampling) if fused else None,
            q_chunk=scfg.q_chunk, kv_chunk=scfg.kv_chunk,
            psum_axes=psum_axes, **kw)
        return (out if fused else out[:, -1]), caches

    def prefill_ragged(params, caches, batch_inputs, lengths, sampling=None):
        """Right-padded ragged batch prefill via the append-at-index path:
        pad K/V never enters the cache, each slot's index lands on its real
        length, and logits are gathered per-request at ``lengths - 1``."""
        kw = _model_inputs(cfg, batch_inputs)
        out, caches, _ = T.lm_apply(
            params, cfg, caches=caches, merged=True,
            prefill_append=lengths, logits_index=lengths - 1,
            prefill_kernel=scfg.prefill_kernel,
            prefill_kv_block=scfg.prefill_kv_block,
            fill_bound=scfg.fill_bound,
            logits_epilogue=_epilogue(sampling) if fused else None,
            q_chunk=scfg.q_chunk, kv_chunk=scfg.kv_chunk,
            psum_axes=psum_axes, **kw)
        return (out if fused else out[:, 0]), caches

    def decode_step(params, caches, batch_inputs, sampling=None):
        """One-token decode. Fused: batch_inputs["tokens"] is the (b,)
        last-token vector; returns the next (b,) tokens, with rows where
        ``active`` is False passed through unchanged (their cache rows and
        index also stay untouched). Legacy: tokens (b,1) | embeds (b,1,d),
        returns (b, vocab) logits. Optional ``page_table`` (b, max_pages)
        int32 for paged caches either way."""
        toks = batch_inputs.get("tokens")
        if fused:
            batch_inputs = dict(batch_inputs, tokens=toks[:, None])
        kw = _model_inputs(cfg, batch_inputs)
        index = T.cache_index(caches)
        positions = index[:, None] if index is not None else None
        out, caches, _, *counts = T.lm_apply(
            params, cfg, caches=caches, merged=True, positions=positions,
            decode_kernel=scfg.decode_kernel,
            decode_kv_block=scfg.decode_kv_block,
            fill_bound=scfg.fill_bound,
            decode_active=batch_inputs.get("active"),
            page_table=batch_inputs.get("page_table"),
            logits_epilogue=_epilogue(sampling) if fused else None,
            psum_axes=psum_axes, moe_counts=fused and _counts_experts(cfg),
            **kw)
        if not fused:
            return out[:, -1], caches
        active = batch_inputs.get("active")
        if active is not None:
            out = jnp.where(active, out, toks)
        return ((out, *counts) if counts else out), caches

    return init_caches, prefill_step, decode_step, prefill_ragged


def _model_inputs(cfg: ModelConfig, batch_inputs: dict) -> dict:
    kw = {}
    if cfg.frontend == "tokens":
        kw["tokens"] = batch_inputs["tokens"]
    else:
        kw["embeds"] = batch_inputs["embeds"]
    if cfg.cross_attn:
        kw["cond"] = batch_inputs["cond"]
    return kw


class ServeSession:
    """Batched autoregressive generation driver.

    Sampling (greedy / temperature / top-k / top-p / min-p, per row) runs
    fused inside the jitted steps when the arch has attention caches and a
    token frontend; recurrent-only or embedding-frontend archs fall back to
    the host-side path through the same ``serve/sampling`` code (documented
    downgrade — the sampled streams are identical)."""

    def __init__(self, cfg: ModelConfig, scfg: ServeConfig, params):
        if scfg.paged_kv:
            raise NotImplementedError(
                "ServeSession is the static contiguous baseline; paged KV "
                "serving lives in ContinuousBatchingEngine")
        self.cfg, self.scfg = cfg, scfg
        self.params = params
        self._fused = (scfg.fused_sampling and cfg.frontend == "tokens"
                       and _has_attention(cfg))
        fns_scfg = scfg if self._fused == scfg.fused_sampling else (
            dataclasses.replace(scfg, fused_sampling=False))
        ic, pf, dc, pr = make_serve_fns(cfg, fns_scfg)
        self._init_caches = ic
        self._prefill = jax.jit(pf)
        self._prefill_ragged = jax.jit(pr)
        self._decode = jax.jit(dc)

    def generate(self, prompts: jnp.ndarray, *, steps: int,
                 sampling=None, temperature: float = 0.0, seed: int = 0,
                 cond=None, lengths=None):
        """prompts: (b, s) int tokens (token frontend). Returns (b, steps).

        sampling: a ``SamplingParams`` (broadcast over rows) or a per-row
        sequence of them; ``None`` builds one from the legacy
        ``temperature``/``seed`` scalars (0 = greedy).
        lengths: optional (b,) real prompt lengths for a right-padded ragged
        batch — prefill masks pad rows and each row decodes from its own
        position, so row r's output equals serving prompt r alone."""
        if steps < 1:
            raise ValueError(
                f"generate: steps must be >= 1, got {steps} — the prefill "
                "step always samples one token, so steps=0 cannot mean "
                "'no tokens'")
        b, s = prompts.shape
        if sampling is None:
            sampling = SamplingParams(temperature=float(temperature),
                                      seed=seed)
        bank = S.bank_of(sampling, b)
        caches = self._init_caches(b)
        inputs = {"tokens": prompts}
        if cond is not None:
            inputs["cond"] = cond
        if self.cfg.frontend != "tokens":
            raise NotImplementedError("embedding-frontend generation")
        if lengths is not None:
            if not _attention_only(self.cfg):
                # prefill_append masks pad rows in attention KV caches only;
                # recurrent (mamba/xlstm) state would scan the pad tokens
                raise NotImplementedError(
                    "ragged generate(lengths=...) requires a pure-attention "
                    f"block pattern (got {self.cfg.block_pattern})")
            lengths = jnp.asarray(lengths, jnp.int32)
        if self._fused:
            return self._generate_fused(caches, inputs, bank, steps, cond,
                                        lengths)
        return self._generate_host(caches, inputs, bank, steps, s, cond,
                                   lengths)

    def _generate_fused(self, caches, inputs, bank, steps, cond, lengths):
        """Device-side sampling: the steps emit (b,) tokens; the loop feeds
        them straight back — only the final (b, steps) stack reaches the
        host."""
        if lengths is None:
            tok, caches = self._prefill(self.params, caches, inputs, bank)
        else:
            tok, caches = self._prefill_ragged(self.params, caches, inputs,
                                               lengths, bank)
        outs = [tok]
        for _ in range(steps - 1):
            step_in = {"tokens": tok}
            if cond is not None:
                step_in["cond"] = cond
            tok, caches = self._decode(self.params, caches, step_in, bank)
            outs.append(tok)
        return jnp.stack(outs, axis=1)

    def _generate_host(self, caches, inputs, bank, steps, s, cond, lengths):
        """Legacy logits path + host-side sampling through the SAME
        serve/sampling schedule: position t of row r folds
        (seed_r, prompt_len_r + t), so the streams match the fused path."""
        b = bank["seed"].shape[0]
        if lengths is None:
            logits, caches = self._prefill(self.params, caches, inputs)
            pos = jnp.full((b,), s, jnp.int32)
        else:
            logits, caches = self._prefill_ragged(self.params, caches,
                                                  inputs, lengths)
            pos = lengths
        outs = []
        tok = S.sample_tokens(logits, bank, pos)
        for _ in range(steps - 1):
            outs.append(tok)
            step_in = {"tokens": tok[:, None]}
            if cond is not None:
                step_in["cond"] = cond
            logits, caches = self._decode(self.params, caches, step_in)
            pos = pos + 1
            tok = S.sample_tokens(logits, bank, pos)
        outs.append(tok)
        return jnp.stack(outs, axis=1)


# ----------------------------------------------- continuous batching ----
class ContinuousBatchingEngine:
    """Slot-recycling serving engine: submit requests, then run().

    Each engine iteration (a) admits queued requests into free slots —
    writing each request's ``SamplingParams`` row into the device-resident
    SoA sampling bank — (b) runs at most one append-at-index prefill chunk
    per PREFILLING slot, bounded by ``ServeConfig.prefill_budget`` tokens
    per iteration, and (c) advances every DECODING slot with one shared
    jitted decode step. The decode step always runs all ``max_slots`` rows
    with an ``active`` mask; inactive rows (free or still prefilling)
    compute garbage that is masked device-side while their cache rows and
    index stay untouched, which keeps the compiled shape static across the
    whole serve lifetime.

    With fused sampling (the default) the decode step consumes the
    ``(max_slots,)`` last-token vector living on device, samples each
    active slot with its own temperature/top-k/top-p/min-p and the key
    ``fold_in(seed_key, cache position)``, and returns the next token
    vector — the host drains only that small int32 array per step for EOS
    checks and recording, never a ``(max_slots, vocab)`` logits block.

    Prefill appends directly at the slot's cache index in fixed-size
    ``prefill_chunk`` token chunks: K/V land at rows [index, index+n), pad
    rows of a ragged final chunk are zeroed before the write, and the index
    advances by the real chunk length. One prefill shape
    ``(1, prefill_chunk)`` is compiled for the engine's entire lifetime —
    admission never recompiles, and no pad-token K/V ever enters a slot.
    The sampling bank is a step *value*, never a shape, so heterogeneous
    sampling traffic cannot recompile either.

    With ``ServeConfig.paged_kv=True`` the per-slot contiguous
    ``(max_slots, max_seq)`` KV rows become ONE shared
    ``(num_pages, page_size)`` page pool per layer: slots map logical rows
    onto pool pages through a host-side page table
    (``serve/scheduler.PagePool`` — free-list allocation on demand,
    reservation-gated admission, release on completion), so serving
    ``max_seq = 500k`` no longer costs ``max_slots x 500k`` cells of HBM.
    ConSmax is what keeps the paged path cheap: page partials need no
    online-softmax combine, and the paged split-KV kernel iterates
    page-table entries straight from a scalar-prefetch operand.

    Paged engines also prefix-cache (``ServeConfig.prefix_cache``): the
    allocator hashes each slot's fully prefilled prompt pages (chained,
    page-aligned token hashes) and a later request with a matching prefix
    admits *warm* — its table rows point at the shared physical pages, its
    fill index starts past them, and the only prefill compute left is the
    uncached suffix (a fully cached prompt re-scores just its final token
    to produce the sampling logits, copy-on-writing the shared last page).
    The kernels are untouched: they always indirected through the table,
    so "many slots, one page" is purely an allocator-side fact. ConSmax
    again is the enabler — a cached page's attention contribution is a
    slot-independent pure-addition partial, so no per-slot softmax
    renormalization state has to be rebuilt for shared pages.

    Restricted to pure-attention token archs: chunked prefill appends into
    attention KV caches; recurrent (mamba/xlstm) state and cross-attention
    cond streams stay on the static ``ServeSession`` path.
    """

    def __init__(self, cfg: ModelConfig, scfg: ServeConfig, params, *,
                 default_sampling: SamplingParams | None = None):
        if cfg.frontend != "tokens":
            raise NotImplementedError("continuous batching: token frontends")
        if cfg.cross_attn or not _attention_only(cfg):
            raise NotImplementedError(
                "continuous batching requires a pure-attention block pattern "
                f"(got {cfg.block_pattern}, cross_attn={cfg.cross_attn})")
        self.cfg, self.scfg = cfg, scfg
        self.params = params
        self.fused = scfg.fused_sampling
        self.default_sampling = default_sampling
        kv_dtype = CL.kv_cache_dtype(scfg.kv_cache_dtype)
        self.paged = scfg.paged_kv
        # device-mesh plan: None when tp = seq_shards = 1 (single device —
        # the engine's original code paths, bit for bit); otherwise every
        # jitted step below is shard_map-wrapped over the plan's mesh and
        # each attention block ends in ONE output-sized psum combining the
        # per-shard ConSmax partials (see distributed/serve_mesh)
        self.plan = plan = SM.plan_mesh(cfg, scfg)
        mcfg = cfg if plan is None else plan.cfg_local
        psum = plan.psum_axes if plan is not None else ()
        if self.paged:
            # shared page pool: num_pages x page_size KV rows serve every
            # slot; the host-side PagePool maps (slot, logical page) ->
            # pool page and gates admission on worst-case reservations
            # (per-shard reservations under sequence sharding)
            self.pool = PagePool(scfg.num_pages, scfg.page_size,
                                 scfg.max_slots, scfg.max_pages_per_slot,
                                 prefix_cache=scfg.prefix_cache,
                                 evict=scfg.prefix_evict,
                                 seq_shards=scfg.seq_shards)
            self.scheduler = Scheduler(scfg.max_slots, scfg.max_seq,
                                       page_pool=self.pool)
            self.caches = T.init_paged_caches(
                cfg, scfg.max_slots, scfg.num_pages, scfg.page_size,
                kv_dtype=kv_dtype)
        else:
            self.pool = None
            self.scheduler = Scheduler(scfg.max_slots, scfg.max_seq)
            self.caches = T.init_caches(cfg, scfg.max_slots, scfg.max_seq,
                                        kv_dtype=kv_dtype)
        self.results: dict[int, list[int]] = {}
        self.prefilled_tokens = 0          # chunk tokens actually computed —
                                           # warm admissions skip cached rows
        # host telemetry, read by the benchmark: counts over the engine's
        # life, and per-request perf_counter stamps (``submitted``,
        # ``admitted``, ``first_token``, ``finished``) taken where each
        # event happens; the spans of ``step`` carry the same boundaries
        # on the profiler's clock. With dropless expert layers the decode
        # steps' routed rows and experts hit, summed over layers, count
        # too (``moe_rows``, ``moe_experts_hit``)
        self._moe = self.fused and _counts_experts(cfg)
        self.counters = dict.fromkeys(
            ("steps", "decode_steps", "decode_active_slots",
             "decode_kv_pages", "decode_kv_grid_pages", "prefill_chunks",
             "cow_copies", "table_uploads", "host_syncs")
            + (("moe_rows", "moe_experts_hit") if self._moe else ()), 0)
        self.request_times: dict[int, dict[str, float]] = {}
        self._submits = 0                  # drives default-policy seed + k
        self._chunk = scfg.prefill_chunk
        self._budget = scfg.prefill_budget or self._chunk
        self._table_dev = None             # device page table, re-uploaded
        self._table_version = -1           # only when the pool mutates
        # device-resident sampling state, living next to the caches: the
        # SoA per-slot parameter bank (admission writes one row) and the
        # last-token vector the fused decode loop feeds back to itself
        self.bank = S.bank_init(scfg.max_slots)
        self._last = jnp.zeros((scfg.max_slots,), jnp.int32)

        paged, fused = self.paged, self.fused

        def prefill_chunk_step(params, caches, slot, tokens, lengths,
                               sampling, page_row):
            """One append chunk for one slot. tokens: (1, chunk) with rows
            >= lengths[0] as pad; slot, lengths, and the sampling bank are
            traced, so this compiles exactly once. Contiguous caches slice
            the whole slot out of the pool and write it back; paged caches
            slot-address only the per-slot ``index`` leaves (the K/V pools
            are shared — the append lands on them via ``page_row``,
            (1, max_pages)). Fused: returns the (1,) token sampled from the
            row at lengths-1 (only meaningful for a prompt's final chunk,
            with the slot's own bank row sliced inside the step); legacy:
            returns that row's logits."""
            if plan is not None and paged:
                # per-shard view of the global table row: owned entries
                # become local pool indices, foreign pages become the -1
                # holes the fill-bounded kernels skip (identity at ns=1)
                page_row = CL.localize_page_table(
                    page_row, jax.lax.axis_index(SM.SEQ_AXIS),
                    plan.pages_per_shard)
            def take(path, a):
                if paged and not T._is_index(path):
                    return a                  # shared pool: consumed whole
                return jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1)
            slot_caches = jax.tree_util.tree_map_with_path(take, caches)
            epi = None
            if fused:
                row = jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1,
                                                           axis=0), sampling)
                def epi(logits, new_caches):
                    return S.sample_tokens(logits[:, -1], row,
                                           T.cache_index(new_caches))
            out, slot_caches, _ = T.lm_apply(
                params, mcfg, tokens=tokens, caches=slot_caches, merged=True,
                prefill_append=lengths, logits_index=lengths[0] - 1,
                prefill_kernel=scfg.prefill_kernel,
                prefill_kv_block=scfg.prefill_kv_block,
                fill_bound=scfg.fill_bound,
                q_chunk=scfg.q_chunk, kv_chunk=scfg.kv_chunk,
                page_table=page_row, logits_epilogue=epi, psum_axes=psum)
            def put(path, big, one):
                if paged and not T._is_index(path):
                    return one                # shared pool: scatter updated
                return jax.lax.dynamic_update_slice_in_dim(
                    big, one.astype(big.dtype), slot, axis=1)
            caches = jax.tree_util.tree_map_with_path(put, caches,
                                                      slot_caches)
            return (out if fused else out[:, 0]), caches

        _, _, decode_fn, _ = make_serve_fns(mcfg, scfg, psum_axes=psum)

        def decode_step(params, caches, batch_inputs, sampling=None):
            if plan is not None and paged:
                batch_inputs = dict(
                    batch_inputs,
                    page_table=CL.localize_page_table(
                        batch_inputs["page_table"],
                        jax.lax.axis_index(SM.SEQ_AXIS),
                        plan.pages_per_shard))
            return decode_fn(params, caches, batch_inputs, sampling)

        reset_fn = T.reset_slot_paged if self.paged else T.reset_slot
        if plan is None:
            copy_fn = T.copy_kv_page
        else:
            def copy_fn(caches, src, dst):
                return T.copy_kv_page_local(
                    caches, src, dst, jax.lax.axis_index(SM.SEQ_AXIS),
                    plan.pages_per_shard)

        # the engine rebinds self.caches to each result immediately, so the
        # cache pool buffer is donated — prefill/decode/reset update the
        # n_layers x max_slots x max_seq K/V rows (or the shared page pool)
        # in place instead of copying per call (donation is a no-op on CPU
        # smoke runs)
        if plan is None:
            prefill_w, decode_w, reset_w = (prefill_chunk_step, decode_step,
                                            reset_fn)
            index_w, copy_w = T.set_slot_index, copy_fn
        else:
            # shard_map every step body: params over the head rules, cache
            # leaves over hkv ("model") / pages ("seq"), everything else —
            # tokens, slots, tables, sampling banks — replicated (P()
            # prefixes broadcast over dict/None subtrees). The mesh, specs
            # and wrapping are fixed HERE, once, so the one-compiled-shape-
            # per-lifetime invariant is untouched.
            pspec = plan.param_specs(params)
            cspec = plan.cache_specs(
                self.caches, paged=self.paged,
                quantized=CL.kv_quantized(kv_dtype))
            self._cache_spec = cspec
            P0 = SM.P()
            prefill_w = plan.wrap(
                prefill_chunk_step,
                (pspec, cspec, P0, P0, P0, P0, P0), (P0, cspec))
            decode_w = plan.wrap(decode_step,
                                 (pspec, cspec, P0, P0), (P0, cspec))
            reset_w = plan.wrap(reset_fn, (cspec, P0), cspec)
            index_w = plan.wrap(T.set_slot_index, (cspec, P0, P0), cspec)
            copy_w = plan.wrap(copy_fn, (cspec, P0, P0), cspec)
            # donation needs inputs already laid out like the outputs:
            # place params/caches and the device-resident sampling state
            # on the mesh before the first step runs
            self.params = plan.put(params, jax.tree.map(plan.named, pspec))
            self.caches = plan.put(self.caches,
                                   jax.tree.map(plan.named, cspec))
            self.bank = plan.put(self.bank, plan.replicated)
            self._last = plan.put(self._last, plan.replicated)
        self._prefill = jax.jit(prefill_w, donate_argnums=(1,))
        self._decode = jax.jit(decode_w, donate_argnums=(1,))
        self._reset = jax.jit(reset_w, donate_argnums=(0,))
        if self.paged:
            # warm-admission index pin + COW page copy: the device half of
            # the allocator's prefix-sharing bookkeeping, one compiled
            # variant each for the engine's lifetime
            self._set_index = jax.jit(index_w, donate_argnums=(0,))
            self._copy_page = jax.jit(copy_w, donate_argnums=(0,))
        else:
            self._set_index = self._copy_page = None

    # --------------------------------------------------------- frontend ----
    def submit(self, prompt, max_new_tokens: int, eos_id: int | None = None,
               sampling: SamplingParams | None = None,
               n: int = 1) -> int | list[int]:
        """Queue a request; returns its uid (key into results after run).

        ``sampling`` defaults to the engine's ``default_sampling``; that
        default is a *policy*, not a shared stream — request k (in submit
        order) derives ``seed + k``, so two default-policy requests with
        the same prompt still sample independently. Pass an explicit
        ``sampling`` to pin a stream exactly (identical explicit seeds
        deliberately reproduce each other). Greedy when both are None.

        ``n > 1`` submits n parallel samples of the same prompt (returns a
        list of uids): stream i derives ``seed + i`` from an explicit
        ``sampling`` (the default policy already varies per submit). On a
        paged engine with the prefix cache enabled the streams share the
        prompt's physical KV pages — the first to prefill registers them,
        every later one admits warm with only the 1-token tail re-score,
        copy-on-write keeping their generated rows private."""
        if n < 1:
            raise ValueError(f"submit: n must be >= 1, got {n}")
        if n == 1:
            return self._submit_one(prompt, max_new_tokens, eos_id, sampling)
        uids = []
        for i in range(n):
            sp = sampling
            if sp is not None and i:
                sp = dataclasses.replace(sp, seed=(sp.seed + i) % 2**32)
            uids.append(self._submit_one(prompt, max_new_tokens, eos_id, sp))
        return uids

    def _submit_one(self, prompt, max_new_tokens, eos_id, sampling) -> int:
        sp = sampling
        if sp is None and self.default_sampling is not None:
            sp = dataclasses.replace(
                self.default_sampling,
                seed=(self.default_sampling.seed + self._submits) % 2**32)
        self._submits += 1
        uid = self.scheduler.submit(prompt, max_new_tokens, eos_id,
                                    sampling=sp)
        self.request_times[uid] = {"submitted": time.perf_counter()}
        return uid

    def run(self, max_steps: int | None = None) -> dict[int, list[int]]:
        """Drive admissions + decode until the queue and slots drain.
        ``max_steps`` bounds this call, not the engine lifetime — and it
        counts *iterations*, including zero-progress ones (nothing to
        admit, prefill, or decode), so a request the pool can never admit
        cannot spin this loop forever."""
        iters = 0
        while self.scheduler.has_work():
            if max_steps is not None and iters >= max_steps:
                break
            self.step()
            iters += 1
        return self.results

    def step(self):
        """One engine iteration: admit (writing sampling-bank rows),
        prefill up to the token budget, then one shared decode step for
        the DECODING slots. Requests that complete are finished last, each
        phase in a span of its own (``engine.*``) under ``engine.step``."""
        c = self.counters
        with jax.profiler.StepTraceAnnotation("engine.step",
                                              step_num=c["steps"]):
            with Span("engine.admit"):
                self._admit()
                plan = self.scheduler.prefill_plan(self._chunk, self._budget)
            done = []
            for slot, start, n in plan:
                uid = self.scheduler.slots[slot].request.uid
                with Span("engine.prefill", uid=uid, slot=slot):
                    if self._prefill_one(slot, start, n):
                        done.append(slot)
            # a request done at its first token leaves before the decode
            # step, which would otherwise serve it one more
            for slot in done:
                self._finish(slot)
            decoding = self.scheduler.decoding()
            if decoding:
                with Span("engine.decode"):
                    sampled = self._decode_once(decoding)
                with Span("engine.record"):
                    done = [slot for slot, _ in decoding
                            if self.scheduler.record(slot, int(sampled[slot]))]
                for slot in done:
                    self._finish(slot)
            elif not plan:
                return  # nothing queued, nothing active
        c["steps"] += 1

    def _admit(self):
        while True:
            admitted = self.scheduler.admit()
            if admitted is None:
                return
            slot, req = admitted
            self.request_times[req.uid]["admitted"] = time.perf_counter()
            self.bank = S.bank_put(self.bank, slot, req.sampling)
            state = self.scheduler.slots[slot]
            if self.paged and state.filled:
                # warm admission: the slot's table rows already point at
                # cached pages holding rows [0, filled) — pin the device
                # fill index past them so the first prefill chunk appends
                # at the first uncached row
                self.caches = self._set_index(
                    self.caches, jnp.asarray(slot, jnp.int32),
                    jnp.asarray(state.filled, jnp.int32))

    def compile_step(self, name: str):
        """Compile the ``"prefill"`` or ``"decode"`` step ahead of any
        traffic, at the engine's own argument shapes and placements, and
        return it (``jax.stages.Compiled``: ``as_text()`` shows the
        optimized HLO). The step's first call reuses this executable
        instead of compiling again."""
        bank = self.bank if self.fused else None
        if name == "prefill":
            page_row = self._device_table()[:1] if self.paged else None
            return self._prefill.lower(
                self.params, self.caches, jnp.asarray(0, jnp.int32),
                jnp.zeros((1, self._chunk), jnp.int32),
                jnp.ones((1,), jnp.int32), bank, page_row).compile()
        if name == "decode":
            tokens = (self._last if self.fused else
                      jnp.zeros((self.scfg.max_slots, 1), jnp.int32))
            inactive = np.zeros((self.scfg.max_slots,), bool)
            return self._decode.lower(
                self.params, self.caches,
                self._decode_inputs(tokens, inactive), bank).compile()
        raise ValueError(f"compile_step: unknown step {name!r}")

    @property
    def prefill_cache_size(self) -> int:
        """Compiled prefill variants so far (1 for the whole lifetime —
        the append-at-index design's no-recompile guarantee)."""
        return self._prefill._cache_size()

    @property
    def decode_cache_size(self) -> int:
        """Compiled decode variants so far (1 for the whole lifetime: the
        page table and the sampling bank are values, never shapes)."""
        return self._decode._cache_size()

    @property
    def page_occupancy(self) -> float:
        """Fraction of pool pages currently mapped (paged engines only)."""
        return self.pool.occupancy() if self.pool is not None else 0.0

    @property
    def page_reserved(self) -> float:
        """Fraction of pool pages committed by live reservations — includes
        reserved-but-unmapped pages that ``page_occupancy`` cannot see, so
        ``page_reserved - page_occupancy`` is the invisible admission
        pressure stalling the queue (paged engines only)."""
        return (self.pool.reserved_fraction() if self.pool is not None
                else 0.0)

    # ---------------------------------------------------------- internals ----
    def _device_table(self):
        """Device copy of the pool's page table, re-uploaded only when the
        allocator actually mapped or released pages — decode steps between
        mutations (the common case: one token, no new page) reuse the
        resident buffer instead of paying a host transfer per token."""
        if self._table_version != self.pool.version:
            with Span("engine.table_upload"):
                table = jnp.asarray(self.pool.table)
                if self.plan is not None:
                    # GLOBAL table, replicated: each shard localizes it
                    # in-step
                    table = self.plan.put(table, self.plan.replicated)
            self._table_dev = table
            self._table_version = self.pool.version
            self.counters["table_uploads"] += 1
        return self._table_dev

    def _cow_copy(self, copies):
        """Run the allocator's copy-on-write page copies (src, dst)."""
        if not copies:
            return
        with Span("engine.cow_copy"):
            for src, dst in copies:
                self.caches = self._copy_page(
                    self.caches, jnp.asarray(src, jnp.int32),
                    jnp.asarray(dst, jnp.int32))
        self.counters["cow_copies"] += len(copies)

    def _decode_inputs(self, tokens, active) -> dict:
        inputs = {"tokens": tokens, "active": jnp.asarray(active)}
        if self.paged:
            inputs["page_table"] = self._device_table()
        return inputs

    def _prefill_one(self, slot: int, start: int, n: int) -> bool:
        """One prompt chunk; True when the request is done (its first token
        was its last) and must be finished."""
        request = self.scheduler.slots[slot].request
        prompt = request.prompt
        chunk = prompt[start:start + n] + [0] * (self._chunk - n)
        page_row = None
        if self.paged:
            # back rows [0, start + n) and privatize any page in the write
            # window still shared with another slot — the 1-token tail
            # re-score of a fully cached prompt lands in the shared last
            # page, so its COW copy must run before this chunk's K/V write
            _, copies = self.pool.ensure_writable(slot, start, start + n)
            self._cow_copy(copies)
            page_row = self._device_table()[slot:slot + 1]
        self.prefilled_tokens += n
        self.counters["prefill_chunks"] += 1
        out, self.caches = self._prefill(
            self.params, self.caches, jnp.asarray(slot, jnp.int32),
            jnp.asarray(chunk, jnp.int32)[None, :],
            jnp.asarray([n], jnp.int32), self.bank if self.fused else None,
            page_row)
        done = self.scheduler.record_prefill(slot, n)
        if self.paged:
            # register the newly completed prompt pages so later identical
            # prefixes admit warm
            state = self.scheduler.slots[slot]
            self.pool.commit_prefix(slot, prompt, state.filled)
        if not done:
            return False
        # prompt complete: the chunk's output is the first token of the
        # request (sampled in-step when fused; from logits otherwise)
        with Span("engine.first_token"):
            if self.fused:
                tok = int(out[0])
            else:
                state = self.scheduler.slots[slot]
                tok = int(S.sample_tokens(
                    out, S.bank_take(self.bank, slice(slot, slot + 1)),
                    jnp.asarray([state.filled], jnp.int32))[0])
        self.counters["host_syncs"] += 1
        self.request_times[request.uid]["first_token"] = time.perf_counter()
        if self.fused:
            self._last = self._last.at[slot].set(tok)
        return self.scheduler.record(slot, tok)

    def _decode_once(self, decoding) -> np.ndarray:
        """One shared decode step over the DECODING slots; the
        (max_slots,) tokens it sampled, on the host."""
        c = self.counters
        c["decode_steps"] += 1
        c["decode_active_slots"] += len(decoding)
        c["host_syncs"] += 1
        active = np.zeros((self.scfg.max_slots,), bool)
        live = []                          # each slot's pages by its length
        for slot, state in decoding:
            active[slot] = True
            if self.paged:
                # this step writes the last sampled token's K/V at row
                # filled + generated - 1; make sure that row has a page the
                # slot owns exclusively (prefill already privatized the
                # prefix tail, so this window never actually copies — but
                # the COW invariant is enforced here, not assumed)
                rows = state.filled + len(state.generated)
                _, copies = self.pool.ensure_writable(slot, rows - 1, rows)
                self._cow_copy(copies)
                live.append(-(-rows // self.scfg.page_size))
        if live:
            # the pages the decode kernel fetches, against its grid of
            # max_slots rows of the batch-max live page count
            c["decode_kv_pages"] += sum(live)
            c["decode_kv_grid_pages"] += self.scfg.max_slots * max(live)
        if self.fused:
            # device-side feedback: last tokens in, next tokens out — the
            # only host traffic is draining the (max_slots,) token vector
            out, self.caches = self._decode(
                self.params, self.caches,
                self._decode_inputs(self._last, active), self.bank)
            if not self._moe:
                self._last = out
                with Span("engine.decode_drain"):
                    sampled = np.asarray(self._last)
            else:
                # the expert counts come back in the same drain as the
                # tokens
                self._last = out[0]
                with Span("engine.decode_drain"):
                    sampled, counts = jax.device_get(out)
                c["moe_rows"] += int(counts[0])
                c["moe_experts_hit"] += int(counts[1])
        else:
            # legacy A/B baseline: ship (max_slots, vocab) logits to the
            # host and sample there — through the SAME per-slot schedule
            toks = np.zeros((self.scfg.max_slots, 1), np.int32)
            for slot, state in decoding:
                toks[slot, 0] = state.last_token
            logits, self.caches = self._decode(
                self.params, self.caches,
                self._decode_inputs(jnp.asarray(toks), active), None)
            rows = np.asarray([slot for slot, _ in decoding])
            pos = jnp.asarray([st.filled + len(st.generated)
                               for _, st in decoding], jnp.int32)
            drawn = S.sample_tokens(logits[rows], S.bank_take(self.bank,
                                                              rows), pos)
            sampled = np.zeros((self.scfg.max_slots,), np.int32)
            with Span("engine.decode_drain"):
                sampled[rows] = np.asarray(drawn)
        return sampled

    def _finish(self, slot: int):
        uid = self.scheduler.slots[slot].request.uid
        with Span("engine.finish", uid=uid, slot=slot):
            _, generated = self.scheduler.finish(slot)
            self.results[uid] = generated
            self.caches = self._reset(self.caches,
                                      jnp.asarray(slot, jnp.int32))
        self.request_times[uid]["finished"] = time.perf_counter()


# --------------------------------------------------- dry-run entry point ----
def make_decode_for_dryrun(cfg: ModelConfig, seq_len: int):
    """serve_step(params, caches, tokens) with the cache index pinned at
    seq_len-1 — the decode_32k / long_500k cell semantics. The dryrun cells
    keep the logits-returning steps (fused_sampling=False): they measure and
    shard the (batch, vocab) logits surface itself."""
    scfg = ServeConfig(max_seq=seq_len, fused_sampling=False)
    _, _, decode_step, _ = make_serve_fns(cfg, scfg)

    def serve_step(params, caches, batch_inputs):
        return decode_step(params, caches, batch_inputs)

    return serve_step, scfg
