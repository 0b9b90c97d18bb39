"""Fused ConSmax append-at-index prefill Pallas kernel (TPU target).

The serving prefill hot path: a fixed-size ``(b, c)`` token chunk whose K/V
were just written at per-slot cache position ``index`` attends to
``cache[0:index]`` plus its own causal self-block. This is the Pallas-tiled
version of ``core.attention.append_attention``'s jnp KV walk, and the chunk
analogue of the split-KV decode kernel next door (../consmax_decode): with
no running max and no denominator sum, every KV shard's ``p @ v`` partial is
*independent*, so the KV axis of the grid is marked ``parallel`` like every
other dimension. Each program writes its shard's partial into its own
output slot and the shards combine by one plain fp32 addition outside the
kernel — no online-softmax rescale state between KV blocks, no (m, l)
exchange, no final divide. That a multi-row prefill chunk needs *nothing*
beyond what single-token decode needs is the paper's sync-free property
doing the work.

The cache is consumed in its stored layout ``(b, L, hkv, dk)``: one grid
program covers a KV shard of **all** hkv heads (K/V blocks
``(1, bk, hkv, dk)``, per-head beta/gamma in SMEM, a static head loop in
the body — shared design with the decode kernel, helpers in
../cache_layout.py), so every block's last two dims are full extents, as
Mosaic's TPU tiling rule requires, and a prefill chunk never traces a
transposed or padded copy of the cache. GQA is folded into the q rows
position-major (row = chunk position * g + group head), giving a
``(bq*g, bk)`` score tile for the MXU without repeating K/V.

Per (batch, q-block, kv-shard) program, for each kv-head:

    s = q @ k^T * scale            (MXU; q is a bq*g row block)
    p = exp(s - beta) / gamma      (VPU; causal/length/window mask)
    o = p @ v                      (MXU; partial, summed across shards)

VMEM per program: the (hkv, bq*g, dk) q and fp32 out blocks, (bk, hkv, dk)
K/V blocks and one head's (bq*g, bk) fp32 s/p tiles at a time —
``analysis.kernel_contracts`` models it against the per-core budget. The
parallel split costs ``ns`` output-sized fp32 partial buffers in HBM; pick
``bk`` (ServeConfig.prefill_kv_block) so ns = L/bk stays small on long
caches.

The paged variant walks *page-table entries* via a scalar-prefetch
operand: program (ib, iq, ij) DMAs pool page ``page_table[ib, ij]`` (all
heads) straight from HBM through its BlockSpec index map. Its page axis
accumulates into VMEM scratch ('arbitrary' trailing dim) instead of
per-page partial buffers: a chunk's partials are (c*g, d)-sized, so per-page slots would
cost max_pages_per_slot × chunk-output HBM — at 500k context that is
thousands of copies, defeating the page pool's memory saving. The
accumulation is still a bare ``acc += p @ v``: ConSmax removes the (m, l)
rescale that softmax would thread between pages, which is what keeps the
fused page walk this simple.

Fill bounding (``fill_bound=True``, the default): serving caches are sized
at capacity but a prefill chunk only ever reads rows below the batch-max
``index + lengths``, so the KV-shard / page grid axis is clamped to the
traced live shard count (``cache_layout.live_blocks`` — fill stays a
*value*, the compiled shape never changes) and each surviving program
additionally ``pl.when``-skips its compute when its shard lies beyond the
slot's own fill or the chunk's causal/window reach
(``cache_layout.shard_live``). A skipped contiguous shard writes exact
zeros to its partial slot; a skipped page simply doesn't accumulate. Both
are pure zero-writes because ConSmax partials combine by addition — a
skipped shard owes no rescale and no denominator term — so the bounded and
capacity-swept paths are bit-identical. ``fill_bound=False`` keeps the
capacity-swept grid (the pre-bounding behavior) for A/B benchmarking.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import cache_layout as CL

# ceiling on the contiguous kernel's parallel KV split: each shard owns a
# chunk-output-sized fp32 partial buffer, so ns must stay O(10), not O(L/bk)
MAX_KV_SHARDS = 64


def _head_partials(q_ref, k_ref, v_ref, ks_ref, vs_ref, beta_ref,
                   gamma_ref, mask, *, g: int, **partial_kw):
    """Yield ``(h, partial)`` for every KV head of an all-heads block: the
    chunk's (bqg, dk) q rows of head ``h`` against the block's rows."""
    bqg = q_ref.shape[2]
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h]                              # (bqg, d)
        yield h, CL.consmax_partial(
            q, CL.kv_head(k_ref, ks_ref, 0, h, q.dtype),
            CL.kv_head(v_ref, vs_ref, 0, h, q.dtype), mask,
            CL.head_rows(beta_ref, h, g, bqg),
            CL.head_rows(gamma_ref, h, g, bqg), **partial_kw)


def _chunk_mask(idx, kvl, iq, start, rows, *, bqg: int, g: int,
                window: int):
    """(bqg, rows) mask of q-block ``iq`` (chunk starts at ``idx``, rows
    position-major) against cache rows ``[start, start + rows)``."""
    row = iq * bqg + jax.lax.broadcasted_iota(jnp.int32, (bqg, rows), 0)
    kpos = start + jax.lax.broadcasted_iota(jnp.int32, (bqg, rows), 1)
    return CL.kv_mask(idx + row // g, kpos, kvl, window)


def _kernel(idx_ref, kvl_ref, beta_ref, gamma_ref, q_ref, k_ref, v_ref,
            *rest, window: int, bqg: int, bk: int, bq: int, g: int,
            bounded: bool, **partial_kw):
    *scale_refs, o_ref = rest                        # quantized KV: (ks, vs)
    ks_ref, vs_ref = scale_refs or (None, None)
    ib, iq, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    idx = idx_ref[ib]                                # chunk start position
    kvl = kvl_ref[ib]                                # index + real length

    def compute():
        mask = _chunk_mask(idx, kvl, iq, ik * bk, bk, bqg=bqg, g=g,
                           window=window)
        for h, part in _head_partials(q_ref, k_ref, v_ref, ks_ref, vs_ref,
                                      beta_ref, gamma_ref, mask, g=g,
                                      **partial_kw):
            o_ref[0, h, 0] = part                    # independent partial

    if not bounded:
        compute()
        return
    live = CL.shard_live(ik * bk, bk, kvl,           # this slot's fill and
                         qpos_hi=idx + iq * bq + bq - 1,  # the q-block's
                         qpos_lo=idx + iq * bq,      # causal/window reach
                         window=window)
    pl.when(live)(compute)

    @pl.when(jnp.logical_not(live))
    def _dead():                                     # exact-zero partial
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)


def consmax_prefill(q, k, v, index, lengths, beta, gamma, *, window: int = 0,
                    softcap: float = 0.0, merged: bool = True,
                    scale: float | None = None, bq: int = 128, bk: int = 512,
                    fill_bound: bool = True, interpret: bool = False,
                    k_scale=None, v_scale=None):
    """q: (b, c, H, dk) chunk queries at per-slot positions index + [0, c);
    k, v: (b, L, hkv, dk) caches *after* the chunk's K/V were written at
    ``index`` (consumed as stored — no transpose); index, lengths: (b,)
    int32 chunk start positions / real (non-pad) chunk lengths; beta/gamma:
    (H,) fp32. Returns (b, c, H, dk) in q.dtype.
    ``k_scale``/``v_scale``: (b, L, hkv) fp32 per-row-per-head quant scales
    for a quantized cache, upcast per-block in VMEM (None = stored as-is).

    Grid (b, nq, ns) — ALL dims parallel, every program covering all hkv
    heads; shard partials are summed in fp32 by the caller-side reduction
    (pure addition, the sync-free combine). Query rows >= lengths are pad
    rows: their output is garbage and must be ignored by the caller (their
    K/V never entered the cache), exactly as in ``append_attention``. Block
    sizes prefer the largest divisors of c / L <= ``bq`` / ``bk`` so
    operands are not padded
    (``cache_layout.block_cache_rows`` handles degenerate-divisor L); the
    shard count is additionally capped at ``MAX_KV_SHARDS`` by growing the
    shard — the parallel split buys its independence with ``ns``
    chunk-output-sized fp32 partial buffers, and an uncapped ns at 500k
    context would cost ~1000x the chunk output in HBM.

    ``fill_bound=True`` clamps the shard axis to the traced batch-max live
    shard count and skips per-program work beyond each slot's own fill or
    the q-block's causal/window reach (see module docstring) — bit-identical
    to the capacity sweep, fill stays a value.
    """
    b, c, H, dk = q.shape
    L, hkv = k.shape[1], k.shape[2]
    g = H // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(dk)
    bq = CL.divisor_block(c, bq)
    bqg = bq * g
    nq = c // bq
    k, v, bk, ns = CL.block_cache_rows(
        k, v, max(bk, -(-L // MAX_KV_SHARDS)))
    quant = k_scale is not None
    if quant:
        k_scale = CL.block_scale_rows(k_scale, bk, ns)
        v_scale = CL.block_scale_rows(v_scale, bk, ns)

    idx1 = index.astype(jnp.int32)
    kvl1 = (index + lengths).astype(jnp.int32)

    ns_live = CL.live_blocks(jnp.max(kvl1), bk, ns) if fill_bound else ns

    kernel = functools.partial(_kernel, scale=scale, window=window,
                               softcap=softcap, bqg=bqg, bk=bk, bq=bq, g=g,
                               merged=merged, bounded=fill_bound)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kv_spec = pl.BlockSpec((1, bk, hkv, dk),
                           lambda ib, iq, ik: (ib, ik, 0, 0))
    in_specs = [smem, smem, smem, smem,          # index, kv_len, beta, gamma
                pl.BlockSpec((1, hkv, bqg, dk),
                             lambda ib, iq, ik: (ib, 0, iq, 0)),  # q rows
                kv_spec, kv_spec]
    operands = [idx1, kvl1, beta.astype(jnp.float32),
                gamma.astype(jnp.float32), CL.fold_gqa(q, hkv), k, v]
    if quant:
        in_specs += [pl.BlockSpec((1, bk, hkv),
                                  lambda ib, iq, ik: (ib, ik, 0))] * 2
        operands += [k_scale, v_scale]

    partials = pl.pallas_call(
        kernel,
        grid=(b, nq, ns_live),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, 1, bqg, dk),
                               lambda ib, iq, ik: (ib, 0, ik, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, ns, c * g, dk), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
    )(*operands)

    out = CL.fill_bounded_sum(partials, ns_live)     # the sync-free combine
    return CL.unfold_gqa(out, b, c, H).astype(q.dtype)


# ------------------------------------------------------------- paged KV ----
def _paged_kernel(tab_ref, idx_ref, kvl_ref, beta_ref, gamma_ref, q_ref,
                  k_ref, v_ref, *rest, window: int, bqg: int, ps: int,
                  bq: int, g: int, bounded: bool, **partial_kw):
    *scale_refs, o_ref, acc_ref = rest               # quantized KV: (ks, vs)
    ks_ref, vs_ref = scale_refs or (None, None)
    ib, iq, ij = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    idx = idx_ref[ib]
    kvl = kvl_ref[ib]

    @pl.when(ij == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate():
        mask = _chunk_mask(idx, kvl, iq, ij * ps, ps, bqg=bqg, g=g,
                           window=window)            # unmapped page => all
                                                     # kpos >= kvl => zeroed
        for h, part in _head_partials(q_ref, k_ref, v_ref, ks_ref, vs_ref,
                                      beta_ref, gamma_ref, mask, g=g,
                                      **partial_kw):
            acc_ref[h] += part                       # bare add — no rescale

    if bounded:
        live = (tab_ref[ib, ij] >= 0) & CL.shard_live(
            ij * ps, ps, kvl, qpos_hi=idx + iq * bq + bq - 1,
            qpos_lo=idx + iq * bq, window=window)
        pl.when(live)(accumulate)                    # dead page: no add —
    else:                                            # init/flush still run
        accumulate()

    @pl.when(ij == nj - 1)
    def _flush():
        o_ref[0] = acc_ref[...]


def consmax_prefill_paged(q, kp, vp, page_table, index, lengths, beta,
                          gamma, *, window: int = 0, softcap: float = 0.0,
                          merged: bool = True, scale: float | None = None,
                          bq: int = 128, fill_bound: bool = True,
                          interpret: bool = False, k_scale=None,
                          v_scale=None):
    """Paged fused prefill. q: (b, c, H, dk) chunk queries; kp, vp: shared
    page pools (P, ps, hkv, dk) *after* the chunk's K/V were scattered in;
    page_table: (b, max_pages) int32 (-1 = unmapped); index, lengths: (b,)
    chunk start positions / real chunk lengths. Returns (b, c, H, dk).
    ``k_scale``/``v_scale``: (P, ps, hkv) fp32 quant-scale pools beside the
    page table for a quantized KV pool, gathered through the same page
    index map and upcast per-page in VMEM.

    The page axis is the grid's trailing 'arbitrary' dimension accumulating
    into VMEM scratch — a pure ``acc += p @ v`` per page, no (m, l) state —
    because per-page partial buffers would cost max_pages × chunk-output
    HBM (see module docstring). The page table and the per-slot scalars
    ride in as scalar-prefetch operands, so the gather lives in the
    BlockSpec index map: unmapped entries clamp to page 0 and every row
    they could contribute is masked via ``kv_len``.

    ``fill_bound=True`` clamps the page axis to the traced batch-max live
    page count and skips the accumulate of any unmapped page
    (``page_table[ib, ij] < 0``) or page beyond the slot's fill /
    causal/window reach — the per-q-block init and final flush still run,
    so a fully-dead walk flushes exact zeros. Bit-identical to the
    capacity sweep.
    """
    b, c, H, dk = q.shape
    P, ps, hkv = kp.shape[0], kp.shape[1], kp.shape[2]
    g = H // hkv
    npg = page_table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(dk)
    bq = CL.divisor_block(c, bq)
    bqg = bq * g
    nq = c // bq

    tab = page_table.astype(jnp.int32)
    idx1 = index.astype(jnp.int32)
    kvl1 = (index + lengths).astype(jnp.int32)

    npg_live = CL.live_blocks(jnp.max(kvl1), ps, npg) if fill_bound else npg

    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               softcap=softcap, bqg=bqg, ps=ps, bq=bq, g=g,
                               merged=merged, bounded=fill_bound)

    def page_map(ib, iq, ij, tab_ref, idx_ref, kvl_ref):
        return (jnp.maximum(tab_ref[ib, ij], 0), 0, 0, 0)

    def scale_page_map(ib, iq, ij, tab_ref, idx_ref, kvl_ref):
        return (jnp.maximum(tab_ref[ib, ij], 0), 0, 0)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [
        smem, smem,                                             # beta, gamma
        pl.BlockSpec((1, hkv, bqg, dk),
                     lambda ib, iq, ij, *_: (ib, 0, iq, 0)),    # q
        pl.BlockSpec((1, ps, hkv, dk), page_map),               # k page
        pl.BlockSpec((1, ps, hkv, dk), page_map),               # v page
    ]
    operands = [beta.astype(jnp.float32), gamma.astype(jnp.float32),
                CL.fold_gqa(q, hkv), kp, vp]
    if k_scale is not None:
        in_specs += [pl.BlockSpec((1, ps, hkv), scale_page_map)] * 2
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                       # table, index, kv_len
        grid=(b, nq, npg_live),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, bqg, dk),
                               lambda ib, iq, ij, *_: (ib, 0, iq, 0)),
        scratch_shapes=[pltpu.VMEM((hkv, bqg, dk), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, c * g, dk), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(tab, idx1, kvl1, *operands)

    return CL.unfold_gqa(out, b, c, H).astype(q.dtype)
