"""Attention with pluggable score normalizer (softmax / softermax / consmax).

Two execution paths:

* ``blockwise_attention`` — training/prefill. Static outer loop over query
  chunks; inner ``lax.scan`` over KV chunks bounded by the causal/window
  structure (no wasted upper-triangle FLOPs). For softmax/softermax the scan
  carries the online (m, l, acc) state — the synchronization the paper
  removes. For **consmax the carry is the output accumulator alone**: each KV
  chunk contributes ``(exp(s-beta)/gamma) @ v`` independently, which is the
  paper's sync-free property expressed at the JAX level (the Pallas kernel in
  ``kernels/consmax_attn`` is the TPU-tiled version of exactly this loop).

* ``append_attention`` — chunked append-at-index prefill. A fixed-size token
  chunk sitting at per-slot cache position ``index`` attends to
  ``cache[0:index] + itself``. For consmax there is NO online-softmax rescale
  state to carry between prefill chunks — each chunk's ``exp(s-beta)/gamma @
  v`` partial is final — so chunked prefill is literally the blockwise loop
  restarted per chunk; softmax/softermax keep their (m, l) carry inside one
  chunk call. The KV walk is a ``fori_loop`` whose trip count is the *actual*
  fill level, so a chunk near the start of a long cache does not pay for the
  empty tail.

* ``decode_attention`` — single-token decode against a KV cache. Scores for
  one query row are small even at 512k context, so the row is materialized;
  with a sequence-sharded cache, softmax requires global max+sum collectives
  while consmax needs only the output psum (visible in the dry-run HLO).

* ``paged_attention`` — append/decode against a *shared page pool* instead
  of per-slot contiguous rows: a (num_pages, page_size, hkv, dk) K/V buffer
  per layer, every layer's stacked and read at a layer index, plus a
  per-slot page table. The KV walk iterates page-table entries; for
  consmax each page's partial is final (pure-addition combine — the same
  sync-free property, now doing memory-management work), softmax/softermax
  keep their online (m, l) fallback across pages.

Supports GQA (grouped KV heads without materializing repeated K/V), partial /
interleaved RoPE, sliding-window ("local") layers, attn-logit softcapping,
and cross-attention.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import normalizers
from repro.distributed.sharding import shard
from repro.kernels import cache_layout as CL
from repro.kernels.cache_layout import kv_mask
from repro.nn import layers as L
from repro.nn import rope as R

NEG_INF = normalizers.NEG_INF


# ------------------------------------------------------------------ init ----
def attention_init(ctx, name: str, cfg: ModelConfig, *, cross: bool = False):
    d, H, hkv, dk = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    pdt = cfg.pdtype()
    with ctx.scope(name):
        p = {
            "q": L.heads_proj_init(ctx, "q", d, H, dk, bias=cfg.qkv_bias,
                                   dtype=pdt, head_axis="heads"),
            "k": L.heads_proj_init(ctx, "k", d, hkv, dk, bias=cfg.qkv_bias,
                                   dtype=pdt, head_axis="kv_heads"),
            "v": L.heads_proj_init(ctx, "v", d, hkv, dk, bias=cfg.qkv_bias,
                                   dtype=pdt, head_axis="kv_heads"),
            "o": L.heads_out_init(ctx, "o", H, dk, d, dtype=pdt,
                                  head_axis="heads"),
            "score_norm": normalizers.norm_init(
                ctx, "score_norm", cfg.score_norm, H, cfg.consmax),
        }
    return p


# ------------------------------------------------------------- masks ----
def _chunk_mask(qpos, kpos, *, causal, window, kv_len):
    """qpos: (q,) kpos: (c,) -> bool (q, c)."""
    m = jnp.broadcast_to(kpos[None, :] < kv_len,
                         (qpos.shape[0], kpos.shape[0]))
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        m &= (qpos[:, None] - kpos[None, :]) < window
    return m


# ------------------------------------------------- blockwise attention ----
def blockwise_attention(q, k, v, *, norm_kind: str, norm_params,
                        causal: bool = True, window: int = 0,
                        softcap: float = 0.0, merged: bool = False,
                        q_chunk: int = 2048, kv_chunk: int = 1024,
                        q_offset: int = 0):
    """q: (b, sq, H, dk); k, v: (b, skv, hkv, dk). Returns (b, sq, H, dk).

    Chunk scores are computed in fp32; the accumulator is fp32.
    """
    b, sq, H, dk = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = H // hkv
    qc = min(q_chunk, sq)
    kc = min(kv_chunk, skv)

    # pad KV to a chunk multiple once; padded keys masked via kv_len.
    n_kv = -(-skv // kc)
    pad = n_kv * kc - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qg = q.reshape(b, sq, hkv, g, dk)
    cdt = q.dtype

    def q_chunk_body(q_blk, i0, n_lo, n_hi):
        """q_blk: (b, qc_i, hkv, g, dk); scan KV chunks [n_lo, n_hi)."""
        qc_i = q_blk.shape[1]
        qpos = i0 + jnp.arange(qc_i)

        def kv_step(carry, j):
            k_blk = jax.lax.dynamic_slice_in_dim(k, j * kc, kc, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, j * kc, kc, axis=1)
            s = jnp.einsum("bqhgd,bchd->bhgqc", q_blk, k_blk,
                           preferred_element_type=jnp.float32)
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            kpos = j * kc + jnp.arange(kc)
            msk = _chunk_mask(qpos, kpos, causal=causal, window=window,
                              kv_len=skv)[None, None, None]  # (1,1,1,q,c)
            if norm_kind == "consmax":
                acc = carry
                ps = normalizers.apply_norm(
                    "consmax", norm_params,
                    s.reshape(b, H, qc_i, kc), msk.reshape(1, 1, qc_i, kc),
                    head_axis=1, merged=merged).reshape(b, hkv, g, qc_i, kc)
                acc = acc + jnp.einsum("bhgqc,bchd->bqhgd",
                                       ps.astype(cdt), v_blk,
                                       preferred_element_type=jnp.float32)
                return acc, None
            # online softmax / softermax (base e / base 2)
            acc, m, l = carry
            base2 = norm_kind == "softermax"
            s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            expf = jnp.exp2 if base2 else jnp.exp
            alpha = expf(m - m_new)                       # rescale factor
            e = expf(s - m_new[..., None])
            e = jnp.where(msk, e, 0.0)
            l = l * alpha + jnp.sum(e, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqc,bchd->bhgqd", e.astype(cdt), v_blk,
                preferred_element_type=jnp.float32)
            return (acc, m_new, l), None

        js = jnp.arange(n_lo, n_hi)
        if norm_kind == "consmax":
            acc0 = jnp.zeros((b, qc_i, hkv, g, dk), jnp.float32)
            acc, _ = jax.lax.scan(kv_step, acc0, js)
            return acc.astype(cdt)
        acc0 = jnp.zeros((b, hkv, g, qc_i, dk), jnp.float32)
        m0 = jnp.full((b, hkv, g, qc_i), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, qc_i), jnp.float32)
        (acc, _, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), js)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.transpose(out, (0, 3, 1, 2, 4)).astype(cdt)  # b q h g d

    outs = []
    n_q = -(-sq // qc)
    for i in range(n_q):
        i0, i1 = i * qc, min((i + 1) * qc, sq)
        # static causal/window bounds on KV chunks
        hi = n_kv if not causal else min(n_kv, -(-(q_offset + i1) // kc))
        lo = 0
        if window > 0:
            lo = max(0, (q_offset + i0 - window) // kc)
        body = jax.checkpoint(
            partial(q_chunk_body, i0=q_offset + i0, n_lo=lo, n_hi=max(hi, lo + 1)))
        outs.append(body(qg[:, i0:i1]))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out.reshape(b, sq, H, dk)


# ---------------------------------------------------- append attention ----
def _append_cache_write(cache, new, index):
    """Write ``new``: (b, c, hkv, dk) into ``cache``: (b, L, hkv, dk) at
    per-slot row ``index``: (b,).

    Read-modify-write on a c-row window so the write stays in-bounds even
    when ``index + c > L`` (a ragged final chunk near the cache end):
    the window start is clamped to ``L - c`` and the chunk rows are shifted
    to their true absolute positions; window rows below ``index`` keep the
    existing (real) cache content. In the common chunk-aligned case the
    offset is 0 and this reduces to a plain dynamic_update_slice.

    Shape-generic past the row axis: (b, c, hkv, dk) data leaves and
    (b, c, hkv) quantization-scale leaves share this write."""
    L_, c = cache.shape[1], new.shape[1]

    def one(cb, nb, ib):
        start = jnp.clip(ib, 0, max(L_ - c, 0))
        off = ib - start
        win = jax.lax.dynamic_slice_in_dim(cb, start, c, axis=0)
        rows = jnp.arange(c)
        keep = (rows >= off).reshape((c,) + (1,) * (nb.ndim - 1))
        new_win = jnp.where(keep, jnp.roll(nb, off, axis=0), win)
        return jax.lax.dynamic_update_slice_in_dim(cb, new_win, start, axis=0)

    return jax.vmap(one)(cache, new.astype(cache.dtype), index)


def _kv_walk(q, index, lengths, gather, hi, kc, hkv, *, norm_kind,
             norm_params, window=0, softcap=0.0, merged=True,
             block_valid=None):
    """Shared KV walk behind append_attention / paged_attention: a (b, c)
    query chunk at per-slot positions index + [0, c) attends cache blocks
    j = 0..hi, where ``gather(j) -> (k_blk, v_blk)`` yields the
    (b, kc, hkv, dk) block holding logical rows [j*kc, (j+1)*kc) — a
    dynamic slice of a contiguous cache, or a one-page-per-slot gather
    through a page table. Each query row attends causally to rows
    < index + lengths. For consmax the loop carry is the output accumulator
    alone (each block's partial is final); softmax/softermax carry the
    online (m, l) rescale state across blocks.

    ``block_valid(j) -> (b,) bool`` (optional) marks slots whose block j
    holds NO real rows — e.g. a -1 page-table entry, which under sequence
    sharding means "another shard owns this page", not just "unmapped tail".
    Invalid blocks are masked out entirely (the gather may have clamped
    them onto arbitrary real data)."""
    b, c, H, dk = q.shape
    g = H // hkv
    qg = q.reshape(b, c, hkv, g, dk)
    qpos = index[:, None] + jnp.arange(c)                    # (b, c)
    kv_len = index + lengths                                 # (b,)
    cdt = q.dtype

    def block_parts(j):
        k_blk, v_blk = gather(j)
        s = jnp.einsum("bqhgd,bchd->bhgqc", qg, k_blk.astype(cdt),
                       preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j * kc + jnp.arange(kc)
        # the one serving mask formula, shared with the Pallas kernels
        msk = kv_mask(qpos[:, :, None], kpos[None, None, :],
                      kv_len[:, None, None], window)          # (b, c, kc)
        if block_valid is not None:
            msk &= block_valid(j)[:, None, None]
        return s, v_blk.astype(cdt), msk

    if norm_kind == "consmax":
        def body(j, acc):
            s, v_blk, msk = block_parts(j)
            p = normalizers.apply_norm(
                "consmax", norm_params, s.reshape(b, H, c, kc),
                msk[:, None], head_axis=1, merged=merged
            ).reshape(b, hkv, g, c, kc)
            return acc + jnp.einsum("bhgqc,bchd->bqhgd", p.astype(cdt),
                                    v_blk, preferred_element_type=jnp.float32)
        acc = jax.lax.fori_loop(
            0, hi, body, jnp.zeros((b, c, hkv, g, dk), jnp.float32))
        return acc.reshape(b, c, H, dk).astype(cdt)

    # online softmax / softermax: the (m, l) carry spans the whole walk
    base2 = norm_kind == "softermax"
    expf = jnp.exp2 if base2 else jnp.exp

    def body(j, carry):
        acc, m, l = carry
        s, v_blk, msk = block_parts(j)
        msk = msk[:, None, None]                             # (b,1,1,c,kc)
        s = jnp.where(msk, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = expf(m - m_new)
        e = expf(s - m_new[..., None])
        e = jnp.where(msk, e, 0.0)
        l = l * alpha + jnp.sum(e, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqc,bchd->bhgqd", e.astype(cdt), v_blk,
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc0 = jnp.zeros((b, hkv, g, c, dk), jnp.float32)
    m0 = jnp.full((b, hkv, g, c), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, c), jnp.float32)
    acc, _, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, c, H, dk).astype(cdt)


def append_attention(q, k, v, index, lengths, *, norm_kind, norm_params,
                     window=0, softcap=0.0, merged=True, kv_chunk=1024,
                     k_scale=None, v_scale=None):
    """q: (b, c, H, dk) chunk queries at per-slot positions index + [0, c);
    k, v: (b, L, hkv, dk) caches *after* the chunk's K/V were written at
    ``index``; lengths: (b,) real (non-pad) tokens in this chunk.

    Each query row attends causally to cache rows < index + lengths. Rows
    >= lengths are pad queries: their output is garbage and must be ignored
    by the caller (their K/V never entered the cache — see attention_apply).
    The KV loop runs only up to the highest filled chunk, so cost tracks the
    fill level, not the cache capacity.

    ``k_scale``/``v_scale``: (b, L, hkv) fp32 row scales for quantized
    caches — each gathered block is dequantized block-at-a-time (the same
    round-trip the Pallas kernel performs in VMEM); the full cache is never
    materialized dequantized.
    """
    L_ = k.shape[1]
    kc = min(kv_chunk, L_)
    n_kv = -(-L_ // kc)
    pad = n_kv * kc - L_
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
    hi = jnp.max(-(-(index + lengths) // kc))                # dynamic bound

    def gather(j):
        k_blk = jax.lax.dynamic_slice_in_dim(k, j * kc, kc, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v, j * kc, kc, axis=1)
        if k_scale is not None:
            ks = jax.lax.dynamic_slice_in_dim(k_scale, j * kc, kc, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v_scale, j * kc, kc, axis=1)
            k_blk = CL.dequantize_kv(k_blk, ks, q.dtype)
            v_blk = CL.dequantize_kv(v_blk, vs, q.dtype)
        return k_blk, v_blk

    return _kv_walk(q, index, lengths, gather, hi, kc, k.shape[2],
                    norm_kind=norm_kind, norm_params=norm_params,
                    window=window, softcap=softcap, merged=merged)


# ------------------------------------------------------ paged KV cache ----
def _paged_cache_write(pool, new, layer, index, lengths, page_table):
    """Scatter ``new``: (b, c, hkv, dk) into layer ``layer`` of the stacked
    shared page pools ``pool``: (n_layers, P, ps, hkv, dk) at per-slot
    logical rows [index, index + lengths) — in place, nothing sliced. (A
    scale pool: ``new`` (b, c, hkv), ``pool`` (n_layers, P, ps, hkv).)

    Logical row t of slot b lands in pool page ``page_table[b, t // ps]``,
    page row ``t % ps``. Pad rows (>= lengths) and rows whose page-table
    entry is unmapped are routed out of bounds and dropped by the scatter —
    no pad-token K/V ever reaches a page, mirroring the contiguous append
    path. Slots own disjoint pages (the PagePool invariant), so the scatter
    indices never collide.

    A pool that XLA does not store row-major (``CL.page_view``) is written
    a whole page at a time instead (``_paged_page_write``): a row scatter
    would make XLA relay the carried pool out around the layer loop."""
    if CL.page_view(pool.dtype, pool.shape[2:]) != CL.ROW_MAJOR:
        return _paged_page_write(pool, new, layer, index, lengths,
                                 page_table)
    P, ps = pool.shape[1], pool.shape[2]
    b, c = new.shape[:2]
    pos = index[:, None] + jnp.arange(c)[None, :]            # (b, c) logical
    valid = jnp.arange(c)[None, :] < lengths[:, None]
    logical_page = pos // ps
    pid = jnp.take_along_axis(
        page_table, jnp.clip(logical_page, 0, page_table.shape[1] - 1),
        axis=1)
    oob = ~valid | (logical_page >= page_table.shape[1]) | (pid < 0)
    pid = jnp.where(oob, P, pid)                             # dropped below
    row = pos % ps
    return pool.at[layer, pid.reshape(-1), row.reshape(-1)].set(
        new.reshape((b * c,) + new.shape[2:]).astype(pool.dtype),
        mode="drop")


def _paged_page_write(pool, new, layer, index, lengths, page_table):
    """``_paged_cache_write`` a whole page at a time: each page a slot's
    chunk lands on is gathered, its fresh rows merged in, and scattered
    back whole — a scatter whose window is the page's every dim, which XLA
    does in place in any layout, where a row's window ``(hkv, dk)`` would
    have it keep those dims minor-most. Pages that take no fresh row (past
    ``lengths``, unmapped) are dropped, so the pool ends bit-identical to
    the row scatter's."""
    P, ps = pool.shape[1], pool.shape[2]
    b, c = new.shape[:2]
    npg = page_table.shape[1]
    m = (c + ps - 2) // ps + 1                   # pages a chunk can touch
    lp = index[:, None] // ps + jnp.arange(m)[None, :]       # (b, m)
    pid = jnp.take_along_axis(page_table, jnp.clip(lp, 0, npg - 1), axis=1)
    off = (lp[..., None] * ps + jnp.arange(ps)) - index[:, None, None]
    fresh = (off >= 0) & (off < lengths[:, None, None])      # (b, m, ps)
    pid = jnp.where(fresh.any(-1) & (lp < npg) & (pid >= 0), pid, P)
    old = pool[layer, jnp.minimum(pid, P - 1)]     # (b, m, ps, hkv[, dk])
    tail = (1,) * (new.ndim - 2)
    upd = jnp.take_along_axis(
        new, jnp.clip(off, 0, c - 1).reshape(b, m * ps, *tail), axis=1)
    pages = jnp.where(fresh.reshape(fresh.shape + tail),
                      upd.reshape(old.shape).astype(pool.dtype), old)
    return pool.at[layer, pid.reshape(-1)].set(
        pages.reshape((b * m,) + pages.shape[2:]), mode="drop")


def paged_attention(q, kp, vp, layer, page_table, index, lengths, *,
                    norm_kind, norm_params, window=0, softcap=0.0,
                    merged=True, k_scale=None, v_scale=None):
    """Attention of a (b, c, H, dk) chunk against page-pool KV.

    kp, vp: (n_layers, P, ps, hkv, dk) stacked shared pools, read at
    ``layer`` (int32 scalar); page_table: (b, max_pages) int32
    (-1 = unmapped); index: (b,) chunk start positions; lengths: (b,) real
    tokens in the chunk. Covers both chunked append prefill (c > 1) and
    one-token decode (c == 1, lengths = active mask — an inactive slot gets
    kv_len = index, i.e. a fully masked row whose output is discarded).

    The KV walk iterates *page-table entries*: iteration j gathers one page
    per slot (``kp[layer, page_table[:, j]]``, a batched one-page gather)
    holding logical rows [j*ps, (j+1)*ps), bounded by the highest filled
    page across the batch — cost tracks fill level, not pool capacity. For
    consmax the carry is the output accumulator alone: each page's
    ``exp(s-beta)/gamma @ v`` partial is final (the paper's sync-free
    property is what makes paging this cheap). softmax/softermax keep their
    online (m, l) rescale fallback across pages. Unmapped entries are
    clamped to page 0; every position they could contribute sits at
    kpos >= kv_len and is masked.

    ``k_scale``/``v_scale``: (n_layers, P, ps, hkv) fp32 scale pools for
    quantized page pools — each gathered page is dequantized page-at-a-time
    (the round-trip the Pallas kernel performs in VMEM).

    Unmapped entries (-1) are clamped to page 0 by the gather but their
    whole block is masked via ``block_valid`` — under sequence sharding a
    shard's localized table holds -1 for every page another shard owns
    *mid-fill*, where the kv_len bound alone would not exclude page 0's
    (foreign) rows."""
    ps = kp.shape[2]
    hi = jnp.max(-(-(index + lengths) // ps))                # dynamic bound

    def gather(j):
        pid = jnp.maximum(page_table[:, j], 0)               # (b,)
        k_blk, v_blk = kp[layer, pid], vp[layer, pid]        # (b, ps, hkv, dk)
        if k_scale is not None:
            k_blk = CL.dequantize_kv(k_blk, k_scale[layer, pid], q.dtype)
            v_blk = CL.dequantize_kv(v_blk, v_scale[layer, pid], q.dtype)
        return k_blk, v_blk

    return _kv_walk(q, index, lengths, gather, hi, ps, kp.shape[3],
                    norm_kind=norm_kind, norm_params=norm_params,
                    window=window, softcap=softcap, merged=merged,
                    block_valid=lambda j: page_table[:, j] >= 0)


# ---------------------------------------------------- decode attention ----
def decode_attention(q, k, v, index, *, norm_kind, norm_params, window=0,
                     softcap=0.0, merged=True, k_scale=None, v_scale=None):
    """q: (b, 1, H, dk); k, v: (b, L, hkv, dk); index: (b,) current position.

    Materializes the single score row (cheap even at 512k). With consmax the
    kv reduction is a plain weighted sum — partial sums across a sharded L
    axis combine with one psum and no (m, l) exchange.

    ``k_scale``/``v_scale``: (b, L, hkv) fp32 row scales for a quantized
    cache, applied in-register by the einsum inputs (fallback path only —
    the Pallas decode kernel dequantizes per-block in VMEM).
    """
    b, _, H, dk = q.shape
    L_, hkv = k.shape[1], k.shape[2]
    g = H // hkv
    if k_scale is not None:
        k = CL.dequantize_kv(k, k_scale, q.dtype)
        v = CL.dequantize_kv(v, v_scale, q.dtype)
    qg = q.reshape(b, hkv, g, dk)
    s = jnp.einsum("bhgd,bchd->bhgc", qg, k,
                   preferred_element_type=jnp.float32)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    kpos = jnp.arange(L_)
    msk = kv_mask(index[:, None], kpos[None, :],
                  index[:, None] + 1, window)               # (b, L)
    s = s.reshape(b, H, 1, L_)
    msk = msk[:, None, None, :]
    p = normalizers.apply_norm(norm_kind, norm_params, s, msk,
                               head_axis=1, merged=merged)
    p = p.reshape(b, hkv, g, L_).astype(q.dtype)
    out = jnp.einsum("bhgc,bchd->bhgd", p, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, H, dk).astype(q.dtype)


# ----------------------------------------------------------- module api ----
def attention_apply(p, x, cfg: ModelConfig, *, kind: str = "global",
                    positions=None, cache=None, cond=None, merged=False,
                    q_chunk: int = 2048, kv_chunk: int = 1024,
                    decode_kernel: bool = False, decode_kv_block: int = 256,
                    prefill_kernel: bool = False, prefill_kv_block: int = 512,
                    fill_bound: bool = True, prefill_append=None,
                    decode_active=None, page_table=None, layer=None,
                    psum_axes=()):
    """Self- or cross-attention over x: (b, s, d).

    cache: None (train/prefill) or dict(k, v, index) for one-token decode.
    cond:  (b, n_cond, d) conditioning stream for cross-attention.
    decode_kernel: route one-token consmax decode through the split-KV
    Pallas kernel (kernels/consmax_decode) instead of decode_attention.
    prefill_kernel: route chunked consmax append prefill (contiguous and
    paged) through the fused Pallas kernel (kernels/consmax_prefill)
    instead of the jnp KV walk; ``prefill_kv_block`` sizes its KV shards.
    fill_bound: bound the serving kernels' KV grid work by the traced fill
    level (per-slot cache ``index``) instead of cache capacity — fill stays
    a value, never a shape, so the compiled step is shared across fills.
    False keeps the capacity-swept grids for A/B benchmarking.
    prefill_append: (b,) int32 — chunked prefill: x is a fixed-size chunk
    appended at the cache's per-slot ``index``; the entry gives the real
    (non-pad) token count per slot. Pad rows' K/V are zeroed before the
    cache write and ``index`` advances by the real count, so no pad-token
    K/V ever enters the cache and ragged tails need no pad rows.
    decode_active: (b,) bool — one-token decode only: slots where False
    keep their cache row and index untouched (their logits are garbage to
    be discarded), letting a shared decode step skip prefilling/free slots.
    Quantized KV: when the cache dict carries ``k_scale``/``v_scale``
    leaves (int8/fp8 caches — see models.transformer.init_caches), fresh
    K/V rows are quantized per-row-per-head at write time and the kernels
    (or jnp fallbacks) dequantize block-at-a-time at read time; the cache
    is never materialized in a wide dtype.
    page_table: (b, max_pages) int32 — paged KV: the cache's leaves are
    every layer's, stacked — k/v the shared (n_layers, num_pages,
    page_size, hkv, dk) pools, ``index`` (n_layers, b) — and this block
    writes and reads layer ``layer`` (int32 scalar) of them in place; each
    slot's logical rows live on the pages its table row maps (-1 =
    unmapped). Applies to the chunked-prefill and one-token decode cache
    paths only.
    psum_axes: ("model", "seq") mesh axis pair for sharded serving under
    shard_map; empty = single-device, no collective. The combine runs on
    the per-head outputs BEFORE the o-projection: KV shards ("seq", pages
    split) sum by one output-sized fp32 psum — ConSmax partials carry no
    denominator or running max, so cross-shard combine is the same pure
    addition the split-KV kernel uses — while head shards ("model") are
    reassembled by one output-sized all_gather (disjoint heads: pure
    concatenation, bitwise exact). The o-projection weight is REPLICATED
    and applied full-width on every shard, so the einsum sees operands
    bit-identical to the single-device step. These two output-sized
    collectives are the only cross-device traffic on the serving path.
    Returns (out, new_cache).
    """
    b, s, _ = x.shape
    H, hkv, dk = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    cdt = cfg.cdtype()
    cross = cond is not None
    window = cfg.window if kind == "local" else 0
    yarn = None if kind == "local" else cfg.rope_yarn

    # named scopes: ``attn_proj`` (projections, rope), ``kv_cache`` (every
    # write of new K/V into the cache or pool, and conversions of the
    # cache), ``attn`` (the kernel or jnp attention and the mesh combine)
    with jax.named_scope("attn_proj"):
        q = L.heads_proj(p["q"], x, dtype=cdt) * (1.0 / math.sqrt(dk))
        src = cond if cross else x
        k = L.heads_proj(p["k"], src, dtype=cdt)
        v = L.heads_proj(p["v"], src, dtype=cdt)
        q = shard(q, "act_batch,act_seq,act_heads,")
        k = shard(k, "act_batch,act_seq,act_kv_heads,")
        v = shard(v, "act_batch,act_seq,act_kv_heads,")

    rope_on = cfg.rope_style != "none" and not cross
    interleaved = cfg.rope_style == "interleaved"
    rot = int(dk * cfg.rope_fraction)
    if rot % 2:
        rot -= 1

    def rope(q, k, pos):
        if not rope_on:
            return q, k
        with jax.named_scope("attn_proj"):
            return tuple(R.apply_rope(a, pos, rotary_dim=rot,
                                      theta=cfg.rope_theta,
                                      interleaved=interleaved, yarn=yarn)
                         for a in (q, k))

    if cache is not None and page_table is not None and not cross:
        # paged KV: cache leaves are every layer's, stacked; k/v are shared
        # (n_layers, P, ps, hkv, dk) page pools, written and read at
        # ``layer`` in place. One code path covers chunked append prefill
        # (s = chunk) and one-token decode (s == 1, where the active mask
        # doubles as the chunk length: an inactive slot writes nothing and
        # reads a fully masked row).
        if prefill_append is None and s > 1:
            raise NotImplementedError(
                "paged KV caches serve chunked prefill (prefill_append) "
                "and one-token decode only — whole-prompt prefill writes "
                "contiguous rows")
        idx = cache["index"][layer]                          # (b,) int32
        if prefill_append is not None:
            lengths = prefill_append.astype(jnp.int32)
        else:
            lengths = (jnp.ones((b,), jnp.int32) if decode_active is None
                       else decode_active.astype(jnp.int32))
        q, k = rope(q, k, idx[:, None] + jnp.arange(s)[None, :])
        # pad rows / inactive slots are dropped by the scatter itself
        ksp = vsp = None
        with jax.named_scope("kv_cache"):
            if "k_scale" in cache:
                # quantize fresh rows before they enter the pool; the
                # per-row fp32 scales ride the same page-table scatter as
                # the data
                k, ksc = CL.quantize_kv(k, cache["k"].dtype)
                v, vsc = CL.quantize_kv(v, cache["v"].dtype)
                ksp = _paged_cache_write(cache["k_scale"], ksc, layer, idx,
                                         lengths, page_table)
                vsp = _paged_cache_write(cache["v_scale"], vsc, layer, idx,
                                         lengths, page_table)
            kp = _paged_cache_write(cache["k"], k, layer, idx, lengths,
                                    page_table)
            vp = _paged_cache_write(cache["v"], v, layer, idx, lengths,
                                    page_table)
        with jax.named_scope("attn"):
            if (prefill_append is not None and prefill_kernel
                    and cfg.score_norm == "consmax"):
                # fused paged prefill kernel: walks page-table entries
                # via scalar prefetch; pool consumed in cache layout, q
                # pre-scaled
                from repro.kernels.consmax_prefill.ops import (
                    consmax_prefill_paged_op)
                out = consmax_prefill_paged_op(
                    q, kp, vp, layer, page_table, idx, lengths,
                    jnp.broadcast_to(p["score_norm"]["beta"], (H,)),
                    jnp.broadcast_to(p["score_norm"]["gamma"], (H,)),
                    window=window, softcap=cfg.attn_softcap, merged=merged,
                    scale=1.0, fill_bound=fill_bound, k_scale=ksp, v_scale=vsp)
            elif (prefill_append is None and decode_kernel
                    and cfg.score_norm == "consmax"):
                from repro.kernels.consmax_decode.ops import (
                    consmax_decode_paged_op)
                out = consmax_decode_paged_op(
                    q, kp, vp, layer, page_table, idx + lengths,
                    jnp.broadcast_to(p["score_norm"]["beta"], (H,)),
                    jnp.broadcast_to(p["score_norm"]["gamma"], (H,)),
                    window=window, softcap=cfg.attn_softcap, merged=merged,
                    scale=1.0, fill_bound=fill_bound, k_scale=ksp, v_scale=vsp)
            else:
                out = paged_attention(
                    q, kp, vp, layer, page_table, idx, lengths,
                    norm_kind=cfg.score_norm, norm_params=p["score_norm"],
                    window=window, softcap=cfg.attn_softcap, merged=merged,
                    k_scale=ksp, v_scale=vsp)
        new_cache = {"k": kp, "v": vp,
                     "index": cache["index"].at[layer].set(idx + lengths)}
        if ksp is not None:
            new_cache.update(k_scale=ksp, v_scale=vsp)
    elif cache is not None and prefill_append is not None and not cross:
        # chunked append-at-index prefill: x is a (b, c) chunk at per-slot
        # cache position ``index``; prefill_append holds real chunk lengths
        idx = cache["index"]                                 # (b,) int32
        lengths = prefill_append.astype(jnp.int32)
        q, k = rope(q, k, idx[:, None] + jnp.arange(s)[None, :])
        with jax.named_scope("kv_cache"):
            # zero pad rows (>= lengths) so they never enter the cache
            keep = (jnp.arange(s)[None, :]
                    < lengths[:, None])[..., None, None]
            k = jnp.where(keep, k, 0).astype(k.dtype)
            v = jnp.where(keep, v, 0).astype(v.dtype)
            ks_cache = vs_cache = None
            if "k_scale" in cache:
                # quantize after pad-zeroing: zero rows quantize to (0, 1.0)
                # and dequantize back to exact zeros
                k, ksc = CL.quantize_kv(k, cache["k"].dtype)
                v, vsc = CL.quantize_kv(v, cache["v"].dtype)
                ks_cache = _append_cache_write(cache["k_scale"], ksc, idx)
                vs_cache = _append_cache_write(cache["v_scale"], vsc, idx)
            k_cache = _append_cache_write(cache["k"], k, idx)
            v_cache = _append_cache_write(cache["v"], v, idx)
            k_cache = shard(k_cache, "act_batch,act_kv_seq,act_kv_heads,")
            v_cache = shard(v_cache, "act_batch,act_kv_seq,act_kv_heads,")
        if prefill_kernel and cfg.score_norm == "consmax":
            # fused append-prefill kernel: cache consumed in its stored
            # (b, L, hkv, dk) layout (no transpose/astype copy), KV grid
            # axis fully parallel, partials combined by pure addition
            from repro.kernels.consmax_prefill.ops import consmax_prefill_op
            with jax.named_scope("attn"):
                out = consmax_prefill_op(
                    q, k_cache, v_cache, idx, lengths,
                    jnp.broadcast_to(p["score_norm"]["beta"], (H,)),
                    jnp.broadcast_to(p["score_norm"]["gamma"], (H,)),
                    window=window, softcap=cfg.attn_softcap, merged=merged,
                    scale=1.0, bk=prefill_kv_block, fill_bound=fill_bound,
                    k_scale=ks_cache, v_scale=vs_cache)
        else:
            with jax.named_scope("kv_cache"):
                app_k = (k_cache if ks_cache is not None
                         else k_cache.astype(cdt))
                app_v = (v_cache if vs_cache is not None
                         else v_cache.astype(cdt))
            with jax.named_scope("attn"):
                out = append_attention(
                    q, app_k, app_v, idx, lengths,
                    norm_kind=cfg.score_norm, norm_params=p["score_norm"],
                    window=window, softcap=cfg.attn_softcap, merged=merged,
                    kv_chunk=kv_chunk, k_scale=ks_cache, v_scale=vs_cache)
        new_cache = {"k": k_cache, "v": v_cache, "index": idx + lengths}
        if ks_cache is not None:
            new_cache.update(k_scale=ks_cache, v_scale=vs_cache)
    elif cache is None or s > 1:
        # training, or whole-prompt prefill (cache is filled afterwards)
        q, k = rope(q, k, jnp.arange(s)[None, :] if positions is None
                    else positions)
        with jax.named_scope("attn"):
            out = blockwise_attention(
                q, k, v, norm_kind=cfg.score_norm,
                norm_params=p["score_norm"], causal=not cross, window=window,
                softcap=cfg.attn_softcap, merged=merged, q_chunk=q_chunk,
                kv_chunk=kv_chunk)
        new_cache = None
        if cache is not None and not cross:                  # prefill write
            with jax.named_scope("kv_cache"):
                if "k_scale" in cache:
                    # attention above ran on full-precision K/V; only the cache
                    # write pays the quantization round-trip
                    k, ksc = CL.quantize_kv(k, cache["k"].dtype)
                    v, vsc = CL.quantize_kv(v, cache["v"].dtype)
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
                new_cache = {"k": k_cache, "v": v_cache,
                             "index": jnp.full((b,), s, jnp.int32)}
                if "k_scale" in cache:
                    new_cache["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
                        cache["k_scale"], ksc, 0, axis=1)
                    new_cache["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
                        cache["v_scale"], vsc, 0, axis=1)
    else:
        # one-token decode: s == 1
        idx = cache["index"]                                 # (b,) int32
        q, k = rope(q, k, idx[:, None])
        if cross:
            k_full, v_full = k, v                            # cond K/V, no cache
            kv_index = jnp.full((b,), k.shape[1] - 1, jnp.int32)
            new_cache = cache
            with jax.named_scope("attn"):
                out = decode_attention(q, k_full, v_full, kv_index,
                                       norm_kind=cfg.score_norm,
                                       norm_params=p["score_norm"], window=0,
                                       softcap=cfg.attn_softcap,
                                       merged=merged)
        else:
            def upd(c, new, i):
                if decode_active is None:
                    return jax.vmap(
                        lambda cb, nb, ib: jax.lax.dynamic_update_slice_in_dim(
                            cb, nb, ib, axis=0))(c, new, i)

                # inactive slots keep their row: prefilling/free slots in a
                # shared decode batch must not absorb garbage K/V
                def one(cb, nb, ib, ab):
                    old = jax.lax.dynamic_slice_in_dim(
                        cb, ib, nb.shape[0], axis=0)
                    return jax.lax.dynamic_update_slice_in_dim(
                        cb, jnp.where(ab, nb, old), ib, axis=0)
                return jax.vmap(one)(c, new, i, decode_active)
            ks_cache = vs_cache = None
            with jax.named_scope("kv_cache"):
                if "k_scale" in cache:
                    # quantize the one fresh row; ``upd`` is shape-generic
                    # so the (b, 1, hkv) scale row shares the same slot
                    # write
                    k, ksc = CL.quantize_kv(k, cache["k"].dtype)
                    v, vsc = CL.quantize_kv(v, cache["v"].dtype)
                    ks_cache = upd(cache["k_scale"], ksc, idx)
                    vs_cache = upd(cache["v_scale"], vsc, idx)
                k_cache = upd(cache["k"], k.astype(cache["k"].dtype), idx)
                v_cache = upd(cache["v"], v.astype(cache["v"].dtype), idx)
                k_cache = shard(k_cache,
                                "act_batch,act_kv_seq,act_kv_heads,")
                v_cache = shard(v_cache,
                                "act_batch,act_kv_seq,act_kv_heads,")
            if decode_kernel and cfg.score_norm == "consmax":
                # split-KV Pallas kernel; q is already pre-scaled above and
                # the cache is consumed in its stored layout/dtype (per-
                # block casts inside the kernel, no full-cache copy)
                from repro.kernels.consmax_decode.ops import consmax_decode_op
                with jax.named_scope("attn"):
                    out = consmax_decode_op(
                        q, k_cache, v_cache, idx,
                        jnp.broadcast_to(p["score_norm"]["beta"], (H,)),
                        jnp.broadcast_to(p["score_norm"]["gamma"], (H,)),
                        window=window, softcap=cfg.attn_softcap,
                        merged=merged, scale=1.0, bk=decode_kv_block,
                        fill_bound=fill_bound, k_scale=ks_cache,
                        v_scale=vs_cache)
            else:
                with jax.named_scope("kv_cache"):
                    dec_k = (k_cache if ks_cache is not None
                             else k_cache.astype(cdt))
                    dec_v = (v_cache if vs_cache is not None
                             else v_cache.astype(cdt))
                with jax.named_scope("attn"):
                    out = decode_attention(
                        q, dec_k, dec_v, idx, norm_kind=cfg.score_norm,
                        norm_params=p["score_norm"], window=window,
                        softcap=cfg.attn_softcap, merged=merged,
                        k_scale=ks_cache, v_scale=vs_cache)
            step = (1 if decode_active is None
                    else decode_active.astype(idx.dtype))
            new_cache = {"k": k_cache, "v": v_cache, "index": idx + step}
            if ks_cache is not None:
                new_cache.update(k_scale=ks_cache, v_scale=vs_cache)

    if psum_axes:
        model_axis, seq_axis = psum_axes
        # KV ("seq") shards: per-head ConSmax partials combine by the same
        # fp32 addition the split-KV kernel uses — no log-sum-exp exchange,
        # no rescale. Under the block position map a slot whose pages fit
        # one shard sees exactly +0.0 from every other shard, so the sum
        # returns the owner's bits unchanged.
        out = jax.lax.psum(out.astype(jnp.float32), seq_axis)
        # Head ("model") shards own DISJOINT heads — there is nothing to
        # add. Reassemble the full head axis by concatenation (pure data
        # movement, bitwise exact) and apply the FULL o-projection on every
        # shard: the einsum then sees operands bit-identical to the
        # single-device step, so its result is too. (Summing per-shard
        # o-projection partials instead — the megatron-style combine —
        # reassociates the K contraction and is NOT bit-identical.)
        out = jax.lax.all_gather(out, model_axis, axis=-2, tiled=True)
    with jax.named_scope("attn_proj"):
        out = L.heads_out(p["o"], out, dtype=cdt)
        out = shard(out, "act_batch,act_seq,act_embed")
    return out, new_cache
