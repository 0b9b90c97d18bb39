"""Shared layout / masking helpers for the serving-path ConSmax kernels.

The decode (``consmax_decode``) and prefill (``consmax_prefill``) kernels
read the model's KV-cache layout ``(b, L, hkv, dk)`` (or the page pool's
``(P, ps, hkv, dk)``) *directly*. The contiguous kernels and the paged
prefill kernel block it: one grid program covers a block of rows of **all**
hkv heads — K/V blocks ``(…, rows, hkv, dk)``, scale blocks
``(…, rows, hkv)`` — and loops over the heads in its body. The last two
block dims are then the full ``(hkv, dk)`` / ``(rows, hkv)`` extents, which
is what Mosaic's tiling rule accepts on the TPU (a unit hkv block in the
second-minor dim is refused whenever 1 < hkv). The paged decode kernel
walks a run of page-table columns per program: where a bfloat16 pool keeps
two heads a 32-bit word it fetches each live page with its own DMAs and
reads it once for all heads (``packs_head_pairs`` / ``unpack_head_pair``);
other pools arrive one page of all heads per program, as above. No
per-step ``swapaxes`` copy of the cache is ever traced. Everything the
kernel families agree on lives here:

* ``divisor_block`` — pick a block size that tiles the cache axis exactly,
  so blocking never needs a full-cache ``jnp.pad`` copy either.
* ``fold_gqa`` / ``unfold_gqa`` — fold the g = H/hkv query heads that share
  one KV head into the q rows (row = position * g + group-head, i.e.
  position-major), so a chunk's score tile is ``(c*g, bk)``-shaped for the
  MXU without materializing repeated K/V.
* ``head_rows`` — per-row beta/gamma matching that folding, read from the
  ``(H,)`` SMEM parameter vector inside a kernel body.
* ``kv_head`` / ``consmax_partial`` — one KV head's (dequantized) tile out
  of an all-heads block, and one block's ``p @ v`` partial: the body every
  serving kernel runs once per head.
* ``packs_head_pairs`` / ``unpack_head_pair`` — when a bfloat16 page keeps
  two heads in each 32-bit word, and how one pass over the words yields
  both heads' tiles exactly.
* ``tiled_bytes`` — the VMEM a block takes in Mosaic's tiled layout: what
  sizes a paged-decode program's run of pages.
* ``kv_mask`` — the one causal/length/window mask formula shared by the
  kernels and the jnp walks (``core.attention._kv_walk``): a query at
  absolute position ``qpos`` sees cache row ``kpos`` iff ``kpos < kv_len``,
  ``qpos >= kpos`` and (local layers) ``qpos - kpos < window``.
* ``consmax_weights`` — Eq. 2 / merged Eq. 3 of the paper.
* ``quantize_kv`` / ``dequantize_kv`` / ``dequant_block`` — the ONE
  quantization contract for the serving KV caches (``kv_dtype`` ∈
  {bfloat16, int8, fp8_e4m3}): per-row-per-head absmax scaling into fp32
  scale leaves that live beside the cache (contiguous ``(b, L, hkv)`` /
  paged ``(P, ps, hkv)``), quantized at *write* time and dequantized
  per-block in VMEM inside the kernels (``dequant_block``) or per-block in
  the jnp fallback walks (``dequantize_kv``) — the same round-trip on both
  paths, so kernel-vs-oracle comparisons stay exact. Per-row (not
  per-page-scalar) granularity is what lets a page fill incrementally:
  a decode append quantizes only its own row and never forces earlier
  rows of the page to requantize against a grown amax.
* ``live_blocks`` / ``shard_live`` / ``fill_bounded_sum`` — the fill
  bounding shared by the decode AND prefill kernels: serving caches are
  allocated at *capacity* but filled to the per-slot ``index``, and ConSmax
  shard partials are order-free and skippable (no running max, no
  denominator), so a KV shard that ``kv_mask`` would zero anyway can simply
  not run. ``live_blocks`` clamps a kernel's KV grid axis to the traced
  batch-max shard count (a *value* — the compiled shape never changes with
  fill), ``shard_live`` is the per-program ``pl.when`` predicate (per-slot
  fill, causal reach, window reach), and ``fill_bounded_sum`` is the
  caller-side combine that touches only the live prefix of the partials
  buffer (slots beyond it are never written by the clamped grid).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def divisor_block(n: int, bk: int) -> int:
    """Largest block size <= ``bk`` that divides ``n`` exactly.

    Used instead of padding: padding a cache-sized operand to a block
    multiple would copy the whole cache every step, which is exactly what
    the cache-layout kernels exist to avoid. Serving shapes (max_seq,
    prefill_chunk, page_size) are block-friendly powers of two; odd
    standalone shapes degrade to a smaller block, not to a copy.
    """
    bk = max(1, min(bk, n))
    while n % bk:
        bk -= 1
    return bk


def tiled_bytes(shape, dtype) -> int:
    """Bytes a VMEM block of ``shape`` occupies in Mosaic's tiled layout:
    the last dim pads to 128 lanes; the second-minor dim pads to the dtype's
    sublane tile (8 rows of 32 bits: 8 fp32, 16 bf16, 32 int8), or, when it
    is shorter than a tile, to the next power of two (Mosaic's small
    tilings). So an all-heads scale block ``(rows, hkv)`` costs ``rows``
    full lane rows, and a ``(…, hkv, dk)`` K/V block costs little more than
    its data."""
    itemsize = np.dtype(dtype).itemsize
    dims = [int(d) for d in shape] or [1]
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) >= 2:
        tile = 8 * max(1, 4 // itemsize)
        r = dims[-2]
        dims[-2] = (-(-r // tile) * tile if r >= tile
                    else 1 << max(0, (r - 1).bit_length()))
    return int(np.prod(dims, dtype=np.int64)) * itemsize


def block_cache_rows(k, v, bk: int):
    """Choose the KV row-block size for a (b, L, hkv, dk) cache (or
    anything blocked along axis 1) and return ``(k, v, bk_eff, n_blocks)``.

    Prefers a divisor of L (no copy — the serving hot path, where L is a
    block-friendly power of two). Only when the best divisor is degenerate
    (< 8 rows: prime/awkward standalone L, where (g, 1)-shaped tiles and an
    L-program grid would be far worse than one copy) does it fall back to
    padding L up to a ``bk`` multiple; padded rows sit at kpos >= kv_len
    and are masked to exact zeros by ``kv_mask``.
    """
    L = k.shape[1]
    bk_eff = divisor_block(L, bk)
    if bk_eff == min(bk, L) or bk_eff >= 8:
        return k, v, bk_eff, L // bk_eff
    nb = -(-L // bk)
    pad = ((0, 0), (0, nb * bk - L), (0, 0), (0, 0))
    return jnp.pad(k, pad), jnp.pad(v, pad), bk, nb


def fold_gqa(q: jnp.ndarray, hkv: int) -> jnp.ndarray:
    """(b, c, H, dk) queries -> (b, hkv, c*g, dk), position-major rows.

    Row ``r`` of KV head ``h`` holds query head ``h*g + r % g`` at chunk
    position ``r // g`` — so a contiguous row block is a contiguous span of
    chunk positions (q-axis grid blocking stays a plain BlockSpec index).
    Only the chunk is transposed; the cache never is.
    """
    b, c, H, dk = q.shape
    g = H // hkv
    return q.reshape(b, c, hkv, g, dk).swapaxes(1, 2).reshape(
        b, hkv, c * g, dk)


def unfold_gqa(out: jnp.ndarray, b: int, c: int, H: int) -> jnp.ndarray:
    """(b, hkv, c*g, dk) kernel output -> (b, c, H, dk)."""
    hkv, dk = out.shape[1], out.shape[-1]
    g = H // hkv
    return out.reshape(b, hkv, c, g, dk).swapaxes(1, 2).reshape(b, c, H, dk)


def head_rows(ref, h: int, g: int, rows: int):
    """(rows, 1) fp32 column of the per-head parameter in SMEM ``ref`` (H,)
    for KV head ``h``: row ``r`` of a ``fold_gqa`` tile (position-major, so
    decode's g rows too) belongs to query head ``h*g + r % g``. Built from
    scalar reads and selects — the values are copied, never recomputed, so
    every kernel sees exactly the per-head parameters."""
    col = jnp.full((rows, 1), ref[h * g], jnp.float32)
    if g > 1:
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % g
        for j in range(1, g):
            col = jnp.where(r == j, ref[h * g + j], col)
    return col


def kv_head(x_ref, s_ref, i: int, h: int, dtype):
    """KV head ``h`` of leading block row ``i`` of an all-heads K/V block
    ``(n, rows, hkv, dk)`` as a ``(rows, dk)`` tile in ``dtype`` —
    dequantized in VMEM when its ``(n, rows, hkv)`` scale block ``s_ref``
    rides along (None = cache stored as-is)."""
    if s_ref is None:
        return x_ref[i, :, h].astype(dtype)
    return dequant_block(x_ref[i, :, h], s_ref[i, :, h:h + 1], dtype)


def packs_head_pairs(dtype, hkv: int, dk: int) -> bool:
    """True iff a ``(rows, hkv, dk)`` page stores each row's KV heads two
    to a 32-bit word: bfloat16, an even head count and 128-lane head rows.
    XLA's TPU layout then keeps the pool's ``(…, hkv, dk)`` tiles with
    heads ``2j`` and ``2j + 1`` in the low and high halves of one word, so
    the page viewed as ``(rows * hkv, dk)`` is the same bytes."""
    return (jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16) and hkv % 2 == 0
            and dk == 128)


def unpack_head_pair(words, dtype):
    """``words``: a uint32 tile whose words each hold two bf16 values,
    the even head's in the low half. Returns the two heads' tiles in
    ``dtype``: each half moved to the top of an fp32 word is exactly that
    bf16 value, so the cast loses nothing and the tiles equal the stored
    values."""
    lo = jax.lax.bitcast_convert_type(words << 16, jnp.float32)
    hi = jax.lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return lo.astype(dtype), hi.astype(dtype)


def consmax_partial(q, k, v, mask, beta, gamma, *, scale: float,
                    softcap: float, merged: bool):
    """One KV block's ConSmax partial ``p @ v`` in fp32: q (m, dk), k/v
    (rows, dk), ``mask`` (m, rows), beta/gamma (m, 1). No running max and no
    denominator, so partials of different blocks combine by addition."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    p = jnp.where(mask, consmax_weights(s, beta, gamma, merged), 0.0)
    return jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def kv_mask(qpos, kpos, kv_len, window: int):
    """The serving-path attention mask, shared verbatim by the Pallas
    kernels and the jnp KV walks: causal vs the absolute query position,
    bounded by the slot's valid-row count, optionally sliding-window."""
    mask = (kpos < kv_len) & (qpos >= kpos)
    if window > 0:
        mask = mask & ((qpos - kpos) < window)
    return mask


def live_blocks(max_kv_len, block: int, n_cap: int):
    """Traced count of ``block``-row KV shards holding any valid cache row.

    ``max_kv_len`` is the batch-max fill level (a traced value inside the
    jitted serving steps); the result clamps a kernel's KV grid axis so
    programs beyond the fill never launch. Bounded to [1, n_cap]: the grid
    must stay non-empty and never exceed the capacity-sized partials
    allocation. Fill stays a *value* — one compiled step serves every fill
    level."""
    return jnp.clip((max_kv_len + block - 1) // block, 1, n_cap)


def shard_live(start, size: int, kv_len, *, qpos_hi=None, qpos_lo=None,
               window: int = 0):
    """True iff cache rows [start, start + size) can contribute a non-zero
    partial for any query in [qpos_lo, qpos_hi] — the per-program skip
    predicate of the fill-bounded kernels, the grid-level complement of
    ``kv_mask``:

    * ``start < kv_len`` — the shard holds at least one *filled* row,
    * ``start <= qpos_hi`` — at least one row is causally visible,
    * window reach — the shard's last row is not entirely behind the
      sliding window of the block's earliest query.

    A shard that fails computes only masked-to-zero weights; ConSmax makes
    skipping it a pure zero-write (partials combine by addition — there is
    no rescale or denominator a skipped shard would owe)."""
    live = start < kv_len
    if qpos_hi is not None:
        live &= start <= qpos_hi
    if window > 0 and qpos_lo is not None:
        live &= (start + size) > (qpos_lo - window + 1)
    return live


def fill_bounded_sum(partials, n_live, axis: int = 2):
    """Sum ``partials`` along ``axis``, treating slots >= ``n_live`` as
    exact zeros.

    ``n_live`` may be traced (the ``live_blocks`` clamp): slots at or
    beyond it were *never written* by the clamped grid, so they are
    ``where``-selected to 0.0 (a select, not arithmetic — uninitialized
    garbage, even NaN, never propagates) before the same capacity-shaped
    ``jnp.sum`` the capacity-swept path uses. The reduction tree is
    therefore identical in both paths, and a capacity-swept kernel writes
    exact zeros into dead-shard slots anyway (fully masked weights), so
    bounded and unbounded outputs are bit-identical."""
    shape = [1] * partials.ndim
    shape[axis] = partials.shape[axis]
    live = jax.lax.broadcasted_iota(jnp.int32, tuple(shape), axis) < n_live
    return jnp.sum(jnp.where(live, partials, 0.0), axis=axis)


def consmax_weights(s, beta, gamma, merged: bool):
    """ConSmax score weights: Eq. 2 (training form) or the merged
    inference constant C = e^{-beta}/gamma (Eq. 3). ``beta``/``gamma``
    broadcast against the fp32 score tile ``s``."""
    if merged:
        return jnp.exp(-beta) / gamma * jnp.exp(s)
    return jnp.exp(s - beta) / gamma


# ------------------------------------------------ sequence-sharded pages ----
# Under ServeConfig.seq_shards = ns > 1 the paged pool's P axis is split into
# ns contiguous per-device blocks: shard d owns physical pages
# [d * P/ns, (d+1) * P/ns). The host allocator is position-rigid with a BLOCK
# position map (slot page position j is always backed by a page owned by
# shard j // ceil(max_pages_per_slot/ns) — serve/scheduler.PagePool explains
# why that preserves token bit-identity where an interleave cannot), the
# engine keeps ONE global page table, and each shard localizes it inside
# shard_map: entries it owns become local indices into its pool slice,
# everything else becomes -1 — the same "unmapped" sentinel mid-fill holes
# already use, which the fill-bounded kernels (and the jnp walk's
# block-validity mask) gate on.


def page_shard(page: int, pages_per_shard: int) -> int:
    """Owning shard of physical page ``page`` (host-side allocator math)."""
    return page // pages_per_shard


def position_shard(pos: int, position_block: int, seq_shards: int) -> int:
    """Shard that must back slot page position ``pos``: block map with
    ``position_block = ceil(max_pages_per_slot / seq_shards)`` positions
    per shard — a request within one block stays whole-shard (bit-identical
    psum), a longer one spills block by block across the "seq" axis."""
    return min(pos // position_block, seq_shards - 1)


def localize_page_table(table, shard, pages_per_shard: int):
    """Global page table -> this shard's local view: owned entries become
    indices into the shard's pool slice, non-owned (and already -1) entries
    become -1. Identity when the pool is unsharded (shard 0 owns all P
    pages). ``shard`` may be traced (``lax.axis_index`` inside shard_map)."""
    owned = (table >= 0) & (table // pages_per_shard == shard)
    return jnp.where(owned, table - shard * pages_per_shard, -1)


# --------------------------------------------------- quantized KV cache ----
# The serving caches may store K/V below bf16 (ServeConfig.kv_cache_dtype):
# decode is HBM-bandwidth-bound, so int8/fp8 KV halves the bytes the KV walk
# moves per step. One scale per cache ROW per KV HEAD (fp32, living in
# ``k_scale``/``v_scale`` cache leaves shaped like the cache minus its dk
# axis) — per-row granularity means an incremental append (one decode row
# into a partially filled page) never requantizes earlier rows, and the
# scale leaves add only hkv * 4 bytes per row next to hkv * dk data bytes
# (int8 total ≈ 1.97x smaller than bf16 at dk = 64).

KV_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "bf16": jnp.bfloat16,
    "int8": jnp.int8,
    "fp8_e4m3": jnp.float8_e4m3fn,
}


def kv_cache_dtype(name):
    """The jnp dtype a ``ServeConfig.kv_cache_dtype`` name stores K/V in.
    (``jnp.dtype("fp8_e4m3")`` would throw — the names are ours, the
    mapping lives here so every consumer agrees.)"""
    if isinstance(name, str):
        if name not in KV_DTYPES:
            raise ValueError(
                f"unknown kv cache dtype {name!r}; expected one of "
                f"{sorted(KV_DTYPES)}")
        return jnp.dtype(KV_DTYPES[name])
    return jnp.dtype(name)


def kv_quantized(name) -> bool:
    """True iff this kv dtype needs scale leaves + write-time quantization
    (bf16 is stored as-is — the default path is byte-identical to before
    quantization existed)."""
    return kv_cache_dtype(name) in (jnp.dtype(jnp.int8),
                                    jnp.dtype(jnp.float8_e4m3fn))


def kv_qmax(dtype) -> float:
    """Largest representable magnitude the quantizer scales rows onto."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.dtype(jnp.int8):
        return 127.0
    if dtype == jnp.dtype(jnp.float8_e4m3fn):
        return 448.0
    raise ValueError(f"kv_qmax: {dtype} is not a quantized kv dtype")


def quantize_kv(x, dtype):
    """Quantize K/V rows ``x``: (..., hkv, dk) -> (q (..., hkv, dk) in
    ``dtype``, scale (..., hkv) fp32) with per-row-per-head absmax scaling.

    All-zero rows (pad rows, untouched cache tail) get scale 1.0 and
    quantize to exact zeros, so they dequantize to the exact zeros the
    unquantized path stores. Called at every cache WRITE site (prefill
    append, paged scatter, decode append, whole-prompt fill) — reads never
    requantize."""
    dtype = jnp.dtype(dtype)
    xf = x.astype(jnp.float32)
    qmax = kv_qmax(dtype)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
    q = xf / scale[..., None]
    if dtype == jnp.dtype(jnp.int8):
        q = jnp.clip(jnp.round(q), -qmax, qmax)
    return q.astype(dtype), scale


def dequantize_kv(q, scale, out_dtype=jnp.float32):
    """Inverse of ``quantize_kv``: (..., hkv, dk) quantized values times
    their (..., hkv) fp32 row scales. The jnp fallback walks call this
    per-BLOCK (a page or KV chunk at a time) — the full cache is never
    upcast into HBM on the serving path."""
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
            ).astype(out_dtype)


def dequant_block(x, scale, out_dtype):
    """In-kernel per-block dequant: ``x`` a (rows, dk) VMEM tile, ``scale``
    its (rows, 1) fp32 scale column. Identical arithmetic to
    ``dequantize_kv`` (f32 multiply, then cast) so the Pallas kernels and
    the jnp oracles round-trip bit-identically."""
    return (x.astype(jnp.float32) * scale).astype(out_dtype)


def block_scale_rows(s, bk_eff: int, n_blocks: int):
    """Pad a (b, L, hkv) scale leaf along axis 1 to match the (rare)
    degenerate-divisor padding ``block_cache_rows`` applied to its K/V —
    padded rows carry scale 0 and sit at kpos >= kv_len, masked to exact
    zeros either way. No-op (and no copy) for serving shapes."""
    if s is None:
        return None
    L = s.shape[1]
    target = bk_eff * n_blocks
    if L == target:
        return s
    return jnp.pad(s, ((0, 0), (0, target - L), (0, 0)))
