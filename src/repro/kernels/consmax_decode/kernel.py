"""Split-KV ConSmax decode Pallas kernel (TPU target).

Single-query-token attention against a long KV cache, the serving hot path.
Where the training-time attention kernel (../consmax_attn) walks KV blocks
*sequentially* (grid trailing dim 'arbitrary', fp32 accumulator carried
across iterations), this kernel exploits the paper's sync-free property one
step further: with no running max and no denominator sum, the partial
``p @ v`` contribution of every KV shard is *independent*, so the KV axis of
the grid is marked ``parallel`` like everything else. Each program writes its
shard's partial into its own output slot and the shards combine by a plain
fp32 addition outside the kernel — no rescale pass, no (m, l) exchange, no
cross-shard ordering. This is the decode-time analogue of flash-decoding's
split-KV, but without the log-sum-exp combine step softmax forces.

Both variants read the model's cache layout **directly** — contiguous
``(b, L, hkv, dk)`` rows or the shared ``(P, ps, hkv, dk)`` page pool — so a
decode step never traces a transposed (or padded) copy of the cache.

The contiguous kernel's grid program covers a KV shard of **all** hkv
heads: K/V blocks ``(…, bk, hkv, dk)``, scale blocks ``(…, bk, hkv)``,
per-head beta/gamma read from SMEM, and a static loop over the heads in the
body. The last two block dims are therefore full extents, which Mosaic's
TPU tiling rule accepts at every width. The block size is chosen by
``cache_layout.divisor_block`` to tile L exactly.

The paged kernel's program walks a **run** of ``ppb`` columns of one
slot's page table. Where a bfloat16 pool has an even head count and
128-lane head rows (qwen2-1.5b), the pools stay in HBM and the program
fetches each live page of its run with its own DMAs, all in flight before
the first page is computed, so pages stream in while earlier ones compute;
``ppb`` is the most pages whose K and V fit ``PAGE_VMEM_BYTES`` of VMEM
(the whole table at qwen2-1.5b's 32 columns). Each such page is read
**once** for all heads: it stores a row's heads two to a 32-bit word, so
the page is loaded as words and every pair of heads comes out of one word
by a shift and a mask (``cache_layout.unpack_head_pair``), exact and with
no strided gather per head. Other pools (8 KV heads of dk 64, int8, fp8)
keep ``ppb = 1``: the page arrives through the BlockSpec index map and is
sliced per head. Layout folding, the mask formula, the per-head body and
the ConSmax weights are shared with the prefill kernel via
``kernels/cache_layout.py``.

Per (slot, kv-head, kv-shard) — the head a static loop inside a program:

    s = q @ k^T * scale            (MXU; q is the g-row GQA group)
    p = exp(s - beta) / gamma      (VPU; masked by per-slot length)
    o = p @ v                      (MXU; partial, summed across shards later)

**Fill bounding** (``fill_bound=True``, the default): serving caches are
allocated at capacity but filled to the per-slot ``lengths``. The KV grid
axis is clamped to the traced *batch-max* live shard count
(``cache_layout.live_blocks`` — a value, so one compiled step serves every
fill level), each program ``pl.when``-skips shards the per-slot lengths (or
the sliding window) already zero — writing exact zeros to its partial
slot instead of masking a full compute — and the caller-side combine
(``cache_layout.fill_bounded_sum``) touches only the live prefix of the
capacity-sized partials buffer; slots beyond it are never written or read.
ConSmax is what makes the skip this simple: a dead shard owes no rescale
and no denominator term, so "skip" is literally "contribute zero".
``fill_bound=False`` keeps the capacity-swept grid — the before/after
baseline for the benchmark's fill sweep.

The contiguous bounded kernel additionally *folds the batch into the
block*: ``bf`` slots (largest divisor of b <= 8, VMEM-bounded) share every
program, so the per-program overhead (block DMA setup on TPU, the
full-operand grid sweep in interpret mode) is amortized ``bf``-fold. Each
folded slot still runs its own 2-D dots, so the result is bit-identical to
the capacity sweep (``bf = 1``). The ``pl.when`` skip fires per
(slot-group, shard); per-slot raggedness inside a live group is handled by
the length mask. The paged variant keeps one slot per program: its pages
are gathered through that slot's table row.

GQA is folded into the q rows: the g = n_heads/n_kv_heads query heads that
share one KV head form the (g, d) left operand, so the score tile is (g, bk).

VMEM per program: contiguous, K/V blocks ``bf·bk·hkv·d`` each plus a
(bf, hkv, g, d) q/out block; ``_fold_factor`` caps the fold so each K/V
block stays under 2 MB counted at fp32 — comfortably double-bufferable.
Paged, ``ppb`` pages of K and V in scratch (or one pipelined page of K,
V and scales) plus a (1, hkv, ppb, g, d) partials block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import cache_layout as CL


def _decode_blocks(n_of, slots: int, start, rows, refs, o_ref, *,
                   bounded: bool, g: int, window: int, **partial_kw):
    """The body every contiguous decode program runs: block slot
    ``i < slots`` has ``n_of(i)`` valid rows (its decode row sits at
    ``n_of(i) - 1``), the block holds cache rows ``[start, start + rows)``
    of every KV head, and each (slot, head) writes its own fp32 partial
    ``o_ref[i, h, 0]``. Slots run in a loop, not unrolled: Mosaic's compile
    time grows much faster than linearly in unrolled bodies."""
    beta_ref, gamma_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref = refs
    hkv = q_ref.shape[1]

    def compute():
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, (g, rows), 1)
        for h in range(hkv):
            beta = CL.head_rows(beta_ref, h, g, g)
            gamma = CL.head_rows(gamma_ref, h, g, g)

            @pl.loop(0, slots)
            def _slot(i):
                n = n_of(i)
                q = q_ref[i, h]                              # (g, d)
                o_ref[i, h, 0] = CL.consmax_partial(
                    q, CL.kv_head(k_ref, ks_ref, i, h, q.dtype),
                    CL.kv_head(v_ref, vs_ref, i, h, q.dtype),
                    CL.kv_mask(n - 1, kpos, n, window), beta, gamma,
                    **partial_kw)

    if not bounded:
        compute()
        return
    # fill bounding: a block past every slot's fill or entirely behind
    # every window would compute only masked-to-zero weights — write the
    # zeros directly
    live = functools.reduce(jnp.logical_or, [
        CL.shard_live(start, rows, n_of(i), qpos_lo=n_of(i) - 1,
                      window=window) for i in range(slots)])
    pl.when(live)(compute)

    @pl.when(jnp.logical_not(live))
    def _skip():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)


def _kernel(len_ref, beta_ref, gamma_ref, q_ref, k_ref, v_ref, *rest,
            bk: int, bf: int, **kw):
    """Contiguous cache: program (slot-group, shard), ``bf`` slots."""
    *scale_refs, o_ref = rest                        # quantized KV: (ks, vs)
    ig, ik = pl.program_id(0), pl.program_id(1)
    _decode_blocks(lambda i: len_ref[ig * bf + i], bf, ik * bk, bk,
                   (beta_ref, gamma_ref, q_ref, k_ref, v_ref,
                    *(scale_refs or (None, None))),
                   o_ref, **kw)


def _fold_factor(b: int, bk: int, hkv: int, d: int,
                 limit_bytes: int = 2 << 20) -> int:
    """Slots folded per bounded-decode block: the largest divisor of ``b``
    whose all-heads K/V blocks stay under ``limit_bytes`` each (fp32),
    capped at 8."""
    cap = max(1, limit_bytes // (bk * hkv * d * 4))
    return max(f for f in range(1, min(b, 8, cap) + 1) if b % f == 0)


def consmax_decode(q, k, v, lengths, beta, gamma, *, window: int = 0,
                   softcap: float = 0.0, merged: bool = True,
                   scale: float | None = None, bk: int = 256,
                   fill_bound: bool = True, interpret: bool = False,
                   k_scale=None, v_scale=None):
    """q: (b, nh, d); k, v: (b, L, hkv, d) — the model's cache layout,
    consumed as-is; lengths: (b,) int32 valid counts; beta/gamma: (nh,)
    fp32. Returns (b, nh, d) in q.dtype.

    ``k_scale``/``v_scale``: (b, L, hkv) fp32 per-row-per-head quant scales
    for a quantized (int8/fp8) cache — ride in as small extra operands and
    the kernel upcasts each KV block in VMEM (``cache_layout.dequant_block``),
    so the HBM KV walk moves the narrow bytes. None = cache stored as-is.

    Grid (b/bf, n_shards) — ALL dims parallel; every program covers all hkv
    heads. Shard partials are summed in fp32 by the caller-side reduction
    below (a pure addition; the absence of a softmax combine step is the
    point). With ``fill_bound`` (default) the shard axis is clamped to the
    traced batch-max live shard count, ``bf`` slots share a block
    (per-program overhead amortized), and dead (slot-group, shard) programs
    are ``pl.when``-skipped — KV work tracks *fill*, not cache capacity,
    bit-identically (dead shards contribute exact zeros either way).
    ``fill_bound=False`` sweeps the full per-slot capacity grid (``bf = 1``,
    the pre-fill-bounding behaviour, kept as the benchmark baseline).
    The shard size is the largest divisor of L <= ``bk``, so serving shapes
    are never padded (padding, like the old (b, hkv, L, d) transpose, would
    copy the full cache every step); only a degenerate-divisor L (prime-ish
    standalone shapes) falls back to one padded copy — see
    ``cache_layout.block_cache_rows``.
    """
    b, nh, d = q.shape
    hkv = k.shape[2]
    g = nh // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    k, v, bk, ns = CL.block_cache_rows(k, v, bk)
    quant = k_scale is not None
    if quant:
        k_scale = CL.block_scale_rows(k_scale, bk, ns)
        v_scale = CL.block_scale_rows(v_scale, bk, ns)

    # the grid clamp: a traced VALUE, never a shape — the partials buffer
    # stays capacity-sized but its slots >= ns_live are never written (and
    # never read by the fill-bounded combine below)
    ns_live = CL.live_blocks(jnp.max(lengths), bk, ns) if fill_bound else ns
    bf = _fold_factor(b, bk, hkv, d) if fill_bound else 1
    kernel = functools.partial(_kernel, scale=scale, window=window,
                               softcap=softcap, bk=bk, g=g, merged=merged,
                               bf=bf, bounded=fill_bound)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kv_spec = pl.BlockSpec((bf, bk, hkv, d), lambda ig, ik: (ig, ik, 0, 0))
    in_specs = [smem, smem, smem,                    # lengths, beta, gamma
                pl.BlockSpec((bf, hkv, g, d), lambda ig, ik: (ig, 0, 0, 0)),
                kv_spec, kv_spec]
    operands = [lengths.astype(jnp.int32), beta.astype(jnp.float32),
                gamma.astype(jnp.float32), q.reshape(b, hkv, g, d), k, v]
    if quant:
        # fp32 row scales, blocked alongside their K/V shard (dk/4x
        # smaller than the data operand they rescale)
        in_specs += [pl.BlockSpec((bf, bk, hkv),
                                  lambda ig, ik: (ig, ik, 0))] * 2
        operands += [k_scale, v_scale]
    partials = pl.pallas_call(
        kernel,
        grid=(b // bf, ns_live),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bf, hkv, 1, g, d),
                               lambda ig, ik: (ig, 0, ik, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, ns, g, d), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(*operands)

    out = CL.fill_bounded_sum(partials, ns_live)     # the sync-free combine
    return out.reshape(b, nh, d).astype(q.dtype)


# ------------------------------------------------------------- paged KV ----
PAGE_VMEM_BYTES = 8 << 20     # fetched pages one paged-decode program holds


def _pages_per_program(npg: int, page_bytes: int) -> int:
    """Table columns one paged-decode program walks when it fetches its own
    pages: as many as fit ``PAGE_VMEM_BYTES`` (``page_bytes`` counts a
    page's K and V in VMEM's tiled layout), at least 1, at most the table
    width."""
    return max(1, min(npg, PAGE_VMEM_BYTES // page_bytes))


def _paged_kernel(tab_ref, len_ref, beta_ref, gamma_ref, q_ref, *refs,
                  ps: int, ppb: int, npg: int, n_pools: int, packed: bool,
                  bounded: bool, g: int, window: int, **partial_kw):
    """Program (slot, run): table columns ``[jb * ppb, jb * ppb + ppb)`` of
    slot ``ib``; each column writes its own fp32 partial ``o_ref[0, h, i]``
    per head. Bounded, a column is computed only if it is in the table,
    mapped and holds a live row (by length and window); the others write
    exact zeros. Unbounded, every column in the table is computed, unmapped
    ones from page 0, whose rows sit at kpos >= n.

    ``packed``: the pools are HBM refs of ``(ps * hkv, d)`` word-packed
    pages, and the program DMAs each column it computes into VMEM scratch
    itself, all started before the first is waited on, so later pages
    stream in while earlier ones compute; dead columns are never fetched.
    Otherwise ``ppb`` is 1 and the refs are the one page (and its scales)
    the pipeline fetched through the BlockSpec index map."""
    pools, (o_ref, *scratch) = refs[:n_pools], refs[n_pools:]
    ib, jb = pl.program_id(0), pl.program_id(1)
    n = len_ref[ib]
    hkv, d = q_ref.shape[1], q_ref.shape[-1]
    dtype = q_ref.dtype

    def column(i):
        j = jb * ppb + i
        entry = tab_ref[ib, jnp.minimum(j, npg - 1)]
        live = j < npg
        if bounded:
            live &= (entry >= 0) & CL.shard_live(j * ps, ps, n, qpos_lo=n - 1,
                                                 window=window)
        return j, jnp.maximum(entry, 0), live

    if packed:
        bufs, sem = scratch[:n_pools], scratch[n_pools]

        def copies(i, page):
            return [pltpu.make_async_copy(src.at[page], dst.at[i],
                                          sem.at[c, i])
                    for c, (src, dst) in enumerate(zip(pools, bufs))]

        @pl.loop(0, ppb)
        def _fetch(i):
            _, page, live = column(i)

            @pl.when(live)
            def _():
                for c in copies(i, page):
                    c.start()
    else:
        bufs = pools

    qs = [q_ref[0, h] for h in range(hkv)]                   # (g, d) each
    params = [(CL.head_rows(beta_ref, h, g, g),
               CL.head_rows(gamma_ref, h, g, g)) for h in range(hkv)]

    def heads(i):
        """Each head's (ps, d) K and V tiles of buffered page ``i``."""
        if not packed:
            ks, vs = bufs[2:] or (None, None)
            return [(CL.kv_head(bufs[0], ks, i, h, dtype),
                     CL.kv_head(bufs[1], vs, i, h, dtype))
                    for h in range(hkv)]
        # one pass over the page's 32-bit words: a row's heads 2p and 2p + 1
        # share a word, row p of every hkv/2 in the word view
        out = []
        for buf in bufs:
            words = buf.bitcast(jnp.uint32)
            tiles = []
            for p in range(hkv // 2):
                rows = (pl.ds(p, ps, stride=hkv // 2) if hkv > 2
                        else slice(None))
                tiles += CL.unpack_head_pair(words[i, rows, :], dtype)
            out.append(tiles)
        return zip(*out)

    @pl.loop(0, ppb)
    def _compute(i):
        j, page, live = column(i)

        @pl.when(live)
        def _():
            if packed:
                for c in copies(i, page):
                    c.wait()
            kpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (g, ps), 1)
            mask = CL.kv_mask(n - 1, kpos, n, window)
            for h, (k, v) in enumerate(heads(i)):
                o_ref[0, h, i] = CL.consmax_partial(qs[h], k, v, mask,
                                                    *params[h], **partial_kw)

        @pl.when(jnp.logical_not(live))
        def _():
            o_ref[0, :, i] = jnp.zeros((hkv, g, d), jnp.float32)


def consmax_decode_paged(q, kp, vp, page_table, lengths, beta, gamma, *,
                         window: int = 0, softcap: float = 0.0,
                         merged: bool = True, scale: float | None = None,
                         fill_bound: bool = True, interpret: bool = False,
                         k_scale=None, v_scale=None):
    """Paged split-KV ConSmax decode. q: (b, nh, d); kp, vp: shared page
    pools (P, ps, nkv, d); page_table: (b, max_pages) int32 (-1 = unmapped);
    lengths: (b,) valid logical rows; beta/gamma: (nh,) fp32.
    ``k_scale``/``v_scale``: (P, ps, nkv) fp32 per-row-per-head quant scale
    pools living beside the page table for a quantized (int8/fp8) KV pool —
    fetched with their pages and upcast per page in VMEM.

    Grid (b, runs): program (ib, jb) walks ``ppb`` consecutive columns of
    slot ib's table row, which rides in as a scalar-prefetch operand — no
    materialized per-slot contiguous cache. A bfloat16 pool with an even
    head count and 128-lane head rows (``CL.packs_head_pairs``, qwen2-1.5b)
    is read as ``(P, ps * nkv, d)``, the same bytes (a bitcast in XLA),
    left in HBM (``memory_space=ANY``): each program DMAs the live pages
    of a run of ``ppb`` columns, the most whose K and V fit
    ``PAGE_VMEM_BYTES``, and each page yields every head's ``(ps, d)``
    tile from one pass over its 32-bit words. Any other pool (8 KV heads
    of dk 64, int8, fp8) keeps ``ppb = 1``: its page, all heads, arrives
    through the BlockSpec index map and is sliced per head. A manual DMA
    of such a page would slice dims that are not tile-aligned, which
    Mosaic refuses, and an aligned view costs XLA a whole-pool relayout
    per call.

    Every grid dim stays ``parallel``: page partials are independent (no
    running max, no denominator), one fp32 partial per (slot, head,
    column) in a ``(b, nkv, npg, g, d)`` buffer, and they combine by the
    same caller-side fp32 addition as the contiguous kernel. With
    ``fill_bound`` (default) the run axis covers the traced batch-max live
    page count, and per-slot dead columns (unmapped entries, pages past
    the fill, pages behind the window) are not computed and, when the
    program fetches its own pages, not fetched; ``fill_bound=False``
    computes every table column (the pre-fill-bounding baseline). Unmapped
    entries contribute exact zeros either way.
    """
    b, nh, d = q.shape
    P, ps, nkv = kp.shape[0], kp.shape[1], kp.shape[2]
    g = nh // nkv
    npg = page_table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    tab = page_table.astype(jnp.int32)
    len1 = lengths.astype(jnp.int32)
    npg_live = (CL.live_blocks(jnp.max(len1), ps, npg) if fill_bound
                else npg)
    pools = [kp, vp] + ([k_scale, v_scale] if k_scale is not None else [])
    packed = len(pools) == 2 and CL.packs_head_pairs(kp.dtype, nkv, d)
    if packed:
        pools = [x.reshape(P, ps * nkv, d) for x in pools]
        ppb = _pages_per_program(npg, 2 * CL.tiled_bytes((ps * nkv, d),
                                                         kp.dtype))
        pool_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        scratch = ([pltpu.VMEM((ppb, ps * nkv, d), kp.dtype)] * 2
                   + [pltpu.SemaphoreType.DMA((2, ppb))])
    else:
        ppb, scratch = 1, []

        def page_map(ib, jb, tab_ref, len_ref):
            return (jnp.maximum(tab_ref[ib, jb], 0), 0, 0, 0)

        pool_specs = [pl.BlockSpec((1, *x.shape[1:]),
                                   lambda *a, n=x.ndim: page_map(*a)[:n])
                      for x in pools]

    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               softcap=softcap, ps=ps, ppb=ppb, npg=npg,
                               n_pools=len(pools), packed=packed, g=g,
                               merged=merged, bounded=fill_bound)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # page table + lengths
        grid=(b, (npg_live + ppb - 1) // ppb),
        in_specs=[smem, smem,                                   # beta, gamma
                  pl.BlockSpec((1, nkv, g, d),
                               lambda ib, jb, *_: (ib, 0, 0, 0)),
                  *pool_specs],
        out_specs=pl.BlockSpec((1, nkv, ppb, g, d),
                               lambda ib, jb, *_: (ib, 0, jb, 0, 0)),
        scratch_shapes=scratch,
    )
    partials = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, npg, g, d), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(tab, len1, beta.astype(jnp.float32), gamma.astype(jnp.float32),
      q.reshape(b, nkv, g, d), *pools)

    out = CL.fill_bounded_sum(partials, npg_live)    # the sync-free combine
    return out.reshape(b, nh, d).astype(q.dtype)
