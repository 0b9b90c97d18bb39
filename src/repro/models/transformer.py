"""Decoder LM assembled from the block zoo, with scan-over-super-layers.

The layer stack is grouped into ``n_super = n_layers / len(block_pattern)``
homogeneous super-layers; the pattern entries are unrolled inside one
super-layer and the stack is a single ``lax.scan`` — HLO size (and compile
time) is independent of depth, which is what makes 64-72 layer dry-runs cheap.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.kernels import cache_layout as CL
from repro.models import blocks as B
from repro.models import frontends as F
from repro.models import mamba as MB
from repro.models import xlstm as XL
from repro.nn import layers as L
from repro.nn import module as nn


def _super_init(ctx, cfg: ModelConfig):
    return {f"b{i}": B.block_init(ctx, f"b{i}", cfg, kind)
            for i, kind in enumerate(cfg.block_pattern)}


def lm_init(ctx: nn.Ctx, cfg: ModelConfig):
    pdt = cfg.pdtype()
    if ctx.mode == "axes":
        blocks = nn.stack_axes(nn.axes_of(_super_init, cfg))
    else:
        blocks = nn.vmap_init(_super_init, cfg.n_super_layers,
                              ctx.fold("blocks"), cfg)
    p = {
        "embed": L.embedding_init(ctx, "embed", cfg.vocab_size, cfg.d_model,
                                  dtype=pdt),
        "blocks": blocks,
        "final_norm": L.norm_init(ctx, "final_norm", cfg.d_model,
                                  kind=cfg.norm, dtype=pdt),
    }
    if not cfg.tie_embeddings:
        # untied LM head: its own (vocab, d) table, read by ``unembed``
        p["lm_head"] = L.embedding_init(ctx, "lm_head", cfg.vocab_size,
                                        cfg.d_model, dtype=pdt)
    return p


def lm_apply(p, cfg: ModelConfig, *, tokens=None, embeds=None, cond=None,
             caches=None, positions=None, merged=False, remat="full",
             q_chunk=2048, kv_chunk=1024, logits_slice=None,
             logits_index=None, decode_kernel=False, decode_kv_block=256,
             prefill_kernel=False, prefill_kv_block=512, fill_bound=True,
             prefill_append=None, decode_active=None, page_table=None,
             logits_epilogue=None, psum_axes=(), moe_counts=False):
    """Forward pass.

    tokens: (b, s) int ids (token frontend) | embeds: (b, s, d) stub frontends.
    caches: per-super-layer pytree with leading dim n_super (decode), or None.
    Contiguous caches are scanned over with the parameters, one layer's
    slice in and out per step; paged pools (``page_table``) are carried
    through the scan whole with the layer index, and each layer writes
    and reads its own pool in place.
    logits_index: traced position — unembed only that row (serving prefill on
    a padded prompt, where the last real token is mid-sequence). A scalar
    selects one row for the whole batch; a (b,) array gathers per-batch rows
    (ragged prompts prefilled together).
    decode_kernel: one-token consmax decode via the split-KV Pallas kernel.
    prefill_kernel: chunked consmax append prefill via the fused Pallas
    kernel (kernels/consmax_prefill) instead of the jnp KV walk.
    fill_bound: bound the serving kernels' KV grids by the traced fill
    (cache ``index``) instead of cache capacity; fill stays a value, so
    no extra compiled shape. False = capacity-swept A/B baseline.
    prefill_append: (b,) int32 real chunk lengths — chunked append-at-index
    prefill: tokens is a fixed-size chunk written into each attention cache
    at its per-slot ``index`` (which then advances by the real length).
    decode_active: (b,) bool — one-token decode: slots where False keep
    cache rows and index untouched (shared decode step over a slot pool).
    page_table: (b, max_pages) int32 — paged KV serving: attention caches
    are shared page pools (see init_paged_caches) and each slot's logical
    rows live on the pages its table row maps.
    psum_axes: mesh axis names for sharded serving under shard_map — each
    attention block all-reduces its per-shard ConSmax output partial over
    these axes (see attention_apply); everything outside attention runs
    replicated, so logits (and fused sampling) are identical on every
    device. Empty = single-device.
    logits_epilogue: callable ``(logits, new_caches) -> out`` fused into
    the same computation in place of the logits return — the serving hook
    (serve/sampling.sample_tokens) that turns the jitted prefill/decode
    steps into token emitters, so no (b, vocab) array ever crosses to the
    host. ``new_caches`` is passed so the epilogue can read the post-step
    per-slot cache index (its per-slot sample positions).
    moe_counts: also return the dropless expert layers' counts, (2,) int32
    summed over layers: rows routed to experts and (layer, expert) pairs
    with at least one row.
    Returns (logits | epilogue out, new_caches, aux_loss), and the counts
    last with ``moe_counts``.
    """
    b, s = (tokens.shape if tokens is not None else embeds.shape[:2])
    if positions is None and caches is None:
        positions = jnp.arange(s)[None, :]
    elif positions is None and prefill_append is not None:
        idx = cache_index(caches)                      # per-slot fill level
        positions = idx[:, None] + jnp.arange(s)[None, :]
    # decode: caller passes positions (= cache index) for rope/sinusoidal

    # named scopes (``embed``, ``layers``, ``unembed_sample``, and inside
    # the blocks ``attn_proj``, ``kv_cache``, ``attn``, ``mlp``, ``moe``,
    # ``moe_experts``) live only in the HLO's op_name metadata, where a
    # device trace reads which part of the model each op belongs to
    with jax.named_scope("embed"):
        x = F.frontend_apply(p, cfg, tokens=tokens, embeds=embeds,
                             positions=positions)
        x = shard(x, "act_batch,act_seq,act_embed")

    def super_step(x, bp, cache_in, layer=None):
        new_caches = {} if cache_in is not None else None
        aux = jnp.zeros((), jnp.float32)
        counts = jnp.zeros((2,), jnp.int32) if moe_counts else None
        for i, kind in enumerate(cfg.block_pattern):
            ci = cache_in[f"b{i}"] if cache_in is not None else None
            x, co, a, n = B.block_apply(
                bp[f"b{i}"], x, cfg, kind, positions=positions, cache=ci,
                cond=cond, merged=merged, q_chunk=q_chunk, kv_chunk=kv_chunk,
                decode_kernel=decode_kernel, decode_kv_block=decode_kv_block,
                prefill_kernel=prefill_kernel,
                prefill_kv_block=prefill_kv_block, fill_bound=fill_bound,
                prefill_append=prefill_append, decode_active=decode_active,
                page_table=page_table, layer=layer, psum_axes=psum_axes,
                moe_counts=moe_counts)
            aux = aux + a
            if n is not None:
                counts = counts + n
            if cache_in is not None:
                new_caches[f"b{i}"] = co
        return x, new_caches, (aux, counts)

    if caches is None:
        def body(x, bp):
            y, _, stats = super_step(x, bp, None)
            return y, stats
        if remat == "full":
            body = jax.checkpoint(body)
        elif remat == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        with jax.named_scope("layers"):
            x, (auxs, counts) = jax.lax.scan(body, x, p["blocks"])
        new_caches = None
    elif page_table is not None:
        # paged pools ride the carry whole, beside the layer index: each
        # layer scatters its new rows into, and the kernels read, its own
        # pool at [l] in place, so no step slices, re-stacks or copies a
        # pool
        def body(carry, bp):
            x, l, c = carry
            y, c, stats = super_step(x, bp, c, l)
            return (y, l + 1, c), stats
        with jax.named_scope("layers"):
            (x, _, new_caches), (auxs, counts) = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.int32), caches), p["blocks"])
    else:
        def body(x, xs):
            bp, ci = xs
            y, co, stats = super_step(x, bp, ci)
            return y, (co, stats)
        with jax.named_scope("layers"):
            x, (new_caches, (auxs, counts)) = jax.lax.scan(
                body, x, (p["blocks"], caches))
    aux = jnp.sum(auxs)
    more = (jnp.sum(counts, axis=0),) if moe_counts else ()

    with jax.named_scope("unembed_sample"):
        x = L.norm_apply(p["final_norm"], x, kind=cfg.norm)
        if logits_index is not None:
            li = jnp.asarray(logits_index)
            if li.ndim == 0:
                x = jax.lax.dynamic_slice_in_dim(x, li, 1, axis=1)
            else:                              # (b,) per-batch row gather
                x = jnp.take_along_axis(x, li[:, None, None], axis=1)
        elif logits_slice is not None:
            x = x[:, logits_slice]
        logits = L.unembed(p.get("lm_head", p["embed"]), x,
                           dtype=cfg.cdtype())
        if cfg.final_softcap > 0:
            logits = (cfg.final_softcap * jnp.tanh(
                logits.astype(jnp.float32) / cfg.final_softcap))
        logits = shard(logits, "act_batch,act_seq,act_vocab")
        if logits_epilogue is not None:
            logits = logits_epilogue(logits, new_caches)
        return (logits, new_caches, aux) + more


# --------------------------------------------------------------- caches ----
def init_caches(cfg: ModelConfig, batch: int, max_seq: int,
                kv_dtype=jnp.bfloat16):
    """Per-super-layer decode caches, stacked on a leading n_super dim.

    ``kv_dtype`` accepts a dtype or a name from cache_layout.KV_DTYPES
    ("bfloat16" / "int8" / "fp8_e4m3"). Quantized dtypes add per-row
    fp32 ``k_scale``/``v_scale`` leaves (batch, max_seq, hkv) beside the
    data — attention quantizes at write time and dequantizes per-block at
    read time; bf16 caches carry no scale leaves and are byte-identical
    to the pre-quantization layout."""
    kv_dtype = CL.kv_cache_dtype(kv_dtype)
    quant = CL.kv_quantized(kv_dtype)

    def one_super():
        c = {}
        for i, kind in enumerate(cfg.block_pattern):
            if kind in B.ATTN_KINDS:
                hkv, dk = cfg.n_kv_heads, cfg.head_dim_
                attn = {
                    "k": jnp.zeros((batch, max_seq, hkv, dk), kv_dtype),
                    "v": jnp.zeros((batch, max_seq, hkv, dk), kv_dtype),
                    "index": jnp.zeros((batch,), jnp.int32),
                }
                if quant:
                    attn["k_scale"] = jnp.ones((batch, max_seq, hkv),
                                               jnp.float32)
                    attn["v_scale"] = jnp.ones((batch, max_seq, hkv),
                                               jnp.float32)
                c[f"b{i}"] = {"attn": attn}
            elif kind in ("mamba", "mamba_moe"):
                c[f"b{i}"] = {"mamba": MB.mamba_cache_init(cfg, batch)}
            elif kind == "mlstm":
                c[f"b{i}"] = {"mlstm": XL.mlstm_cache_init(cfg, batch)}
            elif kind == "slstm":
                c[f"b{i}"] = {"slstm": XL.slstm_cache_init(cfg, batch)}
        return c

    one = one_super()
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_super_layers,) + a.shape).copy(),
        one)


def init_paged_caches(cfg: ModelConfig, batch: int, num_pages: int,
                      page_size: int, kv_dtype=jnp.bfloat16):
    """Paged decode caches: ONE shared (num_pages, page_size, hkv, dk) K/V
    pool per layer instead of a per-slot (batch, max_seq, ...) row block;
    the per-slot ``index`` vector keeps its contiguous semantics (fill
    level in *logical* rows). Which pool pages back which slot lives in the
    host-side page table (serve/scheduler.PagePool), passed to lm_apply as
    ``page_table`` — all layers fill in lockstep, so one table serves the
    whole stack. Attention-only: paged serving of recurrent state has no
    meaning (their cache is O(1) per slot already).

    Quantized ``kv_dtype`` (see init_caches) adds fp32 per-row scale pools
    (num_pages, page_size, hkv) that ride the same page table — a page copy
    (COW) or eviction moves data and scales together."""
    kv_dtype = CL.kv_cache_dtype(kv_dtype)
    quant = CL.kv_quantized(kv_dtype)

    def one_super():
        c = {}
        for i, kind in enumerate(cfg.block_pattern):
            if kind in B.ATTN_KINDS:
                hkv, dk = cfg.n_kv_heads, cfg.head_dim_
                attn = {
                    "k": jnp.zeros((num_pages, page_size, hkv, dk), kv_dtype),
                    "v": jnp.zeros((num_pages, page_size, hkv, dk), kv_dtype),
                    "index": jnp.zeros((batch,), jnp.int32),
                }
                if quant:
                    attn["k_scale"] = jnp.ones((num_pages, page_size, hkv),
                                               jnp.float32)
                    attn["v_scale"] = jnp.ones((num_pages, page_size, hkv),
                                               jnp.float32)
                c[f"b{i}"] = {"attn": attn}
            else:
                raise NotImplementedError(
                    f"paged KV caches cover attention blocks only "
                    f"(got {kind!r} in {cfg.block_pattern})")
        return c

    one = one_super()
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_super_layers,) + a.shape).copy(),
        one)


# ------------------------------------------------- cache slot utilities ----
# Continuous batching (serve/engine.py) treats the cache batch dim as a pool
# of independent slots: each slot holds one request at its own position. The
# attention caches already carry a per-slot ``index`` vector (b,), so ragged
# decode needs no padding tricks — masks and rope both read per-slot indices.

def _is_index(path) -> bool:
    return getattr(path[-1], "key", None) == "index"


def cache_index(caches):
    """Per-slot decode positions: (b,) int32 from the first attention cache's
    index leaf (all layers agree); None for attention-free archs."""
    leaves = [v for p, v in
              jax.tree_util.tree_flatten_with_path(caches)[0] if _is_index(p)]
    return leaves[0][0] if leaves else None  # strip layer-stack dim


def write_slot(caches, slot_caches, slot, length):
    """Scatter a batch-1 prefilled cache into slot ``slot`` of a batched
    cache. ``index`` leaves are set to ``length`` — the real prompt length,
    not the padded prefill length, so decode masking ignores pad rows.

    K/V leaves of ``slot_caches`` may carry a *shorter* seq axis than the
    slot (a prefill-bucket cache): only that prefix is written, and rows
    ``>= length`` are zeroed on the way in — a padded prefill computes
    pad-token K/V for those rows, and copying it would leave garbage keys
    in the slot (masked today, a live hazard for anything that later reads
    rows above ``index``, e.g. an append-at-index prefill chunk)."""
    def put(path, big, one):
        if _is_index(path):
            return big.at[:, slot].set(jnp.asarray(length, big.dtype))
        one = one[:, 0].astype(big.dtype)            # (n_super, ...)
        if getattr(path[-1], "key", None) in ("k", "v", "k_scale", "v_scale"):
            keep = jnp.arange(one.shape[1]) < length
            one = jnp.where(
                keep.reshape((1, -1) + (1,) * (one.ndim - 2)), one, 0)
        if one.shape == big.shape[:1] + big.shape[2:]:
            return big.at[:, slot].set(one)
        return big.at[:, slot, :one.shape[1]].set(one)
    return jax.tree_util.tree_map_with_path(put, caches, slot_caches)


def reset_slot(caches, slot):
    """Zero slot ``slot`` (index back to 0; k/v and recurrent state rows
    cleared) so a recycled slot cannot leak a previous request's context."""
    return jax.tree.map(lambda a: a.at[:, slot].set(jnp.zeros((), a.dtype)),
                        caches)


def reset_slot_paged(caches, slot):
    """Paged-cache recycle: only the per-slot ``index`` is slot-addressed —
    K/V pages go back to the host-side free list, and any stale rows a
    future owner inherits sit at kpos >= its kv_len, i.e. permanently
    masked (``reset_slot`` would instead zero pool page ``slot``, which
    belongs to whoever the allocator gave it to)."""
    return jax.tree_util.tree_map_with_path(
        lambda p, a: a.at[:, slot].set(0) if _is_index(p) else a, caches)


def set_slot_index(caches, slot, value):
    """Set slot ``slot``'s fill index to ``value`` across all layers. Warm
    prefix-cache admission needs this: the slot's page-table rows already
    point at cached pages holding ``value`` KV rows, so the device fill
    index must start past them for the first prefill chunk to append at
    the right position."""
    return jax.tree_util.tree_map_with_path(
        lambda p, a: a.at[:, slot].set(jnp.asarray(value, a.dtype))
        if _is_index(p) else a, caches)


def copy_kv_page(caches, src, dst):
    """Copy physical K/V page ``src`` onto page ``dst`` in every layer of a
    *paged* cache (page axis 1, after the layer stack); ``index`` leaves
    untouched. This is the device half of copy-on-write: the allocator
    (PagePool.ensure_writable / fork) picks the pages, the engine runs this
    before a slot writes into a page it no longer shares."""
    def cp(path, a):
        if _is_index(path):
            return a
        return a.at[:, dst].set(a[:, src])
    return jax.tree_util.tree_map_with_path(cp, caches)


def copy_kv_page_local(caches, src, dst, shard, pages_per_shard: int):
    """``copy_kv_page`` for a sequence-sharded pool, running per-shard
    inside shard_map: ``src``/``dst`` are *global* page ids; the shard
    owning them (the position-rigid allocator guarantees COW/fork copies
    never cross shards — replacement pages come from the same slot
    position's shard) rewrites its local slice, every other shard performs
    a no-op self-copy (same traced structure on all devices, no
    collectives). ``shard`` may be ``lax.axis_index``."""
    owned = (src // pages_per_shard == shard) & (dst // pages_per_shard == shard)
    src_l = jnp.where(owned, src - shard * pages_per_shard, 0)
    dst_l = jnp.where(owned, dst - shard * pages_per_shard, 0)

    def cp(path, a):
        if _is_index(path):
            return a
        page = jnp.where(owned, a[:, src_l], a[:, dst_l])
        return a.at[:, dst_l].set(page)
    return jax.tree_util.tree_map_with_path(cp, caches)


def cache_axes(cfg: ModelConfig, *, quantized: bool = False,
               paged: bool = False):
    """Logical axes tree matching init_caches (or, with ``paged=True``,
    init_paged_caches) output. ``quantized`` adds the k_scale/v_scale rows
    a quantized-kv cache tree carries — scale leaves share their row
    leaves' axis names minus the trailing dk axis, so any mesh rule that
    shards the rows shards the scales identically (a page's fp32 scales
    must live on the device holding its int8/fp8 codes). Paged pools name
    their page axis ``act_kv_pages`` — the axis sequence sharding spreads
    across the "seq" mesh devices."""
    def one_super():
        c = {}
        for i, kind in enumerate(cfg.block_pattern):
            if kind in B.ATTN_KINDS:
                if paged:
                    attn = {
                        "k": "layers,act_kv_pages,,act_kv_heads,",
                        "v": "layers,act_kv_pages,,act_kv_heads,",
                        "index": "layers,act_batch",
                    }
                    if quantized:
                        attn["k_scale"] = "layers,act_kv_pages,,act_kv_heads"
                        attn["v_scale"] = "layers,act_kv_pages,,act_kv_heads"
                    c[f"b{i}"] = {"attn": attn}
                    continue
                attn = {
                    "k": "layers,act_batch,act_kv_seq,act_kv_heads,",
                    "v": "layers,act_batch,act_kv_seq,act_kv_heads,",
                    "index": "layers,act_batch",
                }
                if quantized:
                    attn["k_scale"] = "layers,act_batch,act_kv_seq,act_kv_heads"
                    attn["v_scale"] = "layers,act_batch,act_kv_seq,act_kv_heads"
                c[f"b{i}"] = {"attn": attn}
            elif kind in ("mamba", "mamba_moe"):
                c[f"b{i}"] = {"mamba": {
                    "conv": "layers,act_batch,,act_mlp",
                    "h": "layers,act_batch,act_mlp,",
                }}
            elif kind == "mlstm":
                c[f"b{i}"] = {"mlstm": {
                    "conv": "layers,act_batch,,act_mlp",
                    "C": "layers,act_batch,act_heads,,",
                    "n": "layers,act_batch,act_heads,",
                    "m": "layers,act_batch,act_heads",
                }}
            elif kind == "slstm":
                c[f"b{i}"] = {"slstm": {
                    "h": "layers,act_batch,act_mlp",
                    "c": "layers,act_batch,act_mlp",
                    "n": "layers,act_batch,act_mlp",
                    "m": "layers,act_batch,act_mlp",
                }}
        return c
    return one_super()


def lm_axes(cfg: ModelConfig):
    return nn.axes_of(lm_init, cfg)


def lm_abstract(cfg: ModelConfig):
    return nn.abstract_init(lm_init, cfg)
