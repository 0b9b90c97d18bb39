"""MLP variants and residual block assembly (pre-norm, optional gemma2
sandwich post-norms). Block kinds:

  attn / global / local  : attention + dense MLP
  attn_moe / global_moe / local_moe : attention of that kind + MoE
  mamba / mamba_moe      : Mamba SSM block (+ MoE instead of the implicit MLP)
  mlstm / slstm          : xLSTM cells (self-contained, no separate MLP)
  any kind with cfg.cross_attn: adds a cross-attention sub-block (musicgen)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import attention as ATT
from repro.distributed.sharding import ep_info, shard
from repro.models import mamba as MB
from repro.models import moe as MOE
from repro.models import moe_ep as MOE_EP
from repro.models import xlstm as XL
from repro.nn import layers as L


ATTN_KINDS = ("attn", "attn_moe", "global", "local", "global_moe",
              "local_moe")
ATTN_MOE_KINDS = ("attn_moe", "global_moe", "local_moe")


def _moe_apply(p, h, cfg, **kw):
    """Dispatch to explicit expert-parallel all-to-all MoE when the sharding
    context requests it (and the expert count divides the axis)."""
    mesh, axis, n = ep_info()
    if mesh is not None and n and cfg.moe.n_experts % n == 0:
        return MOE_EP.moe_apply_ep(p, h, cfg, mesh, axis)
    return MOE.moe_apply(p, h, cfg, **kw)


# -------------------------------------------------------------------- mlp ----
def mlp_init(ctx, name, cfg: ModelConfig, d_ff=None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    pdt = cfg.pdtype()
    with ctx.scope(name):
        if cfg.mlp in ("silu_glu", "gelu_glu"):
            return {
                "gate": L.linear_init(ctx, "gate", d, ff, dtype=pdt,
                                      axes=("embed", "mlp")),
                "up": L.linear_init(ctx, "up", d, ff, dtype=pdt,
                                    axes=("embed", "mlp")),
                "down": L.linear_init(ctx, "down", ff, d, dtype=pdt,
                                      axes=("mlp", "embed")),
            }
        return {
            "up": L.linear_init(ctx, "up", d, ff, dtype=pdt,
                                axes=("embed", "mlp")),
            "down": L.linear_init(ctx, "down", ff, d, dtype=pdt,
                                  axes=("mlp", "embed")),
        }


def mlp_apply(p, x, cfg: ModelConfig):
    cdt = cfg.cdtype()
    if cfg.mlp in ("silu_glu", "gelu_glu"):
        act = jax.nn.silu if cfg.mlp == "silu_glu" else (
            lambda v: jax.nn.gelu(v, approximate=True))
        h = act(L.linear(p["gate"], x, dtype=cdt)) * L.linear(p["up"], x, dtype=cdt)
    else:
        h = jax.nn.gelu(L.linear(p["up"], x, dtype=cdt), approximate=True)
    h = shard(h, "act_batch,act_seq,act_mlp")
    return L.linear(p["down"], h, dtype=cdt)


# ------------------------------------------------------------------ block ----
def _is_attn(kind):
    return kind in ATTN_KINDS


def block_init(ctx, name, cfg: ModelConfig, kind: str):
    pdt = cfg.pdtype()
    d = cfg.d_model
    with ctx.scope(name):
        p = {}
        if _is_attn(kind):
            p["attn_norm"] = L.norm_init(ctx, "attn_norm", d, kind=cfg.norm,
                                         dtype=pdt)
            p["attn"] = ATT.attention_init(ctx, "attn", cfg)
            if cfg.post_block_norm:
                p["attn_post_norm"] = L.norm_init(ctx, "attn_post_norm", d,
                                                  kind=cfg.norm, dtype=pdt)
            if cfg.cross_attn:
                p["xattn_norm"] = L.norm_init(ctx, "xattn_norm", d,
                                              kind=cfg.norm, dtype=pdt)
                p["xattn"] = ATT.attention_init(ctx, "xattn", cfg, cross=True)
            p["mlp_norm"] = L.norm_init(ctx, "mlp_norm", d, kind=cfg.norm,
                                        dtype=pdt)
            if kind in ATTN_MOE_KINDS:
                p["moe"] = MOE.moe_init(ctx, "moe", cfg)
            else:
                p["mlp"] = mlp_init(ctx, "mlp", cfg)
            if cfg.post_block_norm:
                p["mlp_post_norm"] = L.norm_init(ctx, "mlp_post_norm", d,
                                                 kind=cfg.norm, dtype=pdt)
        elif kind in ("mamba", "mamba_moe"):
            p["mamba_norm"] = L.norm_init(ctx, "mamba_norm", d, kind=cfg.norm,
                                          dtype=pdt)
            p["mamba"] = MB.mamba_init(ctx, "mamba", cfg)
            if kind == "mamba_moe":
                p["moe_norm"] = L.norm_init(ctx, "moe_norm", d, kind=cfg.norm,
                                            dtype=pdt)
                p["moe"] = MOE.moe_init(ctx, "moe", cfg)
        elif kind == "mlstm":
            p["norm"] = L.norm_init(ctx, "norm", d, kind=cfg.norm, dtype=pdt)
            p["mlstm"] = XL.mlstm_init(ctx, "mlstm", cfg)
        elif kind == "slstm":
            p["norm"] = L.norm_init(ctx, "norm", d, kind=cfg.norm, dtype=pdt)
            p["slstm"] = XL.slstm_init(ctx, "slstm", cfg)
            # xLSTM sLSTM blocks carry a 4/3-factor GLU FFN after the cell
            ffs = -(-(4 * d) // (3 * 64)) * 64
            p["mlp_norm"] = L.norm_init(ctx, "mlp_norm", d, kind=cfg.norm,
                                        dtype=pdt)
            p["mlp"] = mlp_init(ctx, "mlp", cfg, d_ff=ffs)
        else:
            raise ValueError(f"unknown block kind {kind!r}")
    return p


def block_apply(p, x, cfg: ModelConfig, kind: str, *, positions=None,
                cache=None, cond=None, merged=False, q_chunk=2048,
                kv_chunk=1024, decode_kernel=False, decode_kv_block=256,
                prefill_kernel=False, prefill_kv_block=512, fill_bound=True,
                prefill_append=None, decode_active=None, page_table=None,
                layer=None, psum_axes=(), moe_counts=False):
    """Returns (x, new_cache, aux_losses, counts): ``counts`` is the
    dropless expert layer's (rows routed, experts hit), (2,) int32, with
    ``moe_counts`` on an expert layer, else None. An expert layer routes
    only the real rows of a prefill chunk and the active decode slots.
    With ``page_table``, ``cache`` holds every layer's paged pools,
    stacked, and the block works on layer ``layer`` of them."""
    aux = jnp.zeros((), jnp.float32)
    counts = None
    new_cache = cache

    if _is_attn(kind):
        akind = "local" if kind in ("local", "local_moe") else "global"
        with jax.named_scope("attn_proj"):
            h = L.norm_apply(p["attn_norm"], x, kind=cfg.norm)
        attn_cache = cache.get("attn") if cache is not None else None
        h, attn_cache = ATT.attention_apply(
            p["attn"], h, cfg, kind=akind, positions=positions,
            cache=attn_cache, merged=merged, q_chunk=q_chunk,
            kv_chunk=kv_chunk, decode_kernel=decode_kernel,
            decode_kv_block=decode_kv_block, prefill_kernel=prefill_kernel,
            prefill_kv_block=prefill_kv_block, fill_bound=fill_bound,
            prefill_append=prefill_append,
            decode_active=decode_active, page_table=page_table,
            layer=layer, psum_axes=psum_axes)
        if cfg.post_block_norm:
            h = L.norm_apply(p["attn_post_norm"], h, kind=cfg.norm)
        x = x + h
        if cfg.cross_attn and cond is not None:
            h = L.norm_apply(p["xattn_norm"], x, kind=cfg.norm)
            # cross-attn: decode passes a dummy cache dict for index handling
            xc = {"index": cache["attn"]["index"] - 1} if (
                cache is not None) else None
            h, _ = ATT.attention_apply(p["xattn"], h, cfg, cond=cond,
                                       cache=xc, merged=merged)
            x = x + h
        with jax.named_scope("mlp"):
            h = L.norm_apply(p["mlp_norm"], x, kind=cfg.norm)
            if kind in ATTN_MOE_KINDS:
                rows = None
                if prefill_append is not None:
                    rows = (jnp.arange(x.shape[1])[None, :]
                            < prefill_append[:, None])
                elif decode_active is not None:
                    rows = decode_active[:, None]
                with jax.named_scope("moe"):
                    h, aux, *c = _moe_apply(p["moe"], h, cfg, row_mask=rows,
                                            counts=moe_counts)
                counts = c[0] if c else None
            else:
                h = mlp_apply(p["mlp"], h, cfg)
            if cfg.post_block_norm:
                h = L.norm_apply(p["mlp_post_norm"], h, kind=cfg.norm)
        x = x + h
        if cache is not None:
            new_cache = dict(cache, attn=attn_cache)
    elif kind in ("mamba", "mamba_moe"):
        h = L.norm_apply(p["mamba_norm"], x, kind=cfg.norm)
        mcache = cache.get("mamba") if cache is not None else None
        h, mcache = MB.mamba_apply(p["mamba"], h, cfg, cache=mcache)
        x = x + h
        if kind == "mamba_moe":
            h = L.norm_apply(p["moe_norm"], x, kind=cfg.norm)
            with jax.named_scope("moe"):
                h, aux = _moe_apply(p["moe"], h, cfg)
            x = x + h
        if cache is not None:
            new_cache = dict(cache, mamba=mcache)
    elif kind == "mlstm":
        h = L.norm_apply(p["norm"], x, kind=cfg.norm)
        mc = cache.get("mlstm") if cache is not None else None
        h, mc = XL.mlstm_apply(p["mlstm"], h, cfg, cache=mc)
        x = x + h
        if cache is not None:
            new_cache = dict(cache, mlstm=mc)
    elif kind == "slstm":
        h = L.norm_apply(p["norm"], x, kind=cfg.norm)
        sc = cache.get("slstm") if cache is not None else None
        h, sc = XL.slstm_apply(p["slstm"], h, cfg, cache=sc)
        x = x + h
        h = L.norm_apply(p["mlp_norm"], x, kind=cfg.norm)
        x = x + mlp_apply(p["mlp"], h, cfg)
        if cache is not None:
            new_cache = dict(cache, slstm=sc)
    x = shard(x, "act_batch,act_seq,act_embed")
    return x, new_cache, aux, counts
