"""Top-k MoE: sort-based capacity dispatch, or dropless grouped matmuls.

Capacity dispatch (``capacity_factor > 0``): FLOPs-faithful, every token
passes through at most its top-k experts (plus capacity_factor padding), via
gather -> (E, C, d) buffers -> batched expert GLU -> scatter-back, per
sequence row; rows over an expert's capacity are dropped. Tokens stay local
to their data shard (expert weights are TP-sharded on their hidden dim over
`model`), so the dispatch needs **no all-to-all** — this is the "expert
slicing" layout; see DESIGN.md §4.

Dropless (``capacity_factor == 0``): the call's b·s·k token-expert rows are
sorted by expert and the three expert matmuls run as grouped matmuls over
the groups (the Pallas megablox ``gmm``, interpreted on a CPU), so no row is
dropped and a token's output does not depend on the rest of its call. Rows
masked out by ``row_mask`` (pad rows of a prefill chunk, idle decode slots)
sort past every group and are not computed.

Router normalizer is pluggable: "softmax" (faithful) or "consmax" (beyond-
paper extension — learnable beta/gamma over router logits; top-k selection is
order-preserving under the monotone map, only mixture weights change and are
left non-unit, matching the paper's non-unit-probability tolerance).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.configs.base import ModelConfig
from repro.nn import module as nn


def moe_init(ctx, name, cfg: ModelConfig):
    m = cfg.moe
    d, ff, E = cfg.d_model, m.d_ff_expert or cfg.d_ff, m.n_experts
    pdt = cfg.pdtype()
    with ctx.scope(name):
        p = {
            "router": ctx.param("router", (d, E), jnp.float32,
                                nn.fan_in_normal(), ("embed", "experts")),
            "gate": ctx.param("gate", (E, d, ff), pdt,
                              nn.fan_in_normal(axis=1),
                              ("experts", "embed", "mlp")),
            "up": ctx.param("up", (E, d, ff), pdt, nn.fan_in_normal(axis=1),
                            ("experts", "embed", "mlp")),
            "down": ctx.param("down", (E, ff, d), pdt,
                              nn.fan_in_normal(axis=1),
                              ("experts", "mlp", "embed")),
        }
        if m.router_norm == "consmax":
            p["beta"] = ctx.param("beta", (), jnp.float32,
                                  nn.constant(0.0), ())
            p["gamma"] = ctx.param("gamma", (), jnp.float32,
                                   nn.constant(float(E)), ())
    return p


def _capacity(s: int, k: int, E: int, cf: float) -> int:
    c = int(s * k * cf / E)
    c = max(8, -(-c // 8) * 8)           # round up to multiple of 8
    return min(c, s * k)


def _dispatch_row(x, idx, w, p, cfg: ModelConfig, C: int, act):
    """x: (s, d); idx, w: (s, k). Sort-based dispatch for one sequence row."""
    s, d = x.shape
    k = idx.shape[1]
    E = cfg.moe.n_experts
    cdt = cfg.cdtype()

    slot_e = idx.reshape(s * k)                     # expert of each slot
    token = jnp.arange(s * k) // k
    order = jnp.argsort(slot_e, stable=True)
    se = slot_e[order]
    tok_s = token[order]
    oh = jax.nn.one_hot(se, E, dtype=jnp.int32)     # (s*k, E)
    pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1, se[:, None],
                              axis=1)[:, 0]         # rank within expert
    keep = pos < C
    bidx = jnp.where(keep, se * C + pos, E * C)     # OOB -> dropped

    xs = x[tok_s].astype(cdt)
    buf = jnp.zeros((E * C, d), cdt).at[bidx].set(xs, mode="drop")
    buf = buf.reshape(E, C, d)

    h = act(jnp.einsum("ecd,edf->ecf", buf, p["gate"].astype(cdt))) * \
        jnp.einsum("ecd,edf->ecf", buf, p["up"].astype(cdt))
    out = jnp.einsum("ecf,efd->ecd", h, p["down"].astype(cdt))
    out = out.reshape(E * C, d)

    ys = out[jnp.minimum(bidx, E * C - 1)] * keep[:, None].astype(cdt)
    y_slots = ys[jnp.argsort(order)]        # inverse-permutation gather
    y = (y_slots.reshape(s, k, d) *
         w.astype(cdt)[..., None]).sum(axis=1)
    return y


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _tile(dim: int, limit: int) -> int:
    """The largest tile of ``dim`` that divides it, is all of it or a
    multiple of 128 lanes, and is at most ``limit`` (else the smallest)."""
    fits = [t for t in range(dim, 0, -1)
            if dim % t == 0 and (t == dim or t % 128 == 0)]
    return next((t for t in fits if t <= limit), fits[-1])


def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(tm, tk, tn) for one grouped matmul of ``m`` sorted rows and (k, n)
    weights per expert. Few rows per expert (decode) take short row tiles,
    so that each expert's weights stream about once; a weight block takes
    up to 2M elements (4 MiB in bfloat16), so grid steps stay few."""
    tm = min(32 if m <= 1024 else 128, m)
    tn = _tile(n, 2304)
    return tm, _tile(k, (1 << 21) // tn), tn


def grouped_matmul(x, w, sizes):
    """x: (m, k) rows sorted by group; w: (G, k, n); sizes: (G,) int32 rows
    per group, summing to at most m (later rows belong to no group and
    their output rows are undefined). Float32 output (m, n)."""
    m, k = x.shape
    tm = _gmm_tiling(m, k, w.shape[-1])[0]
    pad = -m % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = megablox.gmm(x, w, sizes, jnp.float32, _gmm_tiling, None, None,
                       False, _on_cpu())
    return out[:m]


def _dropless(x, idx, w, p, cfg: ModelConfig, act, row_mask):
    """x: (b, s, d); idx, w: (b, s, k); row_mask: (b, s) bool or None.
    Returns (y (b, s, d), counts (2,) int32: routed rows, experts hit)."""
    b, s, d = x.shape
    k = idx.shape[-1]
    E = cfg.moe.n_experts
    cdt = cfg.cdtype()
    n = b * s
    expert = idx.reshape(n * k)
    live = None
    if row_mask is not None:
        live = jnp.repeat(row_mask.reshape(n), k)
        expert = jnp.where(live, expert, E)        # past every group
    order = jnp.argsort(expert, stable=True)
    sizes = jnp.bincount(expert, length=E + 1)[:E].astype(jnp.int32)
    rows = x.reshape(n, d)[order // k].astype(cdt)
    with jax.named_scope("moe_experts"):
        h = (act(grouped_matmul(rows, p["gate"].astype(cdt), sizes))
             * grouped_matmul(rows, p["up"].astype(cdt), sizes))
        out = grouped_matmul(h.astype(cdt), p["down"].astype(cdt), sizes)
    y = out[jnp.argsort(order)] * w.reshape(n * k, 1)
    if live is not None:                           # undefined rows: select
        y = jnp.where(live[:, None], y, 0.0)
    y = y.reshape(n, k, d).sum(axis=1).astype(cdt).reshape(b, s, d)
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)]).astype(
        jnp.int32)
    return y, counts


def moe_apply(p, x, cfg: ModelConfig, *, row_mask=None, counts=False):
    """x: (b, s, d) -> (y, aux_loss), and with ``counts`` a third output,
    (2,) int32: rows routed to experts and experts with at least one row
    (dropless only). ``row_mask`` (b, s) bool: rows to route (dropless
    only; None routes every row)."""
    m = cfg.moe
    b, s, d = x.shape
    E, k = m.n_experts, m.top_k
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)

    if m.router_norm == "consmax":
        probs = jnp.exp(logits - p["beta"]) / p["gamma"]
        w, idx = jax.lax.top_k(probs, k)            # non-unit weights kept
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux (Switch-style), always measured on normalized probs
    probs_n = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    me = jnp.mean(probs_n, axis=(0, 1))             # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    aux = m.aux_loss_weight * E * jnp.sum(me * ce)

    act = jax.nn.silu if cfg.mlp == "silu_glu" else (
        lambda v: jax.nn.gelu(v, approximate=True))
    if m.dropless:
        y, n = _dropless(x, idx, w, p, cfg, act, row_mask)
        return (y, aux, n) if counts else (y, aux)
    if counts:
        raise ValueError("moe_apply: counts are kept by the dropless path "
                         "only (capacity_factor=0)")
    C = _capacity(s, k, E, m.capacity_factor)
    y = jax.vmap(partial(_dispatch_row, p=p, cfg=cfg, C=C, act=act))(
        x, idx, w)
    return y, aux
