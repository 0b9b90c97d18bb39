"""A plain float32 forward of Mellum2-12B-A2.5B for the tests, on the
program's params pytree: ``jax.numpy`` at ``jax.default_matmul_precision
("highest")``, one sequence at a time, with no kernels, cache, batching or
expert capacity.

What it computes, from the published configuration:

* RMSNorm (eps 1e-6) before attention and before the expert layer, and
  after the last layer;
* grouped-query attention, causal; layers of kind ``local_moe`` see only
  the last ``window`` rows (sliding), ``global_moe`` layers all of them;
* rotary positions on q and k, pairs (i, i + dk/2): plain RoPE at
  ``rope_theta`` on sliding layers, YaRN on full layers (``yarn_inv_freq``
  below, written out from transformers' ``_compute_yarn_parameters`` with
  ``truncate`` true; cos and sin times ``attention_factor``);
* the expert layer: a float32 softmax router over every expert, the top-k
  experts of each token, their weights renormalized to sum to one
  (``norm_topk_prob``), each a SiLU-gated MLP; every token reaches all k of
  its experts (no capacity, nothing dropped), no shared expert;
* an untied output head, ``lm_head``.

Departures from the published model, as in the program: attention scores
are normalized by ConSmax, exp(s - beta) / gamma per head with no max and
no sum (the repository's technique), in place of softmax; RMSNorm gains are
stored zero-centred (gain 1 + scale).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN's (dim/2,) rotary frequencies: inv_interp * (1 - e) + inv_extrap
    * e, inv_extrap = theta^(-2i/dim), inv_interp = inv_extrap / factor,
    e = 1 - clamp((i - low) / (high - low), 0, 1), with low and high the
    floor and ceiling of the dimensions at which beta_fast and beta_slow
    rotations fit the original context."""
    def corr(rot):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    extrap = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    i = np.arange(dim // 2)
    e = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return extrap / yarn.factor * (1.0 - e) + extrap * e


def _rms(x, scale, eps=1e-6):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, inv_freq, mscale):
    """x: (t, heads, dk)."""
    dk = x.shape[-1]
    ang = jnp.arange(x.shape[0])[:, None] * jnp.asarray(inv_freq,
                                                         jnp.float32)
    c = jnp.cos(ang)[:, None] * mscale
    s = jnp.sin(ang)[:, None] * mscale
    x1, x2 = x[..., :dk // 2], x[..., dk // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _experts(m, x, top_k):
    """Dropless mixture over x (t, d): every expert computed for every row,
    each row's output the weighted sum of its top-k."""
    probs = jax.nn.softmax(x @ m["router"], axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / w.sum(-1, keepdims=True)
    h = (jax.nn.silu(jnp.einsum("td,edf->etf", x, m["gate"]))
         * jnp.einsum("td,edf->etf", x, m["up"]))
    every = jnp.einsum("etf,efd->ted", h, m["down"])          # (t, E, d)
    chosen = jnp.take_along_axis(every, idx[..., None], axis=1)
    return (chosen * w[..., None]).sum(axis=1)


def _layer(cfg, kind, lp, x):
    a = lp["attn"]
    t = x.shape[0]
    dk, hkv = cfg.head_dim_, cfg.n_kv_heads
    g = cfg.n_heads // hkv
    h = _rms(x, lp["attn_norm"]["scale"])
    q = jnp.einsum("td,dhk->thk", h, a["q"]["w"])
    k = jnp.einsum("td,dhk->thk", h, a["k"]["w"])
    v = jnp.einsum("td,dhk->thk", h, a["v"]["w"])
    inv = 1.0 / cfg.rope_theta ** (np.arange(0, dk, 2) / dk)
    mscale = 1.0
    if kind == "global_moe" and cfg.rope_yarn is not None:
        inv = yarn_inv_freq(dk, cfg.rope_theta, cfg.rope_yarn)
        mscale = cfg.rope_yarn.attention_factor
    q = _rope(q, inv, mscale) / math.sqrt(dk)
    k = _rope(k, inv, mscale)
    s = jnp.einsum("qhgd,khd->hgqk", q.reshape(t, hkv, g, dk), k)
    qpos, kpos = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = kpos <= qpos
    if kind == "local_moe":
        mask &= qpos - kpos < cfg.window
    beta = a["score_norm"]["beta"].reshape(hkv, g, 1, 1)
    gamma = a["score_norm"]["gamma"].reshape(hkv, g, 1, 1)
    p = jnp.where(mask, jnp.exp(s - beta) / gamma, 0.0)       # ConSmax
    o = jnp.einsum("hgqk,khd->qhgd", p, v).reshape(t, cfg.n_heads, dk)
    x = x + jnp.einsum("thk,hkd->td", o, a["o"]["w"])
    h = _rms(x, lp["mlp_norm"]["scale"])
    return x + _experts(lp["moe"], h, cfg.moe.top_k)


def logits(params, cfg, tokens):
    """(t, vocab) float32 logits of one sequence of token ids."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    period = len(cfg.block_pattern)
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["table"][jnp.asarray(tokens)]
        for layer in range(cfg.n_layers):
            i = layer % period
            lp = jax.tree.map(lambda a: a[layer // period],
                              p["blocks"][f"b{i}"])
            x = _layer(cfg, cfg.block_pattern[i], lp, x)
        x = _rms(x, p["final_norm"]["scale"])
        return x @ p["lm_head"]["table"].T
