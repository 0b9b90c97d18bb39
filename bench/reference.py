"""The plain reference: the decoder's forward pass in float32, from the
params pytree alone.

It imports nothing of the program. It follows the published equations: a
pre-norm decoder with RMSNorm (gain stored zero-centred, 1 + scale),
rotary positions on q and k (half-split pairs, theta from the
configuration), grouped-query attention normalized by ConSmax (paper Eq. 2:
exp(s - beta) / gamma per head, no max and no sum), a SiLU-gated MLP, and a
tied unembedding. Every matrix product runs at float32 ``HIGHEST``
precision on the bfloat16 weights widened to float32.

``precision="fp8"`` is the control: every matrix product's two operands
are rounded to float8 (e4m3) with one scale per row or column along the
contracted axes (weights per output channel, activations per token, K and V
per row and head, ConSmax weights per query row), the W8A8 step below the
bfloat16 the configuration states. Products still accumulate in float32.

What is read out is only what the check compares: at each requested
position, the gap between the best logit and the logit of the served token,
and the argmax. A long sequence runs in one call, its attention in blocks of
query rows and its unembedding in blocks of positions, so it fits beside
the weights.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
ROW_BLOCK = 256        # positions per unembedding block
T_MIN = 1024           # sequences pad to a power of two at least this
                       # long (causal: no effect), so a few programs serve
                       # every length a run compares


class Sizes(NamedTuple):
    d: int
    h: int
    hkv: int
    dk: int
    ff: int
    vocab: int
    layers: int
    qkv_bias: bool
    theta: float
    eps: float


FP8_MAX = 448.0         # largest finite float8_e4m3fn


def _fq(x, axes):
    """float8 (e4m3) round trip, one scale per slice along ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, a_axes, b_axes, prec):
    """einsum at HIGHEST; the control rounds both operands along the
    contracted axes first."""
    if prec == "fp8":
        a, b = _fq(a, a_axes), _fq(b, b_axes)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    """x: (t, heads, dk); pairs (i, i + dk/2) rotate by pos * theta^(-2i/dk)."""
    dk = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dk, 2, dtype=jnp.float32) / dk)
    ang = pos[:, None].astype(jnp.float32) * inv          # (t, dk/2)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dk // 2], x[..., dk // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, beta, gamma, prec):
    """Causal GQA ConSmax. q: (t, h, dk) already scaled by 1/sqrt(dk);
    k, v: (t, hkv, dk). Query rows in blocks of Q_BLOCK."""
    t, h, dk = q.shape
    hkv = k.shape[1]
    g = h // hkv
    qb = q.reshape(t // Q_BLOCK, Q_BLOCK, hkv, g, dk)
    kpos = jnp.arange(t)
    b = beta.reshape(hkv, g, 1, 1)
    gm = gamma.reshape(hkv, g, 1, 1)

    def block(args):
        i, qi = args
        s = _mm("qhgd,khd->hgqk", qi, k, (3,), (2,), prec)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        mask = kpos[None, :] <= qpos[:, None]
        p = jnp.where(mask, jnp.exp(s - b) / gm, 0.0)     # ConSmax, Eq. 2
        return _mm("hgqk,khd->qhgd", p, v, (3,), (0,), prec)

    out = jax.lax.map(block, (jnp.arange(t // Q_BLOCK), qb))
    return out.reshape(t, h, dk)


def _layer(s: Sizes, prec, x, lp):
    f32 = lambda a: a.astype(jnp.float32)                 # noqa: E731
    a = lp["attn"]
    t = x.shape[0]
    pos = jnp.arange(t)
    hN = _rms(x, lp["attn_norm"]["scale"], s.eps)

    def proj(p):
        y = _mm("td,dhk->thk", hN, f32(p["w"]), (1,), (0,), prec)
        return y + f32(p["b"]) if "b" in p else y

    q = _rope(proj(a["q"]), pos, s.theta) / np.sqrt(s.dk)
    k = _rope(proj(a["k"]), pos, s.theta)
    v = proj(a["v"])
    o = _attention(q, k, v, a["score_norm"]["beta"],
                   a["score_norm"]["gamma"], prec)
    x = x + _mm("thk,hkd->td", o, f32(a["o"]["w"]), (1, 2), (0, 1), prec)
    m = lp["mlp"]
    h2 = _rms(x, lp["mlp_norm"]["scale"], s.eps)
    gate = _mm("td,df->tf", h2, f32(m["gate"]["w"]), (1,), (0,), prec)
    up = _mm("td,df->tf", h2, f32(m["up"]["w"]), (1,), (0,), prec)
    x = x + _mm("tf,fd->td", jax.nn.silu(gate) * up, f32(m["down"]["w"]),
                (1,), (0,), prec)
    return x, None


def hidden(params, s: Sizes, tokens, prec="fp32"):
    """Final-norm hidden states (t, d) of one sequence, t a multiple of
    Q_BLOCK."""
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, s, prec), x,
                        params["blocks"]["b0"])
    return _rms(x, params["final_norm"]["scale"], s.eps)


@functools.partial(jax.jit, static_argnames=("s", "prec"))
def _gaps(params, tokens, rows, probe, *, s: Sizes, prec: str):
    x = hidden(params, s, tokens, prec)[rows]             # (n, d)
    table = params["embed"]["table"].astype(jnp.float32)
    if prec == "fp8":                      # once, not per block of rows
        x, table = _fq(x, (1,)), _fq(table, (1,))
    n = rows.shape[0]

    def block(args):
        xb, pb = args
        lg = jnp.einsum("nd,vd->nv", xb, table, precision=HI)
        best = jnp.max(lg, axis=-1)
        at = jnp.take_along_axis(lg, pb[:, None], axis=-1)[:, 0]
        return best - at, jnp.argmax(lg, axis=-1).astype(jnp.int32)

    gap, top = jax.lax.map(block, (x.reshape(n // ROW_BLOCK, ROW_BLOCK, -1),
                                   probe.reshape(n // ROW_BLOCK, ROW_BLOCK)))
    return gap.reshape(n), top.reshape(n)


def _pow2(n: int, least: int) -> int:
    p = least
    while p < n:
        p *= 2
    return p


def logit_gaps(params, s: Sizes, prompt, served, prec="fp32"):
    """For each served token i (predicted at position len(prompt) - 1 + i):
    (best logit - logit of served[i], argmax)."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
    n = len(served)
    t_pad = _pow2(len(seq), T_MIN)
    n_pad = _pow2(n, ROW_BLOCK)
    tokens = np.zeros(t_pad, np.int32)
    tokens[:len(seq)] = seq
    rows = np.zeros(n_pad, np.int32)
    rows[:n] = len(prompt) - 1 + np.arange(n)
    pr = np.zeros(n_pad, np.int32)
    pr[:n] = served
    gap, top = _gaps(params, jnp.asarray(tokens), jnp.asarray(rows),
                     jnp.asarray(pr), s=s, prec=prec)
    return np.asarray(gap)[:n], np.asarray(top)[:n]
