"""The plain reference's shared parts: float32 arithmetic on the params
pytree that model modules (``bench/models/<name>.py``) build their forward
passes from.

It imports nothing of the program. Every matrix product runs at float32
``HIGHEST`` precision on the bfloat16 weights widened to float32. RMSNorm
keeps its gain zero-centred (1 + scale), rotary positions rotate half-split
pairs, and attention is normalized by ConSmax (paper Eq. 2: exp(s - beta) /
gamma per head, no max and no sum).

``prec="fp8"`` is the control: every matrix product's two operands are
rounded to float8 (e4m3) with one scale per row or column along the
contracted axes (weights per output channel, activations per token, K and V
per row and head, ConSmax weights per query row), the W8A8 step below the
bfloat16 the configurations state. Products still accumulate in float32.

What is read out is only what the check compares: at each requested
position, the gap between the best logit and the logit of the served token,
and the argmax. A long sequence runs in one call, its attention in blocks of
query rows and its unembedding in blocks of positions, so it fits beside
the weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
ROW_BLOCK = 256        # positions per unembedding block
T_MIN = 1024           # sequences pad to a power of two at least this
                       # long (causal: no effect), so a few programs serve
                       # every length a run compares

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


def _attention(q, k, v, beta, gamma, prec, window: int = 0):
    """Causal GQA ConSmax. q: (t, h, dk) already scaled by 1/sqrt(dk);
    k, v: (t, hkv, dk). Query rows in blocks of Q_BLOCK. ``window`` > 0
    keeps only keys less than ``window`` rows behind the query (a
    sliding-window layer)."""
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
        if window > 0:
            mask &= qpos[:, None] - kpos[None, :] < window
        p = jnp.where(mask, jnp.exp(s - b) / gm, 0.0)     # ConSmax, Eq. 2
        return _mm("hgqk,khd->qhgd", p, v, (3,), (0,), prec)

    out = jax.lax.map(block, (jnp.arange(t // Q_BLOCK), qb))
    return out.reshape(t, h, dk)


def readout(x, table, probe, prec):
    """Final hidden rows x (n, d), n a multiple of ROW_BLOCK, against the
    unembedding ``table`` (vocab, d): per row, (best logit - logit of
    ``probe``, argmax), in blocks of ROW_BLOCK rows."""
    table = table.astype(jnp.float32)
    if prec == "fp8":                      # once, not per block of rows
        x, table = _fq(x, (1,)), _fq(table, (1,))
    n = x.shape[0]

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


def gaps_at(gaps, prompt, served):
    """Teacher-force ``prompt`` and ``served`` through a model's jitted
    ``gaps(tokens, rows, probe)`` (tokens padded to a power of two, at
    least T_MIN; rows and probes to a multiple of ROW_BLOCK), and read, for
    each served token i (predicted at position len(prompt) - 1 + i):
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
    gap, top = gaps(jnp.asarray(tokens), jnp.asarray(rows), jnp.asarray(pr))
    return np.asarray(gap)[:n], np.asarray(top)[:n]
