"""Rotary position embeddings: full, partial (rotary_dim < head_dim), and
chatglm-style "2d" interleaved-pair layout, with optional YaRN scaling."""
from __future__ import annotations

import math

import jax.numpy as jnp


def rope_freqs(rotary_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    assert rotary_dim % 2 == 0
    exponent = jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim
    return 1.0 / (theta ** exponent)  # (rotary_dim//2,)


def yarn_freqs(rotary_dim: int, theta: float, yarn) -> tuple:
    """(inv_freq, attention_factor) of YaRN (``configs.base.YarnConfig``),
    as transformers' ``_compute_yarn_parameters`` with ``truncate`` true:
    pair i keeps theta^(-2i/dim) above the correction range [low, high],
    is divided by ``factor`` below it, and ramps linearly in between."""
    def corr_dim(rotations):
        return (rotary_dim * math.log(yarn.original_max_position_embeddings
                                      / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    extrap = rope_freqs(rotary_dim, theta)
    ramp = jnp.clip((jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                  # share of the unscaled frequency
    inv = extrap / yarn.factor * (1.0 - keep) + extrap * keep
    return inv, yarn.attention_factor


def _angles(positions, inv_freq):
    # positions: (..., seq) int; -> (..., seq, rotary_dim//2) fp32
    return positions[..., None].astype(jnp.float32) * inv_freq


def apply_rope(x, positions, *, rotary_dim=None, theta=10000.0,
               interleaved=False, yarn=None):
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).

    rotary_dim: rotate only the first rotary_dim dims (partial rope, chatglm
    uses head_dim//2). interleaved=True pairs (0,1),(2,3)... (GLM/GPT-NeoX
    "2d" layout); False pairs (i, i+rot/2) (llama half-split layout).
    yarn: a ``YarnConfig`` scales the frequencies and cos/sin (YaRN).
    """
    head_dim = x.shape[-1]
    rot = head_dim if rotary_dim is None else rotary_dim
    if yarn is None:
        inv_freq, mscale = rope_freqs(rot, theta), 1.0
    else:
        inv_freq, mscale = yarn_freqs(rot, theta, yarn)
    ang = _angles(positions, inv_freq)  # (..., seq, rot//2)
    cos = jnp.cos(ang)[..., None, :]  # (..., seq, 1, rot//2) broadcast heads
    sin = jnp.sin(ang)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale

    x_rot, x_pass = x[..., :rot], x[..., rot:]
    if interleaved:
        x1 = x_rot[..., 0::2]
        x2 = x_rot[..., 1::2]
    else:
        x1 = x_rot[..., : rot // 2]
        x2 = x_rot[..., rot // 2 :]
    x1 = x1.astype(jnp.float32)
    x2 = x2.astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    if interleaved:
        out = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    else:
        out = jnp.concatenate([r1, r2], axis=-1)
    out = out.astype(x.dtype)
    return jnp.concatenate([out, x_pass], axis=-1) if rot < head_dim else out
