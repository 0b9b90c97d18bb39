"""Bitwidth-split LUT ConSmax kernel (paper Sec. IV-A, Eq. 4) — TPU adaptation.

The ASIC computes exp of an INT8 score losslessly as the product of two
16-entry LUT reads:  e^{s} = e^{16*MSB4} * e^{LSB4}. TPUs have no LUT silicon;
the MXU-idiomatic equivalent is two one-hot (bq, 16) x (16,) matmuls — the
16-entry tables live in VMEM (128 bytes each), the one-hot encode is VPU
compare ops, and the product + merged-C multiply fuse on the VPU. The result
is bit-identical to fp32 ``C * exp(scale * s_int8)`` up to fp32 rounding of
the two-term product (the tests sweep all 256 codes).

Signed decomposition: s = 16*(s >> 4) + (s & 15) holds for negatives with
arithmetic shift, so MSB4 in [-8, 7] indexes table entry (msb + 8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def make_luts(scale: float):
    """(msb_lut, lsb_lut): 16-entry fp32 tables for e^{scale*16*m}, e^{scale*l}."""
    m = jnp.arange(-8, 8, dtype=jnp.float32)          # entry i -> msb = i-8
    l = jnp.arange(16, dtype=jnp.float32)
    return jnp.exp(scale * 16.0 * m), jnp.exp(scale * l)


def _kernel(c_ref, msb_lut_ref, lsb_lut_ref, s_ref, o_ref, *, block: int):
    s = s_ref[0].astype(jnp.int32)                    # (block,) int8 scores
    msb = (s >> 4) + 8                                # [0, 16)
    lsb = s & 15
    # one-hot LUT reads (MXU-friendly: (block,16) @ (16,1))
    oh_m = (msb[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block, 16), 1)).astype(jnp.float32)
    oh_l = (lsb[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block, 16), 1)).astype(jnp.float32)
    e_m = oh_m @ msb_lut_ref[0][:, None]              # (block, 1)
    e_l = oh_l @ lsb_lut_ref[0][:, None]
    c = c_ref[0, 0]                                   # merged constant C
    o_ref[0] = (c * e_m[:, 0] * e_l[:, 0]).astype(o_ref.dtype)


def consmax_lut(scores_int8, c, scale: float, *, block: int = 1024,
                interpret: bool = False):
    """scores_int8: (n,) int8; c: scalar fp32 merged constant (e^{-beta}/gamma).
    Returns fp32 (n,) = C * exp(scale * scores)."""
    n = scores_int8.shape[0]
    block = min(block, n)
    nb = -(-n // block)
    if nb * block != n:
        scores_int8 = jnp.pad(scores_int8, (0, nb * block - n))
    msb_lut, lsb_lut = make_luts(scale)
    kernel = functools.partial(_kernel, block=block)
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 16), lambda i: (0, 0)),
            pl.BlockSpec((1, 16), lambda i: (0, 0)),
            pl.BlockSpec((1, block), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(jnp.asarray(c, jnp.float32).reshape(1, 1),
      msb_lut.reshape(1, 16), lsb_lut.reshape(1, 16),
      scores_int8.reshape(nb, block))
    return out.reshape(nb * block)[:n]
