"""Single-pass ConSmax attention Pallas kernel (TPU target).

The paper's sync-free property expressed as a TPU kernel: the KV-block loop
(grid's ``arbitrary`` trailing dimension) carries ONLY the fp32 output
accumulator — no running max, no running denominator, no per-block rescale
multiplies, no final 1/l normalization. Each (q-block, kv-block) tile is:

    s   = q @ k^T * scale          (MXU, fp32 accumulate)
    p   = exp(s - beta) / gamma    (VPU; masked)
    acc += p @ v                   (MXU)

vs. the online-softmax baseline (../softmax_attn) which additionally keeps
(m, l) scratch, two VPU rescale passes per block and a final divide. GQA is
folded into the k/v index_map (no repeated-KV materialization).

VMEM budget per program @ (bq, bk, d) = (128, 128, 128..256), fp32 acc:
q 128·d·4 + k/v 2·128·d·4 + acc 128·d·4 + s/p 2·128·128·4 ≈ 0.5–0.9 MB — well
inside the ~16 MB/core VMEM, leaving room for the Mosaic double-buffered
pipeline.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(beta_ref, gamma_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, *,
            scale: float, causal: bool, window: int, softcap: float,
            bq: int, bk: int, kv_len: int, merged: bool):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                  # (bq, d)
    k = k_ref[0, 0]                                  # (bk, d)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < kv_len
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= (qpos - kpos) < window

    beta = beta_ref[0, 0]
    gamma = gamma_ref[0, 0]
    if merged:
        p = jnp.exp(-beta) / gamma * jnp.exp(s)      # Eq. 3 (C merged)
    else:
        p = jnp.exp(s - beta) / gamma                # Eq. 2
    p = jnp.where(mask, p, 0.0)

    acc_ref[...] += jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _flush():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


def consmax_attention(q, k, v, beta, gamma, *, causal: bool = True,
                      window: int = 0, softcap: float = 0.0,
                      merged: bool = False, scale: float | None = None,
                      bq: int = 128, bk: int = 128,
                      interpret: bool = False):
    """q: (b, nh, sq, d); k, v: (b, nkv, skv, d); beta/gamma: (nh,) fp32.

    Returns (b, nh, sq, d) in q.dtype. Grid: (b, nh, nq, nk) with the KV axis
    sequential ('arbitrary'); everything else parallel.
    """
    b, nh, sq, d = q.shape
    nkv, skv = k.shape[1], k.shape[2]
    g = nh // nkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq = min(bq, sq)
    bk = min(bk, skv)
    nq = -(-sq // bq)
    nk = -(-skv // bk)
    # pad sequences to block multiples (masked out via kv_len)
    if nq * bq != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, nq * bq - sq), (0, 0)))
    if nk * bk != skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, nk * bk - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, nk * bk - skv), (0, 0)))

    beta2 = beta.reshape(nh, 1).astype(jnp.float32)
    gamma2 = gamma.reshape(nh, 1).astype(jnp.float32)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, kv_len=skv, merged=merged)

    out = pl.pallas_call(
        kernel,
        grid=(b, nh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda ib, ih, iq, ik: (ih, 0)),   # beta
            pl.BlockSpec((1, 1), lambda ib, ih, iq, ik: (ih, 0)),   # gamma
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nh, nq * bq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(beta2, gamma2, q, k, v)
    return out[:, :, :sq]
