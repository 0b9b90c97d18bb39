"""Online-softmax (FlashAttention-style) Pallas kernel — the baseline the
paper compares against. Identical tiling to ../consmax_attn; the difference
is exactly the synchronization the paper removes:

* two extra VMEM scratch vectors (running max m, running denominator l),
* a rescale of the accumulator on every KV block (the (m, l) "combine"),
* a final division by l.

Per (bq, bk) tile, vs. ConSmax this costs +2 row-reductions, +2 exp/rescale
VPU passes and +1 divide — the operation-count delta reported by
benchmarks/table1_ops.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, softcap: float,
            bq: int, bk: int, kv_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
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
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                       # rescale factor
    e = jnp.exp(s - m_new)
    e = jnp.where(mask, e, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(e, axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def softmax_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      softcap: float = 0.0, scale: float | None = None,
                      bq: int = 128, bk: int = 128, interpret: bool = False):
    """q: (b, nh, sq, d); k, v: (b, nkv, skv, d) -> (b, nh, sq, d)."""
    b, nh, sq, d = q.shape
    nkv, skv = k.shape[1], k.shape[2]
    g = nh // nkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq = min(bq, sq)
    bk = min(bk, skv)
    nq = -(-sq // bq)
    nk = -(-skv // bk)
    if nq * bq != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, nq * bq - sq), (0, 0)))
    if nk * bk != skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, nk * bk - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, nk * bk - skv), (0, 0)))

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, kv_len=skv)

    out = pl.pallas_call(
        kernel,
        grid=(b, nh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nh, nq * bq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)
    return out[:, :, :sq]
