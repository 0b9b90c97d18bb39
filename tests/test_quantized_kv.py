"""Quantized KV-cache pool: write-time quantization, in-kernel dequant,
scale plumbing, and the accuracy gates.

* ``cache_layout.quantize_kv``/``dequantize_kv`` round-trip properties:
  per-row-per-head fp32 scales, exact zeros for untouched rows, int8 range.
* All FOUR serving kernels (contiguous + paged x decode + prefill) on a
  quantized cache are BIT-IDENTICAL to the same kernel fed the dequantized
  values — the in-VMEM dequant is ``dequantize_kv``'s arithmetic, nothing
  more — and track the fp32 oracle within bf16 output round-off.
* ``consmax_lut`` parity: the decode kernel's dequant + ConSmax over int8
  K codes reproduces the LUT kernel's ``C * exp(scale * s)`` at matching
  bitwidths — the paper's int8-score LUT and the quantized cache agree on
  what an int8 code means.
* Cache trees: bf16 caches carry NO scale leaves (the default path is
  byte-identical to before quantization existed); quantized caches carry
  fp32 ones-initialized scale leaves; ``copy_kv_page`` moves a page's
  scale rows with its data (the COW contract).
* Engine end-to-end: int8 serving is deterministic (identical prompts,
  identical streams) on contiguous and paged caches with both kernels on.
* The accuracy gate: teacher-forced perplexity on the gpt2-consmax smoke
  config with an int8 KV cache stays within 1% of the bf16-KV perplexity.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

from repro.configs.base import ServeConfig
from repro.configs.registry import get_config
from repro.kernels import cache_layout as CL
from repro.kernels.consmax_decode.ops import (consmax_decode_op,
                                              consmax_decode_paged_op)
from repro.kernels.consmax_decode.ref import consmax_decode_ref
from repro.kernels.consmax_lut.kernel import consmax_lut
from repro.kernels.consmax_prefill.ops import (consmax_prefill_op,
                                               consmax_prefill_paged_op)
from repro.models import transformer as T
from repro.nn.module import Ctx
from repro.serve.engine import ContinuousBatchingEngine, make_serve_fns

QDTYPES = ["int8", "fp8_e4m3"]


# ----------------------------------------------------- quantize round-trip ----
@pytest.mark.parametrize("name", QDTYPES)
def test_quantize_roundtrip_scales_and_zeros(name):
    dtype = CL.kv_cache_dtype(name)
    x = random.normal(random.key(0), (2, 9, 3, 16), jnp.float32) * 3.0
    x = x.at[0, 4].set(0.0)                    # an untouched cache row
    q, s = CL.quantize_kv(x, dtype)
    assert q.dtype == dtype and q.shape == x.shape
    assert s.dtype == jnp.float32 and s.shape == x.shape[:-1]
    # zero rows: scale 1.0, exact-zero codes, exact-zero dequant
    assert np.all(np.asarray(s[0, 4]) == 1.0)
    assert np.all(np.asarray(q[0, 4].astype(jnp.float32)) == 0.0)
    deq = CL.dequantize_kv(q, s)
    assert np.all(np.asarray(deq[0, 4]) == 0.0)
    # per-row absmax scaling keeps the row error below one quant step
    amax = np.abs(np.asarray(x)).max(-1)
    step = amax / CL.kv_qmax(dtype)
    err = np.abs(np.asarray(deq) - np.asarray(x)).max(-1)
    if name == "int8":
        assert np.all(err <= 0.51 * step + 1e-7)   # round-to-nearest
        assert np.abs(np.asarray(q, np.int32)).max() <= 127
    else:
        # fp8 e4m3: 3 mantissa bits -> <= 2^-4 relative per element
        assert np.all(err <= amax / 16 + 1e-7)


def test_kv_dtype_resolver_and_config_validation():
    assert CL.kv_cache_dtype("bf16") == jnp.dtype(jnp.bfloat16)
    assert CL.kv_cache_dtype("bfloat16") == jnp.dtype(jnp.bfloat16)
    assert CL.kv_cache_dtype("int8") == jnp.dtype(jnp.int8)
    assert CL.kv_cache_dtype("fp8_e4m3") == jnp.dtype(jnp.float8_e4m3fn)
    assert not CL.kv_quantized("bfloat16") and CL.kv_quantized("int8")
    with pytest.raises(ValueError):
        CL.kv_cache_dtype("int4")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        ServeConfig(max_seq=32, kv_cache_dtype="float16")


# ------------------------------------------------------------ cache trees ----
def _attn_cells(tree):
    return [blk["attn"] for blk in tree.values() if "attn" in blk]


def test_bf16_cache_has_no_scale_leaves_quantized_does():
    cfg = get_config("qwen2-1.5b", smoke=True)
    plain = _attn_cells(T.init_caches(cfg, 2, 16))
    assert plain and not any("k_scale" in c or "v_scale" in c for c in plain)
    for tree in (T.init_caches(cfg, 2, 16, kv_dtype="int8"),
                 T.init_paged_caches(cfg, 2, 6, 8, kv_dtype="int8")):
        for c in _attn_cells(tree):
            assert c["k"].dtype == jnp.int8 and c["v"].dtype == jnp.int8
            for leaf in (c["k_scale"], c["v_scale"]):
                assert leaf.dtype == jnp.float32
                assert leaf.shape == c["k"].shape[:-1]
                # ones-initialized: untouched rows dequant to exact zeros
                assert np.all(np.asarray(leaf) == 1.0)


def test_copy_kv_page_carries_scale_rows():
    cfg = get_config("qwen2-1.5b", smoke=True)
    caches = T.init_paged_caches(cfg, 2, 6, 8, kv_dtype="int8")
    for attn in _attn_cells(caches):
        hkv, dk = attn["k"].shape[-2:]
        qk, sk = CL.quantize_kv(
            random.normal(random.key(1), (8, hkv, dk)), jnp.int8)
        attn["k"] = attn["k"].at[:, 2].set(qk)
        attn["k_scale"] = attn["k_scale"].at[:, 2].set(sk)
    out = T.copy_kv_page(caches, 2, 5)
    for c in _attn_cells(out):
        assert np.any(np.asarray(c["k"][:, 2]) != 0)       # page really set
        np.testing.assert_array_equal(np.asarray(c["k"][:, 5]),
                                      np.asarray(c["k"][:, 2]))
        np.testing.assert_array_equal(np.asarray(c["k_scale"][:, 5]),
                                      np.asarray(c["k_scale"][:, 2]))


# -------------------------------------------- kernels: in-VMEM dequant ----
def _quant(key, shape, name):
    x = random.normal(key, shape).astype(jnp.bfloat16)
    q, s = CL.quantize_kv(x, CL.kv_cache_dtype(name))
    return q, s, CL.dequantize_kv(q, s, jnp.bfloat16)


@pytest.mark.parametrize("name", QDTYPES)
def test_decode_kernel_quantized_bitexact_vs_dequantized(name):
    b, L, nh, nkv, d, bk = 2, 96, 4, 2, 32, 32
    key = random.key(0)
    q = (random.normal(random.fold_in(key, 1), (b, 1, nh, d))
         .astype(jnp.bfloat16))
    kq, ks, kd = _quant(random.fold_in(key, 2), (b, L, nkv, d), name)
    vq, vs, vd = _quant(random.fold_in(key, 3), (b, L, nkv, d), name)
    index = jnp.asarray([95, 40], jnp.int32)
    beta = jnp.linspace(0.5, 2.5, nh)
    gamma = jnp.full((nh,), 100.0)
    out = consmax_decode_op(q, kq, vq, index, beta, gamma, bk=bk,
                            k_scale=ks, v_scale=vs)
    yard = consmax_decode_op(q, kd, vd, index, beta, gamma, bk=bk)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(yard, np.float32))
    # and the fp32 oracle agrees to bf16 output round-off
    ref = consmax_decode_ref(q[:, 0], kq.swapaxes(1, 2), vq.swapaxes(1, 2),
                             index + 1, beta, gamma,
                             k_scale=ks.swapaxes(1, 2),
                             v_scale=vs.swapaxes(1, 2))
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(ref, np.float32), rtol=2e-2,
                               atol=1e-3)


@pytest.mark.parametrize("name", QDTYPES)
def test_decode_paged_kernel_quantized_bitexact_vs_dequantized(name):
    b, P, ps, nh, nkv, d = 2, 10, 8, 4, 2, 32
    key = random.key(1)
    q = (random.normal(random.fold_in(key, 1), (b, 1, nh, d))
         .astype(jnp.bfloat16))
    kq, ks, kd = _quant(random.fold_in(key, 2), (P, ps, nkv, d), name)
    vq, vs, vd = _quant(random.fold_in(key, 3), (P, ps, nkv, d), name)
    table = jnp.asarray([[3, 1, 6, -1], [5, 0, -1, -1]], jnp.int32)
    lengths = jnp.asarray([20, 11], jnp.int32)
    beta = jnp.linspace(0.5, 2.5, nh)
    gamma = jnp.full((nh,), 100.0)
    out = consmax_decode_paged_op(q, kq[None], vq[None], 0, table, lengths,
                                  beta, gamma, k_scale=ks[None],
                                  v_scale=vs[None])
    yard = consmax_decode_paged_op(q, kd[None], vd[None], 0, table, lengths,
                                   beta, gamma)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(yard, np.float32))


@pytest.mark.parametrize("name", QDTYPES)
def test_prefill_kernel_quantized_bitexact_vs_dequantized(name):
    b, c, H, hkv, dk, L = 2, 6, 4, 2, 32, 96
    key = random.key(2)
    q = (random.normal(random.fold_in(key, 1), (b, c, H, dk)) * 0.3
         ).astype(jnp.bfloat16)
    kq, ks, kd = _quant(random.fold_in(key, 2), (b, L, hkv, dk), name)
    vq, vs, vd = _quant(random.fold_in(key, 3), (b, L, hkv, dk), name)
    index = jnp.asarray([40, 3], jnp.int32)
    lengths = jnp.asarray([6, 2], jnp.int32)
    beta = jnp.linspace(0.5, 2.5, H)
    gamma = jnp.full((H,), 100.0)
    out = consmax_prefill_op(q, kq, vq, index, lengths, beta, gamma,
                             scale=1.0, bq=2, bk=32, k_scale=ks, v_scale=vs)
    yard = consmax_prefill_op(q, kd, vd, index, lengths, beta, gamma,
                              scale=1.0, bq=2, bk=32)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(yard, np.float32))


@pytest.mark.parametrize("name", QDTYPES)
def test_prefill_paged_kernel_quantized_bitexact_vs_dequantized(name):
    b, c, H, hkv, dk, ps, P = 3, 4, 4, 2, 32, 8, 12
    key = random.key(3)
    q = (random.normal(random.fold_in(key, 1), (b, c, H, dk)) * 0.3
         ).astype(jnp.bfloat16)
    kq, ks, kd = _quant(random.fold_in(key, 2), (P, ps, hkv, dk), name)
    vq, vs, vd = _quant(random.fold_in(key, 3), (P, ps, hkv, dk), name)
    table = jnp.asarray([[3, 1, 6, -1], [5, 0, 2, 7], [9, -1, -1, -1]],
                        jnp.int32)
    index = jnp.asarray([12, 27, 3], jnp.int32)
    lengths = jnp.asarray([4, 2, 4], jnp.int32)
    beta = jnp.linspace(0.5, 2.5, H)
    gamma = jnp.full((H,), 100.0)
    out = consmax_prefill_paged_op(q, kq[None], vq[None], 0, table, index,
                                   lengths, beta, gamma, scale=1.0, bq=2,
                                   k_scale=ks[None], v_scale=vs[None])
    yard = consmax_prefill_paged_op(q, kd[None], vd[None], 0, table, index,
                                    lengths, beta, gamma, scale=1.0, bq=2)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(yard, np.float32))


# (hkv, dk): pages of 128 rows that XLA's TPU layout stores head-major (2
# heads of dk 128, qwen2-1.5b's int8 pool) and rows-minor (8 heads of dk
# 64); their fp32 scale pages rows-minor
STORED_VIEWS = {"head_major_2x128": (2, 128), "rows_minor_8x64": (8, 64)}


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
@pytest.mark.parametrize("widths", sorted(STORED_VIEWS))
@pytest.mark.parametrize("name", QDTYPES)
def test_paged_kernels_quantized_bitexact_in_stored_views(kernel, widths,
                                                           name):
    """Both paged kernels read a quantized pool, and its scales, in the
    order XLA's TPU layout stores them in (``CL.page_view``), transposing
    a page's codes where they lie across their scales: still bit-identical
    to the kernel fed the dequantized pool."""
    hkv, dk = STORED_VIEWS[widths]
    b, c, H, ps, P = 2, 4, 2 * hkv, 128, 7
    dt = CL.kv_cache_dtype(name)
    assert CL.page_view(dt, (ps, hkv, dk)) != CL.ROW_MAJOR
    assert CL.page_view(jnp.float32, (ps, hkv)) == CL.ROWS_MINOR
    key = random.key(4)
    q = (random.normal(random.fold_in(key, 1), (b, c, H, dk)) * 0.3
         ).astype(jnp.bfloat16)
    kq, ks, kd = _quant(random.fold_in(key, 2), (2, P, ps, hkv, dk), name)
    vq, vs, vd = _quant(random.fold_in(key, 3), (2, P, ps, hkv, dk), name)
    table = jnp.asarray([[3, 1, 6], [5, 0, -1]], jnp.int32)
    index = jnp.asarray([300, 140], jnp.int32)
    lengths = jnp.asarray([c, 2], jnp.int32)
    beta = jnp.linspace(0.5, 2.5, H)
    gamma = jnp.full((H,), 100.0)
    if kernel == "decode":
        def run(k, v, **sc):
            return consmax_decode_paged_op(q[:, :1], k, v, 1, table,
                                           index + 1, beta, gamma, **sc)
    else:
        def run(k, v, **sc):
            return consmax_prefill_paged_op(q, k, v, 1, table, index,
                                            lengths, beta, gamma, scale=1.0,
                                            bq=2, **sc)
    out = run(kq, vq, k_scale=ks, v_scale=vs)
    yard = run(kd, vd)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(yard, np.float32))


# ------------------------------------------------------------ LUT parity ----
def test_decode_kernel_dequant_consmax_matches_lut_kernel():
    """int8 K codes through the quantized decode kernel = the LUT kernel.

    One head, dk = L = 16. Row j of K stores the int8 code s_j in lane 0
    (k_scale 1.0: dequant is the identity on integer codes), q is e_0 in
    fp32, and V is the 16x16 identity — so the decode output's lane d IS
    the ConSmax weight C * exp(scale * s_d), exactly what ``consmax_lut``
    computes from the same codes via its msb/lsb table split."""
    L = d = 16
    codes = jnp.arange(-120, 136, 16, dtype=jnp.int8)          # 16 codes
    k = jnp.zeros((1, L, 1, d), jnp.int8).at[0, :, 0, 0].set(codes)
    v = jnp.eye(L, dtype=jnp.int8)[None, :, None, :]
    ones = jnp.ones((1, L, 1), jnp.float32)
    q = jnp.zeros((1, 1, 1, d), jnp.float32).at[0, 0, 0, 0].set(1.0)
    beta = jnp.asarray([1.5])
    gamma = jnp.asarray([100.0])
    sigma = 1.0 / 16.0                          # the LUT's score scale
    index = jnp.asarray([L - 1], jnp.int32)
    out = consmax_decode_op(q, k, v, index, beta, gamma, scale=sigma,
                            bk=16, k_scale=ones, v_scale=ones)
    c = jnp.exp(-beta[0]) / gamma[0]
    lut = consmax_lut(codes, c, sigma, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0, 0, 0], np.float32),
                               np.asarray(lut), rtol=1e-5)


# -------------------------------------------------------- engine end-to-end ----
@pytest.mark.parametrize("paged", [False, True])
def test_engine_int8_kv_serves_deterministically(paged):
    cfg = get_config("qwen2-1.5b", smoke=True)
    params = T.lm_init(Ctx(random.key(0)), cfg)
    scfg = ServeConfig(max_seq=48, prefill_chunk=8, max_slots=2,
                       decode_kernel=True, prefill_kernel=True,
                       kv_cache_dtype="int8", paged_kv=paged,
                       page_size=8, score_norm="consmax")
    eng = ContinuousBatchingEngine(cfg, scfg, params)
    prompt = list(map(int, random.randint(random.key(5), (11,), 0,
                                          cfg.vocab_size)))
    other = list(map(int, random.randint(random.key(6), (5,), 0,
                                         cfg.vocab_size)))
    u1 = eng.submit(prompt, 6)
    u2 = eng.submit(other, 4)
    u3 = eng.submit(prompt, 6)
    res = eng.run()
    assert len(res[u1]) == 6 and len(res[u2]) == 4
    assert res[u1] == res[u3]                   # identical prompt, stream


# ---------------------------------------------------------- perplexity gate ----
def _cache_ppl(cfg, params, toks, kv_dtype):
    """Teacher-forced NLL through the legacy logits-returning decode step:
    every K/V row is written through (and read back from) the configured
    cache dtype — exactly the serving path's quantization error surface."""
    scfg = ServeConfig(max_seq=len(toks) + 2, max_slots=1,
                       kv_cache_dtype=kv_dtype, fused_sampling=False,
                       score_norm="consmax")
    init_caches, _, decode_step, _ = make_serve_fns(cfg, scfg)
    step = jax.jit(decode_step)
    caches = init_caches(1)
    nll = 0.0
    for t in range(len(toks) - 1):
        logits, caches = step(params, caches,
                              {"tokens": jnp.asarray([[toks[t]]], jnp.int32)})
        logp = jax.nn.log_softmax(logits[0].astype(jnp.float32))
        nll -= float(logp[toks[t + 1]])
    return float(np.exp(nll / (len(toks) - 1)))


def test_int8_kv_perplexity_within_one_percent_of_bf16():
    cfg = get_config("gpt2-consmax", smoke=True)
    params = T.lm_init(Ctx(random.key(0)), cfg)
    toks = list(map(int, random.randint(random.key(8), (33,), 0,
                                        cfg.vocab_size)))
    ppl_bf16 = _cache_ppl(cfg, params, toks, "bfloat16")
    ppl_int8 = _cache_ppl(cfg, params, toks, "int8")
    rel = abs(ppl_int8 - ppl_bf16) / ppl_bf16
    assert rel <= 0.01, (
        f"int8-KV ppl {ppl_int8:.3f} vs bf16-KV {ppl_bf16:.3f}: "
        f"{rel:.2%} > 1% — quantized-cache accuracy gate")
