"""Fused ConSmax prefill/append kernel + cache-layout decode path.

* ``consmax_prefill`` / ``consmax_prefill_paged`` vs the jnp serving
  oracles (``append_attention`` / ``paged_attention``) and the package ref,
  across GQA ratios, ragged index/lengths, sliding window, softcap, and
  merged on/off (interpret mode on CPU, <= 1e-5 fp32).
* Engine output is bit-identical with ``prefill_kernel`` on vs off on the
  qwen2/gemma2/grok smoke configs, contiguous AND paged, with
  ``prefill_chunk`` far below the prompt length (multi-chunk admissions
  interleaved with decode).
* The one-compiled-shape guarantee survives the kernel: exactly one
  prefill and one decode trace across mixed-length traffic.
* The decode/prefill steps' jaxprs contain NO transpose (or pad) of a
  cache-sized array — the kernels consume the cache in its stored
  ``(b, L, hkv, dk)`` layout, so the old per-step ``swapaxes(1, 2)``
  full-cache copies are gone.
* Kernel-flag validation: ``ServeConfig(score_norm=...)`` raises at
  CONSTRUCTION for ``prefill_kernel``/``decode_kernel`` on a non-consmax
  norm, and ``make_serve_fns`` raises against the real ModelConfig.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

from repro.analysis.jaxpr_lint import cache_sized_ops
from repro.configs.base import ServeConfig
from repro.configs.registry import get_config
from repro.core.attention import append_attention, paged_attention
from repro.kernels.consmax_prefill.ops import (consmax_prefill_op,
                                               consmax_prefill_paged_op)
from repro.kernels.consmax_prefill.ref import consmax_prefill_ref
from repro.models import transformer as T
from repro.nn.module import Ctx
from repro.serve.engine import (ContinuousBatchingEngine, ServeSession,
                                make_serve_fns)


def _model(arch):
    cfg = get_config(arch, smoke=True)
    return cfg, T.lm_init(Ctx(random.key(0)), cfg)


def _prompts(cfg, lens, seed=10):
    return [list(map(int, random.randint(random.key(seed + i), (n,), 0,
                                         cfg.vocab_size)))
            for i, n in enumerate(lens)]


# --------------------------------------------------- kernel vs jnp oracle ----
SHAPES = [
    # b, c, H, hkv, dk, L, bq, bk     (GQA 2/4, MQA, ragged blocks)
    (2, 8, 4, 4, 64, 64, 4, 32),
    (3, 6, 8, 2, 32, 96, 2, 32),     # GQA 4:1 + bq not dividing... (6%2=0)
    (2, 5, 4, 1, 64, 200, 5, 64),    # MQA + non-power-of-two L and c
    (1, 16, 2, 2, 128, 48, 128, 512),  # bq/bk > c/L clamp
    (2, 4, 4, 2, 32, 101, 4, 32),    # prime L: degenerate-divisor pad path
]


@pytest.mark.parametrize("merged", [True, False])
@pytest.mark.parametrize("shape", SHAPES)
def test_prefill_kernel_matches_append_attention(shape, merged):
    b, c, H, hkv, dk, L, bq, bk = shape
    key = random.key(0)
    q = random.normal(random.fold_in(key, 1), (b, c, H, dk)) * 0.3
    k = random.normal(random.fold_in(key, 2), (b, L, hkv, dk))
    v = random.normal(random.fold_in(key, 3), (b, L, hkv, dk))
    index = random.randint(random.fold_in(key, 4), (b,), 0, L - c)
    lengths = random.randint(random.fold_in(key, 5), (b,), 1, c + 1)
    beta = jnp.linspace(0.5, 2.5, H)
    gamma = jnp.full((H,), 100.0)
    params = {"beta": beta, "gamma": gamma}

    got = consmax_prefill_op(q, k, v, index, lengths, beta, gamma,
                             merged=merged, scale=1.0, bq=bq, bk=bk)
    oracle = append_attention(q, k, v, index, lengths, norm_kind="consmax",
                              norm_params=params, merged=merged, kv_chunk=32)
    ref = consmax_prefill_ref(q, k, v, index, lengths, beta, gamma,
                              merged=merged, scale=1.0)
    # the three sum the same L fp32 terms p*v in different orders; two
    # orders differ by at most 2*gamma_L*sum|p*v| (plus a few roundings
    # of p), so bound each element by that, with sum|p*v| = ref on |v|
    mag = consmax_prefill_ref(q, k, jnp.abs(v), index, lengths, beta, gamma,
                              merged=merged, scale=1.0)
    tol = 2 * (L + 4) * 2.0 ** -24 * np.asarray(mag, np.float32)
    for want in (oracle, ref):
        err = np.abs(np.asarray(got, np.float32)
                     - np.asarray(want, np.float32))
        assert (err <= tol).all(), float((err - tol).max())


@pytest.mark.parametrize("window,softcap", [(6, 0.0), (64, 0.0), (0, 30.0)])
def test_prefill_kernel_window_and_softcap(window, softcap):
    b, c, H, hkv, dk, L = 2, 6, 4, 2, 32, 96
    key = random.key(1)
    q = random.normal(random.fold_in(key, 1), (b, c, H, dk)) * 0.3
    k = random.normal(random.fold_in(key, 2), (b, L, hkv, dk))
    v = random.normal(random.fold_in(key, 3), (b, L, hkv, dk))
    index = jnp.asarray([40, 3], jnp.int32)
    lengths = jnp.asarray([6, 2], jnp.int32)
    beta = jnp.linspace(0.5, 2.5, H)
    gamma = jnp.full((H,), 100.0)
    params = {"beta": beta, "gamma": gamma}
    got = consmax_prefill_op(q, k, v, index, lengths, beta, gamma,
                             window=window, softcap=softcap, merged=True,
                             scale=1.0, bq=2, bk=32)
    oracle = append_attention(q, k, v, index, lengths, norm_kind="consmax",
                              norm_params=params, window=window,
                              softcap=softcap, merged=True, kv_chunk=32)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(oracle, np.float32), atol=1e-5)


def test_prefill_kernel_bfloat16_io():
    b, c, H, hkv, dk, L = 1, 4, 4, 2, 64, 64
    key = random.key(2)
    q = random.normal(random.fold_in(key, 1), (b, c, H, dk)) * 0.3
    k = random.normal(random.fold_in(key, 2), (b, L, hkv, dk))
    v = random.normal(random.fold_in(key, 3), (b, L, hkv, dk))
    index = jnp.asarray([20], jnp.int32)
    lengths = jnp.asarray([4], jnp.int32)
    beta = jnp.linspace(0.5, 2.5, H)
    gamma = jnp.full((H,), 100.0)
    out = consmax_prefill_op(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                             v.astype(jnp.bfloat16), index, lengths, beta,
                             gamma, scale=1.0, bq=2, bk=32)
    assert out.dtype == jnp.bfloat16
    ref = consmax_prefill_ref(q, k, v, index, lengths, beta, gamma,
                              scale=1.0)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (6, 0.0), (0, 30.0)])
def test_prefill_paged_kernel_matches_paged_attention(window, softcap):
    b, c, H, hkv, dk, ps, P = 3, 4, 4, 2, 32, 8, 12
    key = random.key(3)
    q = random.normal(random.fold_in(key, 1), (b, c, H, dk)) * 0.3
    kp = random.normal(random.fold_in(key, 2), (P, ps, hkv, dk))
    vp = random.normal(random.fold_in(key, 3), (P, ps, hkv, dk))
    table = jnp.asarray([[3, 1, 6, -1], [5, 0, 2, 7], [9, -1, -1, -1]],
                        jnp.int32)
    index = jnp.asarray([12, 27, 3], jnp.int32)
    lengths = jnp.asarray([4, 2, 4], jnp.int32)
    beta = jnp.linspace(0.5, 2.5, H)
    gamma = jnp.full((H,), 100.0)
    params = {"beta": beta, "gamma": gamma}
    got = consmax_prefill_paged_op(q, kp[None], vp[None], 0, table, index,
                                   lengths, beta, gamma, window=window,
                                   softcap=softcap, merged=True, scale=1.0,
                                   bq=2)
    oracle = paged_attention(q, kp[None], vp[None], 0, table, index, lengths,
                             norm_kind="consmax", norm_params=params,
                             window=window, softcap=softcap, merged=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(oracle, np.float32), atol=1e-5)


# ------------------------------------------------------- engine parity ----
@pytest.mark.parametrize("arch,paged", [
    ("qwen2-1.5b", False),      # GQA (4 heads over 1 kv head)
    ("qwen2-1.5b", True),
    ("gemma2-2b", False),       # local/global alternation + attn softcap
    ("gemma2-2b", True),
    ("grok-1-314b", False),     # global softcap + MoE blocks
    ("grok-1-314b", True),
])
def test_engine_bit_parity_prefill_kernel_on_vs_off(arch, paged):
    """The fused prefill kernel is a layout/fusion change, not a numerics
    change: the engine must emit exactly the same tokens with the kernel on
    and off (PR 2/3 pinned the off path to solo decode), across multi-chunk
    ragged admissions on contiguous rows and the page pool."""
    cfg, p = _model(arch)
    prompts = _prompts(cfg, [5, 13, 3, 11])     # chunk=4 << longest prompt
    budgets = [4, 6, 3, 5]

    outs = []
    for prefill_kernel in (False, True):
        scfg = ServeConfig(max_seq=48, prefill_chunk=4, max_slots=3,
                           prefill_kernel=prefill_kernel, prefill_kv_block=16,
                           paged_kv=paged, page_size=4 if paged else 256,
                           num_pages=14 if paged else 0)
        eng = ContinuousBatchingEngine(cfg, scfg, p)
        uids = [eng.submit(pr, mx) for pr, mx in zip(prompts, budgets)]
        results = eng.run(max_steps=400)
        assert sorted(results) == sorted(uids)
        assert eng.prefill_cache_size == 1      # ONE compiled prefill shape
        outs.append([results[u] for u in uids])
    for off, on in zip(*outs):
        np.testing.assert_array_equal(np.asarray(off), np.asarray(on))


def test_engine_prefill_kernel_matches_serving_alone():
    """Kernel-on engine vs solo ServeSession — anchors the on/off parity
    test to the absolute reference, not just to itself."""
    cfg, p = _model("qwen2-1.5b")
    scfg = ServeConfig(max_seq=48, prefill_chunk=4, max_slots=2,
                       prefill_kernel=True, prefill_kv_block=16,
                       decode_kernel=True, decode_kv_block=16)
    prompts = _prompts(cfg, [9, 6], seed=50)
    eng = ContinuousBatchingEngine(cfg, scfg, p)
    uids = [eng.submit(pr, 5) for pr in prompts]
    results = eng.run(max_steps=200)
    alone = ServeSession(cfg, ServeConfig(max_seq=48), p)
    for uid, pr in zip(uids, prompts):
        ref = np.asarray(alone.generate(jnp.asarray([pr], jnp.int32),
                                        steps=5))[0]
        np.testing.assert_array_equal(np.asarray(results[uid]), ref)


def test_engine_prefill_kernel_one_compiled_shape_across_mixed_traffic():
    """Mirror of the PR 2/3 trace-count regressions with the kernel on:
    mixed-length admissions, ragged tails, and recycles still compile
    exactly one prefill shape and one decode shape."""
    cfg, p = _model("qwen2-1.5b")
    scfg = ServeConfig(max_seq=32, prefill_chunk=4, max_slots=2,
                       prefill_kernel=True, prefill_kv_block=8,
                       paged_kv=True, page_size=2, num_pages=24)
    eng = ContinuousBatchingEngine(cfg, scfg, p)
    for pr, mx in zip(_prompts(cfg, [9, 2, 14, 1, 6], seed=30),
                      [3, 1, 5, 2, 4]):
        eng.submit(pr, mx)
    results = eng.run(max_steps=400)
    assert len(results) == 5
    assert eng.prefill_cache_size == 1
    assert eng.decode_cache_size == 1


# --------------------------------------------- no-full-cache-copy jaxpr ----
# the jaxpr traversal + cache-sized-op walk now live in
# repro.analysis.jaxpr_lint (shared with the repro.launch.analyze CI gate);
# these tests keep the original acceptance shapes on the library helper
def test_decode_step_jaxpr_has_no_full_cache_transpose():
    """The satellite fix, verified at the IR level: with the split-KV
    kernel on, the decode step's jaxpr contains no transpose (or pad) of a
    cache-sized array — the old wrapper re-transposed the whole
    (b, L, hkv, dk) cache on EVERY token step. Checked on the PRODUCTION
    step, i.e. with the fused sampling epilogue in the jaxpr too."""
    from repro.serve import sampling as S
    cfg, p = _model("qwen2-1.5b")
    max_slots, max_seq = 4, 2048
    scfg = ServeConfig(max_seq=max_seq, max_slots=max_slots,
                       decode_kernel=True)
    init_caches, _, decode_step, _ = make_serve_fns(cfg, scfg)
    caches = init_caches(max_slots)
    inputs = {"tokens": jnp.zeros((max_slots,), jnp.int32),
              "active": jnp.ones((max_slots,), bool)}
    jaxpr = jax.make_jaxpr(decode_step)(p, caches, inputs,
                                        S.bank_init(max_slots))
    cells = max_slots * max_seq * cfg.n_kv_heads * cfg.head_dim_
    assert cells > cfg.vocab_size * cfg.d_model  # dominates any param/logit
    bad = cache_sized_ops(jaxpr, cells, prims=("transpose", "pad"))
    assert not bad, f"cache-sized layout copies in decode step: {bad}"


def test_prefill_step_jaxpr_has_no_full_cache_transpose():
    """Same IR check for the fused prefill chunk step (the engine slices a
    single (1, L, hkv, dk) slot cache per chunk)."""
    cfg, p = _model("qwen2-1.5b")
    max_seq, chunk = 4096, 16
    scfg = ServeConfig(max_seq=max_seq, prefill_chunk=chunk, max_slots=2,
                       prefill_kernel=True)

    def prefill_chunk(params, caches, tokens, lengths):
        return T.lm_apply(params, cfg, tokens=tokens, caches=caches,
                          merged=True, prefill_append=lengths,
                          logits_index=lengths[0] - 1,
                          prefill_kernel=True,
                          prefill_kv_block=scfg.prefill_kv_block)[0]

    caches = T.init_caches(cfg, 1, max_seq)
    jaxpr = jax.make_jaxpr(prefill_chunk)(
        p, caches, jnp.zeros((1, chunk), jnp.int32),
        jnp.asarray([chunk], jnp.int32))
    cells = max_seq * cfg.n_kv_heads * cfg.head_dim_
    assert cells > cfg.vocab_size * cfg.d_model
    bad = cache_sized_ops(jaxpr, cells, prims=("transpose", "pad"))
    assert not bad, f"cache-sized layout copies in prefill step: {bad}"


# ------------------------------------------------- construction checks ----
def test_serve_config_rejects_prefill_kernel_on_non_consmax_norm():
    """The kernel-flag guard now fires at ServeConfig CONSTRUCTION when the
    config carries the served model's score_norm (launch/serve.py passes
    it), not only inside make_serve_fns."""
    with pytest.raises(ValueError, match="consmax"):
        ServeConfig(max_seq=32, prefill_kernel=True, score_norm="softmax")
    with pytest.raises(ValueError, match="consmax"):
        ServeConfig(max_seq=32, decode_kernel=True, score_norm="softermax")
    # consmax (or unknown norm, checked later in make_serve_fns) is fine
    ServeConfig(max_seq=32, prefill_kernel=True, score_norm="consmax")
    ServeConfig(max_seq=32, prefill_kernel=True)


def test_serve_config_rejects_nonpositive_kernel_blocks():
    with pytest.raises(ValueError, match="kv_block"):
        ServeConfig(max_seq=32, prefill_kv_block=0)
    with pytest.raises(ValueError, match="kv_block"):
        ServeConfig(max_seq=32, decode_kv_block=-1)


def test_prefill_kernel_on_non_consmax_arch_raises_at_construction():
    cfg = get_config("qwen2-1.5b", smoke=True, score_norm="softmax")
    p = T.lm_init(Ctx(random.key(0)), cfg)
    scfg = ServeConfig(max_seq=32, prefill_kernel=True)
    with pytest.raises(ValueError, match="consmax"):
        ServeSession(cfg, scfg, p)
    with pytest.raises(ValueError, match="consmax"):
        ContinuousBatchingEngine(cfg, scfg, p)
    with pytest.raises(ValueError, match="consmax"):
        make_serve_fns(cfg, scfg)
    # the guard does not fire for the kind that has a kernel path
    make_serve_fns(get_config("qwen2-1.5b", smoke=True), scfg)
