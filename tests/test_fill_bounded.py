"""Fill-bounded serving kernels: bit-parity with the capacity-swept grids
at every fill level, oracle agreement, the fill-is-a-value no-recompile
guarantee, and the satellite serving fixes that ride along.

* Fill sweep — decode and prefill, contiguous and paged, fill levels
  {1, one-shard-boundary, mid-shard, full} × {GQA, sliding window,
  softcap}: ``fill_bound=True`` output is BIT-IDENTICAL to
  ``fill_bound=False`` (the pre-bounding capacity sweep — a dead shard's
  partial was an exact zero there, so skipping it changes nothing) and
  matches the jnp oracle.
* Trace-count regression: the jitted ops compile ONCE across heterogeneous
  fills — the clamp is a traced value, never a shape — and an engine run
  over mixed-length traffic keeps decode_cache_size == prefill_cache_size
  == 1 with fill bounding on.
* Engine end-to-end: fill-bounded and capacity-swept engines produce
  bit-identical tokens on heterogeneous prompts.
* ``ServeSession.generate(steps=0)`` raises instead of silently returning
  one token; ``PagePool.reserved_pages`` exposes reserved-but-unmapped
  admission pressure next to ``occupancy()``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

from repro.configs.base import ServeConfig
from repro.configs.registry import get_config
from repro.kernels import cache_layout as CL
from repro.kernels.consmax_decode import kernel as decode_kernel
from repro.kernels.consmax_decode.kernel import (consmax_decode,
                                                 consmax_decode_paged)
from repro.kernels.consmax_decode.ref import consmax_decode_ref
from repro.kernels.consmax_prefill.kernel import (consmax_prefill,
                                                  consmax_prefill_paged)
from repro.kernels.consmax_prefill.ref import consmax_prefill_ref
from repro.models import transformer as T
from repro.nn.module import Ctx
from repro.serve.engine import ContinuousBatchingEngine, ServeSession
from repro.serve.scheduler import PagePool

B, L, NH, NKV, D = 3, 64, 4, 2, 32
BK = 16                                   # KV shard size: 4 shards over L
PS = 16                                   # page size for the paged variants
C = 8                                     # prefill chunk length

# fill levels: single row, exactly one shard, mid-shard, capacity
FILLS = {"one": 1, "shard": BK, "mid": BK * 2 + 3, "full": L}
VARIANTS = {"gqa": dict(window=0, softcap=0.0),
            "window": dict(window=24, softcap=0.0),
            "softcap": dict(window=0, softcap=30.0)}


def _setup(seed=0):
    ks = random.split(random.key(seed), 5)
    q = random.normal(ks[0], (B, NH, D))
    k = random.normal(ks[1], (B, L, NKV, D))
    v = random.normal(ks[2], (B, L, NKV, D))
    beta = jnp.linspace(0.5, 2.5, NH)
    gamma = jnp.full((NH,), 100.0)
    return q, k, v, beta, gamma


def _paged(k, v, kv_lens):
    """Scatter the first kv_lens[b] contiguous rows onto a page pool."""
    npg = L // PS
    kp = jnp.zeros((B * npg + 1, PS, *k.shape[2:]), k.dtype)
    vp = jnp.zeros_like(kp)
    tab = -jnp.ones((B, npg), jnp.int32)
    pid = 1
    for ib in range(B):
        for j in range(-(-int(kv_lens[ib]) // PS)):
            kp = kp.at[pid].set(k[ib, j * PS:(j + 1) * PS])
            vp = vp.at[pid].set(v[ib, j * PS:(j + 1) * PS])
            tab = tab.at[ib, j].set(pid)
            pid += 1
    return kp, vp, tab


# ------------------------------------------------------ decode fill sweep ----
@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("fill", sorted(FILLS))
def test_decode_fill_sweep_bit_parity_and_oracle(fill, variant):
    q, k, v, beta, gamma = _setup()
    kw = VARIANTS[variant]
    # heterogeneous batch: one slot at the swept fill, the others fixed
    lens = jnp.asarray([FILLS[fill], 1, L], jnp.int32)[:B]
    bounded = consmax_decode(q, k, v, lens, beta, gamma, bk=BK,
                             fill_bound=True, interpret=True, **kw)
    capacity = consmax_decode(q, k, v, lens, beta, gamma, bk=BK,
                              fill_bound=False, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(bounded), np.asarray(capacity))
    ref = consmax_decode_ref(q, k.swapaxes(1, 2), v.swapaxes(1, 2), lens,
                             beta, gamma, **kw)
    np.testing.assert_allclose(np.asarray(bounded), np.asarray(ref),
                               atol=1e-5)


# page walks of the paged decode kernel beyond the plain table, each at
# the "mid" fill beside a 1-row slot and a full one. All but the quantized
# walks read bf16 pools of two heads a 32-bit word and 128-lane rows, which
# the kernel fetches by its own DMAs, a run of columns a program
PAGED_WALKS = ("bf16_pairs", "fp8", "hole", "int8", "localized",
               "ragged_runs")


def _paged_walk(walk, q, k, v, lens, monkeypatch):
    """(q, kp, vp, table, scales, the pool as the kernel reads it) for one
    walk; page 0 of every pool the kernel sees is zeros, as in ``_paged``,
    so unmapped columns read zeros on the capacity sweep too."""
    scales = {}
    if walk in ("int8", "fp8"):
        kp, vp, tab = _paged(k, v, lens)
        dt = CL.kv_cache_dtype("fp8_e4m3" if walk == "fp8" else walk)
        (kp, ksc), (vp, vsc) = CL.quantize_kv(kp, dt), CL.quantize_kv(vp, dt)
        scales = dict(k_scale=ksc, v_scale=vsc)
        return (q, kp, vp, tab, scales,
                [CL.dequantize_kv(kp, ksc), CL.dequantize_kv(vp, vsc)])
    assert CL.packs_head_pairs(jnp.bfloat16, NKV, 128)
    ks = random.split(random.key(7), 3)
    q = random.normal(ks[0], (B, NH, 128))
    k, v = (random.normal(kk, (B, L, NKV, 128)).astype(jnp.bfloat16)
            for kk in ks[1:])
    kp, vp, tab = _paged(k, v, lens)
    if walk == "hole":            # slot 0's second page unmapped mid-table
        tab = tab.at[0, 1].set(-1)
    elif walk == "localized":     # shard 1 of 2 over a block position map
        pps, npg = B * (L // PS) // 2 + 1, L // PS
        pages = [iter(range(1, pps)), iter(range(pps + 1, 2 * pps))]
        glob = np.full((B, npg), -1, np.int32)
        kp = jnp.zeros((2 * pps, PS, *k.shape[2:]), k.dtype)
        vp = jnp.zeros_like(kp)
        for ib in range(B):
            for j in range(-(-int(lens[ib]) // PS)):
                glob[ib, j] = pid = next(pages[j // (npg // 2)])
                kp = kp.at[pid].set(k[ib, j * PS:(j + 1) * PS])
                vp = vp.at[pid].set(v[ib, j * PS:(j + 1) * PS])
        tab = CL.localize_page_table(jnp.asarray(glob), 1, pps)
        kp, vp = kp[pps:], vp[pps:]
        assert (tab == -1).any() and (tab >= 0).any()
    elif walk == "ragged_runs":   # 3 columns a program over a 4-column table
        page = 2 * CL.tiled_bytes((PS * NKV, 128), kp.dtype)
        monkeypatch.setattr(decode_kernel, "PAGE_VMEM_BYTES", 3 * page)
        assert decode_kernel._pages_per_program(tab.shape[1], page) == 3
    return q, kp, vp, tab, scales, [kp, vp]


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("fill", sorted(FILLS) + list(PAGED_WALKS))
def test_decode_paged_fill_sweep_bit_parity_and_oracle(fill, variant,
                                                       monkeypatch):
    q, k, v, beta, gamma = _setup(seed=1)
    kw = VARIANTS[variant]
    lens = jnp.asarray([FILLS.get(fill, FILLS["mid"]), 1, L], jnp.int32)[:B]
    if fill in FILLS:
        kp, vp, tab = _paged(k, v, lens)
        scales = {}
    else:
        q, kp, vp, tab, scales, (kseen, vseen) = _paged_walk(
            fill, q, k, v, lens, monkeypatch)
        # the oracle reads what the table maps: unmapped columns page 0
        k, v = (x[jnp.maximum(tab, 0)].reshape(B, -1, *x.shape[2:])
                for x in (kseen, vseen))
    scales = {n: s[None] for n, s in scales.items()}     # one layer
    bounded = consmax_decode_paged(q, kp[None], vp[None], 0, tab, lens,
                                   beta, gamma, fill_bound=True,
                                   interpret=True, **kw, **scales)
    capacity = consmax_decode_paged(q, kp[None], vp[None], 0, tab, lens,
                                    beta, gamma, fill_bound=False,
                                    interpret=True, **kw, **scales)
    np.testing.assert_array_equal(np.asarray(bounded), np.asarray(capacity))
    ref = consmax_decode_ref(q, k.swapaxes(1, 2), v.swapaxes(1, 2), lens,
                             beta, gamma, **kw)
    np.testing.assert_allclose(np.asarray(bounded), np.asarray(ref),
                               atol=1e-5)


# ----------------------------------------------------- prefill fill sweep ----
def _prefill_setup(fill, seed=2):
    """A chunk appended at per-slot index so that index + length lands on
    the swept fill level (ragged real lengths, one slot per regime)."""
    _, k, v, beta, gamma = _setup(seed)
    q = random.normal(random.key(seed + 10), (B, C, NH, D))
    kvl = [fill, min(C, L), L]                       # chunk must fit: kvl>=len
    lengths = [min(C, n) for n in kvl]
    index = [n - ln for n, ln in zip(kvl, lengths)]
    return (q, k, v, jnp.asarray(index, jnp.int32),
            jnp.asarray(lengths, jnp.int32), beta, gamma)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("fill", sorted(FILLS))
def test_prefill_fill_sweep_bit_parity_and_oracle(fill, variant):
    q, k, v, index, lengths, beta, gamma = _prefill_setup(FILLS[fill])
    kw = VARIANTS[variant]
    bounded = consmax_prefill(q, k, v, index, lengths, beta, gamma, bq=4,
                              bk=BK, fill_bound=True, interpret=True, **kw)
    capacity = consmax_prefill(q, k, v, index, lengths, beta, gamma, bq=4,
                               bk=BK, fill_bound=False, interpret=True, **kw)
    ref = consmax_prefill_ref(q, k, v, index, lengths, beta, gamma, **kw)
    for ib in range(B):                              # pad rows are undefined
        n = int(lengths[ib])
        np.testing.assert_array_equal(np.asarray(bounded[ib, :n]),
                                      np.asarray(capacity[ib, :n]))
        np.testing.assert_allclose(np.asarray(bounded[ib, :n]),
                                   np.asarray(ref[ib, :n]), atol=1e-5)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("fill", sorted(FILLS))
def test_prefill_paged_fill_sweep_bit_parity_and_oracle(fill, variant):
    q, k, v, index, lengths, beta, gamma = _prefill_setup(FILLS[fill],
                                                          seed=3)
    kw = VARIANTS[variant]
    kp, vp, tab = _paged(k, v, index + lengths)
    bounded = consmax_prefill_paged(q, kp[None], vp[None], 0, tab, index,
                                    lengths, beta, gamma, bq=4,
                                    fill_bound=True, interpret=True, **kw)
    capacity = consmax_prefill_paged(q, kp[None], vp[None], 0, tab, index,
                                     lengths, beta, gamma, bq=4,
                                     fill_bound=False, interpret=True, **kw)
    ref = consmax_prefill_ref(q, k, v, index, lengths, beta, gamma, **kw)
    for ib in range(B):
        n = int(lengths[ib])
        np.testing.assert_array_equal(np.asarray(bounded[ib, :n]),
                                      np.asarray(capacity[ib, :n]))
        np.testing.assert_allclose(np.asarray(bounded[ib, :n]),
                                   np.asarray(ref[ib, :n]), atol=1e-5)


# ------------------------------------------------- fill is a value, not a ----
# ------------------------------------------------- shape: trace counts   ----
def test_fill_enters_as_value_one_compiled_decode_step():
    q, k, v, beta, gamma = _setup(seed=4)

    @jax.jit
    def step(q, k, v, lens):
        return consmax_decode(q, k, v, lens, beta, gamma, bk=BK,
                              fill_bound=True, interpret=True)

    outs = [step(q, k, v, jnp.asarray([n, 1, L], jnp.int32))
            for n in FILLS.values()]
    jax.block_until_ready(outs)
    assert step._cache_size() == 1, (
        "fill level re-traced the decode step — the live-shard clamp must "
        "be a traced value, never a shape")


def test_fill_enters_as_value_one_compiled_prefill_step():
    q, k, v, index, lengths, beta, gamma = _prefill_setup(L, seed=5)

    @jax.jit
    def step(q, k, v, index, lengths):
        return consmax_prefill(q, k, v, index, lengths, beta, gamma, bq=4,
                               bk=BK, fill_bound=True, interpret=True)

    outs = [step(q, k, v, *_prefill_setup(n, seed=5)[3:5])
            for n in FILLS.values()]
    jax.block_until_ready(outs)
    assert step._cache_size() == 1


# ------------------------------------------------------ engine end-to-end ----
def _smoke(arch="qwen2-1.5b"):
    cfg = get_config(arch, smoke=True)
    return cfg, T.lm_init(Ctx(random.key(0)), cfg)


def _prompts(cfg, lens, seed=10):
    return [list(map(int, random.randint(random.key(seed + i), (n,), 0,
                                         cfg.vocab_size)))
            for i, n in enumerate(lens)]


def test_engine_heterogeneous_fill_one_compiled_step_and_bit_parity():
    """Mixed-length traffic through the kernel-path engine: fill bounding
    keeps ONE compiled prefill and ONE compiled decode step, and the tokens
    are bit-identical to the capacity-swept engine."""
    cfg, p = _smoke()
    prompts = _prompts(cfg, [5, 13, 3, 11, 7])
    budgets = [4, 6, 3, 5, 6]

    results = {}
    for fill_bound in (True, False):
        scfg = ServeConfig(max_seq=48, prefill_chunk=4, max_slots=3,
                           decode_kernel=True, prefill_kernel=True,
                           decode_kv_block=16, prefill_kv_block=16,
                           fill_bound=fill_bound)
        eng = ContinuousBatchingEngine(cfg, scfg, p)
        uids = [eng.submit(pr, mx) for pr, mx in zip(prompts, budgets)]
        out = eng.run(max_steps=300)
        assert sorted(out) == sorted(uids)
        assert eng.prefill_cache_size == 1
        assert eng.decode_cache_size == 1
        results[fill_bound] = [np.asarray(out[u]) for u in uids]

    for got, ref in zip(results[True], results[False]):
        np.testing.assert_array_equal(got, ref)


# ------------------------------------------------------------- satellites ----
def test_generate_steps_below_one_raises():
    cfg, p = _smoke()
    sess = ServeSession(cfg, ServeConfig(max_seq=32), p)
    batch = jnp.ones((1, 4), jnp.int32)
    for steps in (0, -3):
        with pytest.raises(ValueError, match="steps"):
            sess.generate(batch, steps=steps)
    assert sess.generate(batch, steps=1).shape == (1, 1)


def test_page_pool_reserved_pages_tracks_unmapped_pressure():
    pool = PagePool(num_pages=8, page_size=4, max_slots=4,
                    max_pages_per_slot=4)
    assert pool.reserved_pages == 0
    assert pool.reserve(0, 10)                 # 3 pages, none mapped yet
    assert pool.reserved_pages == 3 and pool.in_use == 0
    assert pool.reserved_fraction() == pytest.approx(3 / 8)
    pool.ensure(0, 5)                          # maps 2 of the 3
    assert pool.reserved_pages == 3 and pool.in_use == 2
    assert pool.reserve(1, 16)                 # 4 more, still unmapped
    assert pool.reserved_pages == 7
    assert not pool.reserve(2, 8)              # 2 > 1 page of headroom
    pool.release(0)
    assert pool.reserved_pages == 4 and pool.in_use == 0


def test_engine_reports_page_reserved_next_to_occupancy():
    cfg, p = _smoke()
    scfg = ServeConfig(max_seq=32, prefill_chunk=4, max_slots=2,
                       paged_kv=True, page_size=4, num_pages=8)
    eng = ContinuousBatchingEngine(cfg, scfg, p)
    assert eng.page_reserved == 0.0 and eng.page_occupancy == 0.0
    eng.submit(_prompts(cfg, [6])[0], 2)       # needs 2 pages worst-case
    eng.step()                                 # admit + first chunk
    assert eng.page_reserved >= eng.page_occupancy > 0.0
    eng.run(max_steps=50)
    assert eng.page_reserved == 0.0 and eng.page_occupancy == 0.0

    contiguous = ContinuousBatchingEngine(cfg, ServeConfig(
        max_seq=32, prefill_chunk=4, max_slots=2), p)
    assert contiguous.page_reserved == 0.0     # non-paged: always 0
