"""Paged KV-cache pool: allocator invariants, serving bit-parity, the
one-compiled-shape guarantee, and ServeConfig construction-time validation.

* PagePool property tests (hypothesis when available, plus an
  always-on seeded random walk): under arbitrary admit/extend/finish
  sequences — cold (``_drive``) and prefix-sharing (``_drive_prefix``,
  warm admissions, copy-on-write extends, cache commits) — every page's
  refcount equals the number of slot table rows mapping it, free +
  evictable + pinned pages partition the pool (so no page is freed or
  evicted while referenced, and the refcounts of free pages sum to 0), a
  slot never maps more pages than its reservation, a finished slot
  dereferences every page it held, and ``version`` increases
  monotonically with at most one bump per mutating call.
* Paged engine output is bit-identical to the contiguous engine AND to solo
  decode on the qwen2/gemma2/grok smoke configs — GQA, local-window,
  softcap, the paged split-KV kernel, multi-chunk ragged admissions, and a
  pool small enough that admission has to wait for released pages.
* Warm-vs-cold A/B: the same traffic served with the prefix cache on and
  off emits bit-identical token streams on qwen2/gemma2, while the warm
  engine computes strictly fewer prefill tokens.
* Trace counts for the paged prefill and decode steps stay at 1 across an
  engine lifetime of mixed-length traffic (the page table is a value, not
  a shape).
* Invalid ServeConfig shapes (prefill_chunk > max_seq, page_size not
  dividing prefill_chunk, undersized pool) raise at construction, not deep
  inside a cache write mid-request.
"""
import random as pyrandom

import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

from repro.configs.base import ServeConfig
from repro.configs.registry import get_config
from repro.models import transformer as T
from repro.nn.module import Ctx
from repro.serve.engine import ContinuousBatchingEngine, ServeSession
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import PagePool

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # bare env: seeded walk
    HAVE_HYPOTHESIS = False                           # below still runs


# ------------------------------------------------- allocator invariants ----
def _check_invariants(pool: PagePool, num_pages: int, max_slots: int):
    """The properties the refcounted page pool must never violate."""
    owned = [pool.owned(s) for s in range(max_slots)]
    flat = [p for o in owned for p in o]
    assert all(0 <= p < num_pages for p in flat)
    # refcount[p] == number of slot table rows mapping p
    counts: dict[int, int] = {}
    for p in flat:
        counts[p] = counts.get(p, 0) + 1
    for p in range(num_pages):
        assert pool.refcount[p] == counts.get(p, 0), (
            f"page {p}: refcount {pool.refcount[p]} != "
            f"{counts.get(p, 0)} mapping rows")
    # free, evictable and pinned pages partition the pool — no page is on
    # the free/evictable lists while any slot references it, and the
    # refcounts of allocatable pages sum to 0
    free = {p for shard in pool._free_by for p in shard}
    evictable = set(pool._evictable)
    # per-shard free lists hold only pages the shard owns
    for d, shard in enumerate(pool._free_by):
        assert all(pool.page_shard(p) == d for p in shard)
    assert not free & evictable
    assert not (free | evictable) & set(flat)
    assert sum(pool.refcount[p] for p in free | evictable) == 0
    assert len(free) + len(evictable) + len(set(flat)) == num_pages, (
        f"leak: {len(free)} free + {len(evictable)} evictable + "
        f"{len(set(flat))} pinned != {num_pages}")
    assert pool.free_pages == len(free) + len(evictable)
    for s, o in enumerate(owned):
        table_row = [int(p) for p in pool.table[s] if p >= 0]
        assert table_row == o, f"table/owned mismatch for slot {s}"
        # a slot never maps more pages than its reservation — warm
        # admissions included
        assert len(o) <= pool._reserved[s], (
            f"slot {s} maps {len(o)} pages > reservation "
            f"{pool._reserved[s]}")
    # quantized-KV scale bookkeeping: scale rows are allocated and recycled
    # WITH their page, never separately — every page off the free list
    # (mapped or evictable) holds exactly one live scale block, free pages
    # hold none, and the aggregate matches the free-list complement
    assert pool.live_scale_pages == num_pages - len(free), (
        f"scale leak: {pool.live_scale_pages} live scale pages != "
        f"{num_pages} - {len(free)} free")
    for p in range(num_pages):
        assert pool._scale_live[p] == (p not in free), (
            f"page {p}: scale_live={pool._scale_live[p]} but "
            f"free={p in free} — scales must ride their page")
    # every COW privatization copied its scale rows along with the data
    assert pool.scale_copies >= pool.cow_copies, (
        f"{pool.cow_copies} cow copies but only {pool.scale_copies} "
        "scale copies — a privatized page lost its scales")


def _drive(pool: PagePool, num_pages: int, max_slots: int, page_size: int,
           ops: list[tuple[int, int, int]]):
    """Interpret an arbitrary op sequence against the pool, checking the
    invariants after every step. ops: (kind, slot, amount) triples —
    kind 0 = admit (reserve `amount` rows), 1 = extend (ensure rows up to
    `amount` past what's backed), 2 = finish (release)."""
    max_rows = pool.max_pages_per_slot * page_size
    reserved_rows = [0] * max_slots                   # our model of the pool
    backed_rows = [0] * max_slots
    last_version = pool.version
    for kind, slot, amount in ops:
        slot %= max_slots
        v0 = pool.version
        if kind == 0 and not reserved_rows[slot]:
            rows = 1 + amount % max_rows
            if pool.reserve(slot, rows):
                reserved_rows[slot] = rows
        elif kind == 1 and reserved_rows[slot]:
            rows = min(backed_rows[slot] + 1 + amount % (2 * page_size),
                       reserved_rows[slot])
            pool.ensure(slot, rows)
            backed_rows[slot] = max(backed_rows[slot], rows)
            assert len(pool.owned(slot)) == pool.pages_for(backed_rows[slot])
        elif kind == 2 and reserved_rows[slot]:
            held = set(pool.owned(slot))
            released = set(pool.release(slot))
            assert released == held, "finished slot kept pages"
            assert not pool.owned(slot)
            assert pool.version - v0 <= 1, "release must batch its bump"
            reserved_rows[slot] = backed_rows[slot] = 0
        _check_invariants(pool, num_pages, max_slots)
        assert pool.version >= last_version, "version went backwards"
        assert pool.version - v0 <= 1, "more than one bump per call"
        last_version = pool.version


def _shared_prompt(n: int) -> list[int]:
    # one deterministic token stream all prefix-driver prompts prefix —
    # maximizing cache hits across the op sequence
    return [(7 * i + 3) % 997 for i in range(n)]


def _drive_prefix(pool: PagePool, num_pages: int, max_slots: int,
                  page_size: int, ops: list[tuple[int, int, int]]):
    """Engine-shaped op interpreter with prefix-cache admissions: prompts
    are prefixes of one shared stream (so admissions hit cached pages),
    extends go through ``ensure_writable`` + ``commit_prefix`` exactly like
    ``ContinuousBatchingEngine._prefill_one``, finishes release. Checks the
    refcount invariants, COW exclusivity of every write window, and
    version monotonicity after every op."""
    max_rows = pool.max_pages_per_slot * page_size
    prompt: list = [None] * max_slots
    reserved_rows = [0] * max_slots
    fill = [0] * max_slots
    last_version = pool.version
    for kind, slot, amount in ops:
        slot %= max_slots
        v0 = pool.version
        if kind == 0 and not reserved_rows[slot]:
            rows = 1 + amount % max_rows
            plen = max(1, rows - rows // 4)           # prompt + decode budget
            tokens = _shared_prompt(plen)
            skip = pool.reserve_prefix(slot, rows, tokens)
            if skip is not None:
                assert 0 <= skip <= max(0, plen - 1)
                assert len(pool.owned(slot)) * page_size >= skip
                reserved_rows[slot], prompt[slot] = rows, tokens
                fill[slot] = skip
        elif kind == 1 and reserved_rows[slot]:
            stop = min(fill[slot] + 1 + amount % (2 * page_size),
                       reserved_rows[slot])
            if stop > fill[slot]:
                pool.ensure_writable(slot, fill[slot], stop)
                # COW contract: after ensure_writable the whole write
                # window is exclusively owned
                for pi in range(fill[slot] // page_size,
                                -(-stop // page_size)):
                    page = int(pool.table[slot, pi])
                    assert pool.refcount[page] == 1, (
                        f"write window page {page} still shared")
                pool.commit_prefix(slot, prompt[slot],
                                   min(stop, len(prompt[slot])))
                fill[slot] = stop
        elif kind == 2 and reserved_rows[slot]:
            held = pool.owned(slot)
            released = pool.release(slot)
            assert released == held, "finished slot kept references"
            assert not pool.owned(slot)
            assert pool.version - v0 <= 1, "release must batch its bump"
            reserved_rows[slot] = fill[slot] = 0
            prompt[slot] = None
        _check_invariants(pool, num_pages, max_slots)
        assert pool.version >= last_version, "version went backwards"
        assert pool.version - v0 <= 1, "more than one bump per op"
        last_version = pool.version


def test_page_pool_random_walk_keeps_invariants():
    """Seeded stdlib-random walk — exercised even without hypothesis."""
    rng = pyrandom.Random(0)
    for trial in range(20):
        num_pages = rng.randint(1, 24)
        max_slots = rng.randint(1, 6)
        page_size = rng.choice([1, 2, 4, 8])
        mpps = rng.randint(1, max(1, num_pages))
        pool = PagePool(num_pages, page_size, max_slots, mpps)
        ops = [(rng.randint(0, 2), rng.randint(0, max_slots - 1),
                rng.randint(0, 64)) for _ in range(rng.randint(1, 60))]
        _drive(pool, num_pages, max_slots, page_size, ops)


if HAVE_HYPOTHESIS:
    @settings(max_examples=100, deadline=None)
    @given(st.integers(1, 24), st.integers(1, 6), st.sampled_from([1, 2, 4]),
           st.data())
    def test_page_pool_property_no_double_ownership_no_leaks(
            num_pages, max_slots, page_size, data):
        mpps = data.draw(st.integers(1, num_pages), label="max_pages_per_slot")
        pool = PagePool(num_pages, page_size, max_slots, mpps)
        ops = data.draw(st.lists(
            st.tuples(st.integers(0, 2), st.integers(0, max_slots - 1),
                      st.integers(0, 64)), max_size=60), label="ops")
        _drive(pool, num_pages, max_slots, page_size, ops)


def test_page_pool_prefix_random_walk_keeps_invariants():
    """Seeded walk over the prefix-sharing op set (warm admissions, COW
    extends, cache commits, evictions under pressure) — exercised even
    without hypothesis."""
    rng = pyrandom.Random(1)
    for trial in range(20):
        num_pages = rng.randint(2, 24)
        max_slots = rng.randint(1, 6)
        page_size = rng.choice([1, 2, 4, 8])
        mpps = rng.randint(1, max(1, num_pages))
        pool = PagePool(num_pages, page_size, max_slots, mpps,
                        evict=rng.choice(["lru", "fifo"]))
        ops = [(rng.randint(0, 2), rng.randint(0, max_slots - 1),
                rng.randint(0, 64)) for _ in range(rng.randint(1, 60))]
        _drive_prefix(pool, num_pages, max_slots, page_size, ops)


if HAVE_HYPOTHESIS:
    @settings(max_examples=100, deadline=None)
    @given(st.integers(2, 24), st.integers(1, 6), st.sampled_from([1, 2, 4]),
           st.sampled_from(["lru", "fifo"]), st.data())
    def test_page_pool_property_prefix_sharing_refcounts(
            num_pages, max_slots, page_size, evict, data):
        mpps = data.draw(st.integers(1, num_pages), label="max_pages_per_slot")
        pool = PagePool(num_pages, page_size, max_slots, mpps, evict=evict)
        ops = data.draw(st.lists(
            st.tuples(st.integers(0, 2), st.integers(0, max_slots - 1),
                      st.integers(0, 64)), max_size=60), label="ops")
        _drive_prefix(pool, num_pages, max_slots, page_size, ops)


def test_page_pool_version_bumps_only_on_table_mutation():
    """The engine keys its device page-table upload off ``version`` — a
    decode step that maps no new page must not force a host transfer."""
    pool = PagePool(num_pages=8, page_size=4, max_slots=2,
                    max_pages_per_slot=4)
    assert pool.reserve(0, 10)
    v0 = pool.version
    pool.ensure(0, 5)                                 # maps 2 pages
    assert pool.version == v0 + 1
    pool.ensure(0, 6)                                 # still 2 pages: no-op
    assert pool.version == v0 + 1
    pool.release(0)
    assert pool.version == v0 + 2
    assert pool.reserve(1, 4)
    pool.release(1)                                   # held nothing: no-op
    assert pool.version == v0 + 2


def test_page_pool_reservation_gates_allocation():
    pool = PagePool(num_pages=8, page_size=4, max_slots=4,
                    max_pages_per_slot=4)
    assert pool.reserve(0, 16)                        # 4 pages
    assert pool.reserve(1, 13)                        # 4 pages (ceil)
    assert not pool.reserve(2, 1)                     # pool fully committed
    with pytest.raises(ValueError, match="reservation"):
        pool.ensure(2, 4)                             # never reserved
    with pytest.raises(ValueError, match="exceed"):
        pool.ensure(0, 17)                            # beyond reservation
    assert pool.ensure(0, 9) == [0, 1, 2]             # 3 pages, on demand
    assert pool.ensure(0, 9) == []                    # idempotent
    pool.release(0)
    assert pool.reserve(2, 1)                         # freed commitment
    with pytest.raises(ValueError, match="already holds"):
        pool.reserve(2, 1)


# ------------------------------------------------------- serving parity ----
def _model(arch):
    cfg = get_config(arch, smoke=True)
    return cfg, T.lm_init(Ctx(random.key(0)), cfg)


def _prompts(cfg, lens, seed=10):
    return [list(map(int, random.randint(random.key(seed + i), (n,), 0,
                                         cfg.vocab_size)))
            for i, n in enumerate(lens)]


@pytest.mark.parametrize("arch,decode_kernel", [
    ("qwen2-1.5b", True),       # GQA + the paged split-KV kernel
    ("gemma2-2b", False),       # local/global alternation + attn softcap
    ("grok-1-314b", False),     # global softcap + MoE blocks
])
def test_paged_engine_bit_parity_with_contiguous_and_solo(arch,
                                                          decode_kernel):
    """The page pool is a memory-layout change, not a numerics change:
    the paged engine must emit exactly the contiguous engine's tokens
    (which PR 2 pinned to solo decode). num_pages is deliberately below
    max_slots * max_pages_per_slot, so admission also has to wait for
    pages released by finished requests."""
    cfg, p = _model(arch)
    prompts = _prompts(cfg, [5, 13, 3, 11, 7])  # chunk=4 ≪ longest prompt
    budgets = [4, 6, 3, 5, 6]

    scfg_paged = ServeConfig(max_seq=48, prefill_chunk=4, max_slots=3,
                             paged_kv=True, page_size=4, num_pages=14,
                             decode_kernel=decode_kernel, decode_kv_block=16)
    assert scfg_paged.num_pages < 3 * scfg_paged.max_pages_per_slot
    paged = ContinuousBatchingEngine(cfg, scfg_paged, p)
    uids = [paged.submit(pr, mx) for pr, mx in zip(prompts, budgets)]
    results = paged.run(max_steps=400)
    assert sorted(results) == sorted(uids)
    assert paged.pool.free_pages == scfg_paged.num_pages  # all returned

    scfg_cont = ServeConfig(max_seq=48, prefill_chunk=4, max_slots=3,
                            decode_kernel=decode_kernel, decode_kv_block=16)
    cont = ContinuousBatchingEngine(cfg, scfg_cont, p)
    cuids = [cont.submit(pr, mx) for pr, mx in zip(prompts, budgets)]
    cresults = cont.run(max_steps=400)

    alone = ServeSession(cfg, ServeConfig(max_seq=48), p)
    for uid, cuid, pr, mx in zip(uids, cuids, prompts, budgets):
        ref = np.asarray(alone.generate(jnp.asarray([pr], jnp.int32),
                                        steps=mx))[0]
        np.testing.assert_array_equal(np.asarray(results[uid]),
                                      np.asarray(cresults[cuid]))
        np.testing.assert_array_equal(np.asarray(results[uid]), ref)


def test_paged_engine_one_compiled_shape_across_mixed_traffic():
    """Mirror of PR 2's prefill_cache_size assertion, extended to decode:
    across mixed-length admissions, ragged tails, recycles, and page-table
    growth, the paged engine compiles exactly one prefill shape and one
    decode shape — the table rides along as a value, never a shape."""
    cfg, p = _model("qwen2-1.5b")
    scfg = ServeConfig(max_seq=32, prefill_chunk=4, max_slots=2,
                       paged_kv=True, page_size=2, num_pages=24)
    eng = ContinuousBatchingEngine(cfg, scfg, p)
    for pr, mx in zip(_prompts(cfg, [9, 2, 14, 1, 6], seed=30),
                      [3, 1, 5, 2, 4]):
        eng.submit(pr, mx)
    results = eng.run(max_steps=400)
    assert len(results) == 5
    assert eng.prefill_cache_size == 1
    assert eng.decode_cache_size == 1
    assert eng.pool.free_pages == scfg.num_pages


def test_paged_engine_pool_pressure_serializes_but_serves_all():
    """A pool that fits one worst-case request at a time still drains the
    queue — reservations serialize admissions instead of deadlocking."""
    cfg, p = _model("qwen2-1.5b")
    scfg = ServeConfig(max_seq=16, prefill_chunk=4, max_slots=3,
                       paged_kv=True, page_size=4, num_pages=4)
    eng = ContinuousBatchingEngine(cfg, scfg, p)
    uids = [eng.submit(pr, 3) for pr in _prompts(cfg, [9, 8, 10], seed=40)]
    results = eng.run(max_steps=400)
    assert sorted(results) == sorted(uids)
    assert all(len(results[u]) == 3 for u in uids)
    assert eng.pool.free_pages == 4


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "gemma2-2b"])
def test_warm_vs_cold_streams_bit_identical(arch):
    """Prefix cache on/off A/B on the smoke archs: the same traffic —
    a shared prefix re-served under several suffixes, including a fully
    cached page-aligned re-serve (the 1-token tail re-score path) — must
    emit bit-identical token streams, while the warm engine computes
    exactly the uncached suffix tokens. Sampling is stochastic: per-slot
    keys fold the cache *position*, so skipping cached rows cannot shift
    a stream."""
    cfg, p = _model(arch)
    shared = _prompts(cfg, [12], seed=77)[0]          # 3 pages of 4, aligned
    tails = _prompts(cfg, [7, 4, 12], seed=80)
    sp = SamplingParams(temperature=0.8, top_k=20, seed=123)

    def serve(prefix_cache):
        scfg = ServeConfig(max_seq=48, prefill_chunk=4, max_slots=1,
                           paged_kv=True, page_size=4, num_pages=24,
                           prefix_cache=prefix_cache)
        eng = ContinuousBatchingEngine(cfg, scfg, p, default_sampling=sp)
        uids = [eng.submit(shared, 4)]                # cold: seeds the cache
        uids += [eng.submit(shared + t, 4) for t in tails]
        uids.append(eng.submit(shared, 4))            # fully cached re-serve
        results = eng.run(max_steps=600)
        assert sorted(results) == sorted(uids)
        return [results[u] for u in uids], eng

    warm, weng = serve(True)
    cold, ceng = serve(False)
    assert warm == cold
    # cold computes every prompt token; warm only the uncached suffixes
    # plus the fully-cached request's 1-token tail re-score
    assert ceng.prefilled_tokens == 12 + 19 + 16 + 24 + 12
    assert weng.prefilled_tokens == 12 + 7 + 4 + 12 + 1
    assert weng.pool.prefix_hit_rows > 0
    assert ceng.pool.prefix_hit_rows == 0
    # all references dropped after drain; cached pages stay allocatable
    assert weng.pool.free_pages == 24
    assert weng.pool.cached_pages > 0


# ------------------------------------------------- construction checks ----
def test_serve_config_rejects_prefill_chunk_above_max_seq():
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServeConfig(max_seq=32, prefill_chunk=64)


def test_serve_config_default_prefill_chunk_resolves_to_max_seq():
    assert ServeConfig(max_seq=48).prefill_chunk == 48
    assert ServeConfig(max_seq=100_000).prefill_chunk == 2048


def test_serve_config_rejects_page_size_not_dividing_prefill_chunk():
    with pytest.raises(ValueError, match="page_size"):
        ServeConfig(max_seq=64, prefill_chunk=8, paged_kv=True, page_size=3)
    # page_size is unused (hence unvalidated) without paged_kv
    ServeConfig(max_seq=64, prefill_chunk=8, page_size=3)


def test_serve_config_rejects_undersized_pool():
    with pytest.raises(ValueError, match="num_pages"):
        ServeConfig(max_seq=64, prefill_chunk=8, paged_kv=True, page_size=8,
                    num_pages=4)                      # < 8 pages for one slot


def test_serve_config_paged_defaults_cover_all_slots():
    scfg = ServeConfig(max_seq=60, prefill_chunk=8, paged_kv=True,
                       page_size=8, max_slots=3)
    assert scfg.max_pages_per_slot == 8               # ceil(60 / 8)
    assert scfg.num_pages == 24


def test_serve_session_rejects_paged_config():
    cfg, p = _model("qwen2-1.5b")
    with pytest.raises(NotImplementedError, match="paged"):
        ServeSession(cfg, ServeConfig(max_seq=32, prefill_chunk=4,
                                      paged_kv=True, page_size=4), p)


# ----------------------------------------------------- paged kernel op ----
def test_paged_decode_kernel_matches_jnp_paged_attention():
    """Direct numeric check of the scalar-prefetch paged kernel against the
    jnp paged row, across GQA + window + softcap."""
    from repro.core.attention import paged_attention
    from repro.kernels.consmax_decode.ops import consmax_decode_paged_op

    b, H, hkv, dk, ps, P = 3, 4, 2, 32, 8, 10
    key = random.key(0)
    q = random.normal(random.fold_in(key, 1), (b, 1, H, dk)) * 0.3
    kp = random.normal(random.fold_in(key, 2), (P, ps, hkv, dk))
    vp = random.normal(random.fold_in(key, 3), (P, ps, hkv, dk))
    table = jnp.asarray([[3, 1, -1, -1], [5, 0, 2, 7], [9, -1, -1, -1]],
                        jnp.int32)
    index = jnp.asarray([12, 27, 3])
    beta = jnp.linspace(0.5, 2.5, H)
    gamma = jnp.full((H,), 100.0)
    params = {"beta": beta, "gamma": gamma}
    for window, softcap in ((0, 0.0), (6, 0.0), (0, 30.0)):
        ref = paged_attention(q, kp[None], vp[None], 0, table, index,
                              jnp.ones((b,), jnp.int32),
                              norm_kind="consmax", norm_params=params,
                              window=window, softcap=softcap, merged=True)
        got = consmax_decode_paged_op(q, kp[None], vp[None], 0, table,
                                      index + 1, beta, gamma, window=window,
                                      softcap=softcap, merged=True,
                                      scale=1.0)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32), atol=1e-5)


# ------------------------------------------- layer-indexed paged kernels ----
# (H, hkv, dk, pool dtype, window): the packed-DMA decode path (qwen2-1.5b),
# the BlockSpec path (granite-3-2b), an int8 pool with its scale pools, and
# mellum2-12b-a2.5b's 1024-row window
LAYER_CASES = {"packed_qwen2": (12, 2, 128, "bfloat16", 0),
               "blockspec_granite": (32, 8, 64, "bfloat16", 0),
               "int8_scales": (12, 2, 128, "int8", 0),
               "window_mellum2": (32, 4, 128, "bfloat16", 1024)}


def _stacked_pools(case, n_layers=3, ps=128, npg=10, b=2):
    """Three layers of distinct pools, a shuffled table and the per-slot
    fills; quantized pools come with their scale pools."""
    from repro.kernels import cache_layout as CL
    H, hkv, dk, dtype, window = LAYER_CASES[case]
    P = b * npg + 1
    key = random.key(5)
    pools, scales = [], {}
    for name, i in (("k", 1), ("v", 2)):
        x = random.normal(random.fold_in(key, i), (n_layers, P, ps, hkv, dk))
        if dtype == "int8":
            x, scales[f"{name}_scale"] = CL.quantize_kv(
                x, CL.kv_cache_dtype(dtype))
        pools.append(x.astype(CL.kv_cache_dtype(dtype)))
    perm = np.random.default_rng(0).permutation(P - 1) + 1
    table = jnp.asarray(perm[:b * npg].reshape(b, npg), jnp.int32)
    lengths = jnp.asarray([1200, 700], jnp.int32)[:b]
    beta = jnp.linspace(0.5, 2.5, H)
    gamma = jnp.full((H,), 100.0)
    return (*pools, scales, table, lengths, beta, gamma, H, dk, window)


@pytest.mark.parametrize("case", sorted(LAYER_CASES))
def test_layer_indexed_paged_decode_equals_the_one_layer_call(case):
    """The paged decode kernel on the stacked pools at layer ``l`` is
    bit-identical to the same kernel on ``pool[l]`` alone."""
    from repro.kernels.consmax_decode.kernel import consmax_decode_paged
    kp, vp, scales, table, lengths, beta, gamma, H, dk, window = (
        _stacked_pools(case))
    q = random.normal(random.key(6), (table.shape[0], H, dk)
                      ).astype(jnp.bfloat16)
    kw = dict(window=window, interpret=True)
    for l in (1, 2):
        got = consmax_decode_paged(q, kp, vp, l, table, lengths, beta, gamma,
                                   **scales, **kw)
        one = consmax_decode_paged(
            q, kp[l][None], vp[l][None], 0, table, lengths, beta, gamma,
            **{n: s[l][None] for n, s in scales.items()}, **kw)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(one, np.float32))


@pytest.mark.parametrize("case", sorted(LAYER_CASES))
def test_layer_indexed_paged_prefill_equals_the_one_layer_call(case):
    """The paged prefill kernel on the stacked pools at layer ``l`` is
    bit-identical to the same kernel on ``pool[l]`` alone."""
    from repro.kernels.consmax_prefill.kernel import consmax_prefill_paged
    kp, vp, scales, table, lengths, beta, gamma, H, dk, window = (
        _stacked_pools(case))
    c = 16
    q = random.normal(random.key(7), (table.shape[0], c, H, dk)
                      ).astype(jnp.bfloat16)
    index, chunk = lengths - c, jnp.full_like(lengths, c)
    kw = dict(window=window, bq=8, interpret=True)
    l = 2
    got = consmax_prefill_paged(q, kp, vp, l, table, index, chunk, beta,
                                gamma, **scales, **kw)
    one = consmax_prefill_paged(
        q, kp[l][None], vp[l][None], 0, table, index, chunk, beta, gamma,
        **{n: s[l][None] for n, s in scales.items()}, **kw)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(one, np.float32))


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_rows_minor_paged_kernels_match_paged_attention(kernel):
    """At granite-3-2b's widths (8 bfloat16 KV heads of dk 64, pages of 128
    rows) the paged kernels read each layer through the pools' rows-minor
    view, as compiled for the TPU, and give the jnp ``paged_attention`` of
    that layer (equal on a CPU); another layer's pools, or K read as V,
    give outputs more than 2 apart."""
    from repro.core.attention import paged_attention
    from repro.kernels import cache_layout as CL
    from repro.kernels.consmax_decode.kernel import consmax_decode_paged
    from repro.kernels.consmax_prefill.kernel import consmax_prefill_paged
    kp, vp, _, table, lengths, beta, gamma, H, dk, _ = (
        _stacked_pools("blockspec_granite"))
    assert CL.page_view(kp.dtype, kp.shape[2:]) == CL.ROWS_MINOR
    b = table.shape[0]
    c = 1 if kernel == "decode" else 16
    q = (random.normal(random.key(10), (b, c, H, dk)) * dk ** -0.5
         ).astype(jnp.bfloat16)
    index, chunk = lengths - c, jnp.full_like(lengths, c)
    params = {"beta": beta, "gamma": gamma}
    for l in (0, 2):
        want = paged_attention(q, kp, vp, l, table, index, chunk,
                               norm_kind="consmax", norm_params=params,
                               merged=True)
        if kernel == "decode":
            got = consmax_decode_paged(q[:, 0], kp, vp, l, table, lengths,
                                       beta, gamma, scale=1.0,
                                       interpret=True)[:, None]
        else:
            got = consmax_prefill_paged(q, kp, vp, l, table, index, chunk,
                                        beta, gamma, scale=1.0, bq=8,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=1e-5)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_decode_step_writes_each_layer_row_into_its_own_pool(kv_dtype):
    """One paged decode step through ``lm_apply`` (three layers, the pools
    carried through the layer scan): layer ``l`` writes exactly its own
    pool's row at each slot's index, every other row of every layer is
    bit-unchanged, and every layer's index advances by one."""
    cfg = get_config("qwen2-1.5b", smoke=True, n_layers=3)
    params = T.lm_init(Ctx(random.key(0)), cfg)
    b, P, ps = 2, 6, 4
    caches = T.init_paged_caches(cfg, b, P, ps, kv_dtype=kv_dtype)
    key = random.key(1)
    caches = {blk: {"attn": {
        name: (a if name == "index" else
               random.normal(random.fold_in(key, i), a.shape).astype(a.dtype))
        for i, (name, a) in enumerate(leaves["attn"].items())}}
        for blk, leaves in caches.items()}
    index = jnp.asarray([5, 2], jnp.int32)
    caches = T.set_slot_index(T.set_slot_index(caches, 0, 5), 1, 2)
    table = jnp.asarray([[3, 1, -1], [0, 4, -1]], jnp.int32)
    tokens = jnp.asarray([[7], [11]], jnp.int32)
    _, new, _ = T.lm_apply(params, cfg, tokens=tokens, caches=caches,
                           positions=index[:, None], merged=True,
                           page_table=table, decode_kernel=True)
    written = {(int(table[s, int(index[s]) // ps]), int(index[s]) % ps)
               for s in range(b)}
    for name in caches["b0"]["attn"]:
        old = np.asarray(caches["b0"]["attn"][name])
        got = np.asarray(new["b0"]["attn"][name])
        if name == "index":
            np.testing.assert_array_equal(got, old + 1)
            continue
        rows = (old != got).reshape(old.shape[:4] + (-1,)).any(-1)
        rows = rows.reshape(old.shape[0], P, ps, -1).any(-1)
        for l in range(cfg.n_layers):
            changed = {tuple(map(int, r)) for r in np.argwhere(rows[l])}
            assert changed == written, (name, l, changed)
        # each layer's own projection: no layer's rows landed in another's
        pages, page_rows = zip(*sorted(written))
        fresh = got[:, list(pages), list(page_rows)]
        assert all(not np.array_equal(fresh[l], fresh[l + 1])
                   for l in range(cfg.n_layers - 1)), name


def test_rows_minor_tiles_give_the_row_major_partial():
    """A head's (dk, rows) K^T / V^T tiles, as the paged kernels read a
    pool XLA stores rows-minor, give the partial of its (rows, dk) tiles."""
    from repro.kernels import cache_layout as CL
    key = random.key(8)
    g, rows, hkv, dk = 4, 16, 8, 64
    q = random.normal(random.fold_in(key, 1), (g, dk)).astype(jnp.bfloat16)
    k, v = (random.normal(random.fold_in(key, i), (1, rows, hkv, dk)
                          ).astype(jnp.bfloat16) for i in (2, 3))
    mask = jnp.arange(rows)[None, :] < jnp.asarray([[11], [16], [3], [0]])
    beta, gamma = jnp.full((g, 1), 1.5), jnp.full((g, 1), 100.0)
    kw = dict(scale=1.0, softcap=0.0, merged=True)
    for h in (0, 5):
        want = CL.consmax_partial(
            q, CL.kv_head(k, None, 0, h, q.dtype),
            CL.kv_head(v, None, 0, h, q.dtype), mask, beta, gamma, **kw)
        kt, vt = (jnp.moveaxis(x, 1, -1) for x in (k, v))
        views = (CL.ROWS_MINOR, CL.ROWS_MINOR)
        (kh, rows_minor), (vh, _) = (
            CL.page_head(x, None, 0, h, q.dtype, *views) for x in (kt, vt))
        assert rows_minor
        got = CL.consmax_partial(q, kh, vh, mask, beta, gamma,
                                 rows_minor=True, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("c,index,lengths", [
    (1, [5, 0, 17], [1, 0, 1]),              # decode, one slot inactive
    (8, [3, 0, 12], [8, 5, 2]),              # chunks across page edges
    (8, [14, 16, 40], [8, 8, 8])])           # into unmapped columns
def test_page_writes_equal_row_writes(c, index, lengths):
    """Writing whole pages (the write for a pool XLA stores rows-minor)
    leaves the stacked pool bit-identical to the row scatter: fresh rows
    land at their pages, pad rows and unmapped columns write nothing."""
    from repro.core.attention import _paged_cache_write, _paged_page_write
    n, P, ps, hkv, dk = 3, 12, 8, 2, 16
    key = random.key(9)
    pool = random.normal(random.fold_in(key, 1), (n, P, ps, hkv, dk))
    new = random.normal(random.fold_in(key, 2), (3, c, hkv, dk))
    table = jnp.asarray([[3, 1, -1, 7], [0, 4, 9, -1], [2, 5, 6, 8]],
                        jnp.int32)
    index, lengths = (jnp.asarray(x, jnp.int32) for x in (index, lengths))
    rows = _paged_cache_write(pool, new, 1, index, lengths, table)
    pages = _paged_page_write(pool, new, 1, index, lengths, table)
    np.testing.assert_array_equal(np.asarray(pages), np.asarray(rows))
    assert not np.array_equal(np.asarray(rows), np.asarray(pool))
