"""Prefix-sharing paged KV serving: scheduler hang regressions, warm
admission, copy-on-write under live sharers, page-table forks, n>1
parallel sampling, and the new ServeConfig knobs.

Companion to tests/test_paged_kv.py (allocator property tests + warm/cold
bit-parity live there). This file covers the engine- and scheduler-level
behavior the prefix cache introduces:

* the two PR-8 bugfixes — ``Scheduler.submit`` rejects a request whose
  worst-case reservation could never be satisfied (it used to park at the
  FIFO head failing ``reserve`` forever), and
  ``ContinuousBatchingEngine.run(max_steps=N)`` terminates within N
  iterations even when no iteration makes progress (``step()`` used to
  early-return without counting, spinning ``run`` forever);
* copy-on-write fires exactly when a slot writes into a page another live
  slot still references, and both streams stay bit-identical to the
  cache-off run;
* ``PagePool.fork`` shares full pages, eager-copies the partial tail, and
  respects reservation accounting;
* ``submit(n=k)`` fans one prompt into k distinct streams that reuse the
  prompt's cached pages when serialized;
* eviction (lru/fifo) reclaims only refcount-0 cached pages, and the
  ``prefix_cache``/``prefix_evict`` knobs validate at construction.
"""
import pytest
from jax import random

from repro.configs.base import ServeConfig
from repro.configs.registry import get_config
from repro.models import transformer as T
from repro.nn.module import Ctx
from repro.serve.engine import ContinuousBatchingEngine
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import PagePool, Request, Scheduler


def _model(arch="qwen2-1.5b"):
    cfg = get_config(arch, smoke=True)
    return cfg, T.lm_init(Ctx(random.key(0)), cfg)


def _prompt(cfg, n, seed=5):
    return list(map(int, random.randint(random.key(seed), (n,), 0,
                                        cfg.vocab_size)))


# ----------------------------------------------- hang regressions (bugs) ----
def test_scheduler_submit_rejects_pool_unservable_request():
    """A request needing more pages than the pool holds (or than one slot
    may map) used to queue forever: reserve failed at the FIFO head on
    every admit, blocking everything behind it. submit must reject it
    up front, mirroring the max_seq ValueError."""
    pool = PagePool(num_pages=4, page_size=4, max_slots=2,
                    max_pages_per_slot=8)
    sched = Scheduler(max_slots=2, max_seq=64, page_pool=pool)
    with pytest.raises(ValueError, match="could never be admitted"):
        sched.submit(list(range(17)), 4)       # 21 rows → 6 pages > 4 pool
    # per-slot cap binds even when the pool is large enough in total
    sched2 = Scheduler(2, 64, PagePool(32, 4, 2, 4))
    with pytest.raises(ValueError, match="could never be admitted"):
        sched2.submit(list(range(17)), 4)      # 6 pages > 4 per slot
    # a servable request still queues; the max_seq check still fires first
    sched.submit(list(range(10)), 4)           # 14 rows → 4 pages: fits
    with pytest.raises(ValueError, match="max_seq"):
        sched.submit(list(range(10)), 60)


def test_run_max_steps_terminates_on_zero_progress():
    """run(max_steps=N) must return within N iterations even when no
    iteration admits, prefills, or decodes — the state an unservable
    request at the FIFO head used to spin forever (step() early-returned
    without counting). Simulated by shrinking the pool under the engine
    and smuggling a request past submit validation."""
    cfg, p = _model()
    scfg = ServeConfig(max_seq=16, prefill_chunk=4, max_slots=2,
                       paged_kv=True, page_size=4, num_pages=4)
    eng = ContinuousBatchingEngine(cfg, scfg, p)
    small = PagePool(2, scfg.page_size, scfg.max_slots,
                     scfg.max_pages_per_slot)
    eng.pool = eng.scheduler.page_pool = small
    # 12 rows → 3 pages ≤ max_pages_per_slot but > the 2-page pool: admit
    # returns None forever, slots stay idle, nothing ever progresses
    eng.scheduler.queue.append(Request(0, list(range(9)), 3, None, None))
    assert eng.run(max_steps=25) == {}
    assert eng.scheduler.queue_depth == 1      # still queued — but we return


# ------------------------------------------------------- copy-on-write ----
def test_cow_fires_under_live_sharer_and_streams_stay_bit_identical():
    """Request B admits with A's prompt fully cached while A still holds
    the pages (refcount 2): B's 1-token tail re-score must copy the shared
    last page before writing, and both streams must match the cache-off
    run bit for bit."""
    cfg, p = _model()
    prompt = _prompt(cfg, 12)                  # 3 pages of 4 — page-aligned
    sp = SamplingParams(temperature=0.7, top_k=30, seed=9)

    def serve(prefix_cache):
        scfg = ServeConfig(max_seq=32, prefill_chunk=4, max_slots=2,
                           paged_kv=True, page_size=4, num_pages=16,
                           prefix_cache=prefix_cache)
        eng = ContinuousBatchingEngine(cfg, scfg, p, default_sampling=sp)
        ua = eng.submit(prompt, 10)
        eng.run(max_steps=5)                   # A prefilled, now decoding
        ub = eng.submit(prompt, 6)             # same prompt, A still live
        res = eng.run(max_steps=400)
        return res[ua], res[ub], eng

    wa, wb, weng = serve(True)
    ca, cb, ceng = serve(False)
    assert wa == ca and wb == cb
    assert weng.pool.cow_copies >= 1           # the shared-tail privatization
    assert ceng.pool.cow_copies == 0
    assert weng.prefilled_tokens < ceng.prefilled_tokens
    assert weng.pool.free_pages == 16          # drained: all refs dropped
    t = weng.request_times[1]                  # stamped per uid, in order
    assert t["submitted"] <= t["admitted"] <= t["first_token"] \
        <= t["finished"]


# ---------------------------------------------------------------- fork ----
def test_fork_shares_full_pages_and_copies_partial_tail():
    pool = PagePool(num_pages=12, page_size=4, max_slots=3,
                    max_pages_per_slot=4)
    assert pool.reserve(0, 12)
    pool.ensure(0, 10)                         # 3 pages, last one partial
    src_pages = pool.owned(0)
    copies = pool.fork(src=0, dst=1, rows=14, src_rows=10)
    assert [s for s, _ in copies] == [src_pages[2]]
    # full pages shared (refcount 2), tail copied into a private page
    assert pool.owned(1)[:2] == src_pages[:2]
    assert pool.owned(1)[2] not in src_pages
    assert pool.refcount[src_pages[0]] == pool.refcount[src_pages[1]] == 2
    assert pool.refcount[src_pages[2]] == 1
    # dst appends past the fork point without touching src's pages
    new, cow = pool.ensure_writable(1, 10, 14)
    assert not cow and len(new) == 1
    # src's own append into its partial tail needs no COW either
    _, cow = pool.ensure_writable(0, 10, 12)
    assert not cow
    pool.release(0)
    assert pool.refcount[src_pages[0]] == 1    # dst still holds the shares
    pool.release(1)
    assert pool.free_pages == 12


def test_fork_rejects_overcommit_and_busy_slot():
    pool = PagePool(num_pages=4, page_size=4, max_slots=3,
                    max_pages_per_slot=4)
    assert pool.reserve(0, 8)
    pool.ensure(0, 8)                          # 2 full pages
    assert pool.reserve(2, 8)                  # eats the remaining supply
    assert pool.fork(0, 1, 12, 8) is None      # would need 1 new page
    pool.release(2)
    copies = pool.fork(0, 1, 12, 8)            # aligned fork: no tail copy
    assert copies == []
    with pytest.raises(ValueError, match="already holds"):
        pool.fork(0, 1, 12, 8)


# --------------------------------------------------- n>1 parallel sampling ----
def test_submit_n_parallel_samples_share_the_prefilled_prefix():
    """submit(n=2) on a one-slot engine serializes through the prefix
    cache: stream 2 admits with stream 1's prompt pages cached, so the
    prompt is prefilled once plus a 1-token tail re-score — and the two
    streams draw from distinct seeds."""
    cfg, p = _model()
    prompt = _prompt(cfg, 12)
    scfg = ServeConfig(max_seq=32, prefill_chunk=4, max_slots=1,
                       paged_kv=True, page_size=4, num_pages=16)
    eng = ContinuousBatchingEngine(cfg, scfg, p)
    sp = SamplingParams(temperature=0.9, top_k=40, seed=7)
    uids = eng.submit(prompt, 5, sampling=sp, n=2)
    assert len(uids) == 2
    res = eng.run(max_steps=400)
    assert sorted(res) == sorted(uids)
    assert res[uids[0]] != res[uids[1]]        # seed + i: distinct streams
    assert eng.prefilled_tokens == 12 + 1      # one prefill + tail re-score
    with pytest.raises(ValueError, match="n must be"):
        eng.submit(prompt, 5, n=0)


# ------------------------------------------------------------- eviction ----
@pytest.mark.parametrize("evict", ["lru", "fifo"])
def test_eviction_reclaims_only_refcount_zero_cached_pages(evict):
    """With the free list dry, allocation evicts cached (refcount-0) pages
    in policy order; pinned pages are untouchable. The evicted prefix then
    misses on its next admission."""
    ps = 4
    pool = PagePool(num_pages=4, page_size=ps, max_slots=2,
                    max_pages_per_slot=4, evict=evict)
    toks_a, toks_b = [1] * ps, [2] * ps
    assert pool.reserve_prefix(0, ps, toks_a) == 0
    pool.ensure(0, ps)
    pool.commit_prefix(0, toks_a, ps)
    pool.release(0)
    assert pool.reserve_prefix(0, ps, toks_b) == 0
    pool.ensure(0, ps)
    pool.commit_prefix(0, toks_b, ps)
    pool.release(0)
    assert pool.cached_pages == 2
    assert sum(len(shard) for shard in pool._free_by) == 2
    # a 4-page reservation must drain the free list then evict both
    assert pool.reserve(1, 4 * ps)
    pool.ensure(1, 4 * ps)
    assert pool.evictions == 2 and pool.cached_pages == 0
    pool.release(1)
    # both prefixes were evicted: cold again
    assert pool.reserve_prefix(0, ps, toks_a) == 0
    assert pool.prefix_hit_rows == 0


def test_eviction_order_lru_vs_fifo():
    ps = 2
    for evict, survivor in (("lru", [3] * ps), ("fifo", [4] * ps)):
        pool = PagePool(3, ps, 2, 3, evict=evict)
        # register prefix A then B; release B first, then A — so lru order
        # (release) is B,A while fifo order (registration) is A,B
        assert pool.reserve_prefix(0, ps, [3] * ps) == 0   # A
        pool.ensure(0, ps)
        pool.commit_prefix(0, [3] * ps, ps)
        assert pool.reserve_prefix(1, ps, [4] * ps) == 0   # B
        pool.ensure(1, ps)
        pool.commit_prefix(1, [4] * ps, ps)
        pool.release(1)
        pool.release(0)
        assert pool.reserve(0, 2 * ps)         # needs 2 pages: 1 free + 1
        pool.ensure(0, 2 * ps)                 # evicted (B for lru, A fifo)
        assert pool.evictions == 1
        pool.release(0)
        # the surviving prefix still hits: skip = ps - 1 (tail re-score)
        skip = pool.reserve_prefix(1, ps, survivor)
        assert skip == ps - 1, (evict, skip)


# ------------------------------------------------------- config knobs ----
def test_serve_config_validates_prefix_knobs():
    with pytest.raises(ValueError, match="prefix_evict"):
        ServeConfig(max_seq=64, prefill_chunk=8, paged_kv=True, page_size=8,
                    prefix_evict="random")
    scfg = ServeConfig(max_seq=64, prefill_chunk=8, paged_kv=True,
                       page_size=8, prefix_cache=False, prefix_evict="fifo")
    assert not scfg.prefix_cache
    with pytest.raises(ValueError, match="evict"):
        PagePool(4, 4, 2, 4, evict="mru")


def test_prefix_cache_off_pool_never_caches():
    pool = PagePool(num_pages=4, page_size=4, max_slots=2,
                    max_pages_per_slot=4, prefix_cache=False)
    toks = [5] * 4
    assert pool.reserve_prefix(0, 4, toks) == 0
    pool.ensure(0, 4)
    assert pool.commit_prefix(0, toks, 4) == 0
    pool.release(0)
    assert pool.cached_pages == 0
    assert pool.reserve_prefix(0, 4, toks) == 0    # no warm admission
    assert pool.prefix_hit_rows == 0
