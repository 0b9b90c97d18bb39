"""The serving engine's own telemetry: ``engine.counters`` agree with what
the scheduler and the page pool did, ``engine.request_times`` stamps each
request's events in order, ``engine.step`` and its phases appear in a
profiler trace as nested spans, and the named scopes reach the compiled
decode step's HLO without renaming its module or its ops.
"""
import jax
from jax import random

from repro.configs.base import ServeConfig
from repro.configs.registry import get_config
from repro.models import transformer as T
from repro.nn.module import Ctx
from repro.serve.engine import ContinuousBatchingEngine

CHUNK = 8


def _engine(prefix_cache=False):
    cfg = get_config("qwen2-1.5b", smoke=True)
    params = T.lm_init(Ctx(random.key(0)), cfg)
    scfg = ServeConfig(max_slots=2, max_seq=64, prefill_chunk=CHUNK,
                       paged_kv=True, page_size=4, num_pages=32,
                       prefix_cache=prefix_cache)
    return ContinuousBatchingEngine(cfg, scfg, params)


def _prompt(eng, n, seed):
    return list(map(int, random.randint(random.key(seed), (n,), 0,
                                        eng.cfg.vocab_size)))


def test_counters_agree_with_the_scheduler():
    eng = _engine()
    lens, outs = [13, 5, 20, 9], [6, 1, 4, 3]
    uids = [eng.submit(_prompt(eng, n, i), m)
            for i, (n, m) in enumerate(zip(lens, outs))]
    steps = 0
    while eng.scheduler.has_work():
        eng.step()
        steps += 1
    c = eng.counters
    assert c["steps"] == steps
    # every request's first token comes from its last prefill chunk, the
    # rest one per decode step it was served in
    assert sum(len(eng.results[u]) for u in uids) == len(uids) + \
        c["decode_active_slots"]
    assert c["prefill_chunks"] == sum(-(-n // CHUNK) for n in lens)
    assert c["host_syncs"] == c["decode_steps"] + len(uids)
    assert 0 < c["decode_steps"] <= steps
    assert c["cow_copies"] == eng.pool.cow_copies == 0


def test_decode_kv_page_counters_follow_slot_lengths():
    """``decode_kv_pages`` sums the live pages of the slots each decode
    step serves; ``decode_kv_grid_pages`` is max_slots times the most any
    of them holds — the decode kernel's fetched pages against its grid."""
    eng = _engine()
    ps, want = eng.scfg.page_size, {"pages": 0, "grid": 0}
    decode_once = eng._decode_once

    def counted(decoding):
        live = [-(-(st.filled + len(st.generated)) // ps)
                for _, st in decoding]
        want["pages"] += sum(live)
        want["grid"] += eng.scfg.max_slots * max(live)
        return decode_once(decoding)

    eng._decode_once = counted
    for i, (n, m) in enumerate([(13, 6), (3, 9), (20, 4)]):
        eng.submit(_prompt(eng, n, i), m)
    eng.run()
    c = eng.counters
    assert c["decode_kv_pages"] == want["pages"] > 0
    assert c["decode_kv_grid_pages"] == want["grid"] >= c["decode_kv_pages"]
    assert c["decode_kv_grid_pages"] <= (c["decode_steps"] * eng.scfg.max_slots
                                         * eng.scfg.max_pages_per_slot)


def test_table_uploads_follow_pool_versions():
    eng = _engine()
    eng.submit(_prompt(eng, 6, 1), 12)
    eng.submit(_prompt(eng, 9, 2), 7)
    while eng.scheduler.has_work():
        before = eng.counters["table_uploads"]
        current = eng._table_version == eng.pool.version
        version = eng.pool.version
        eng.step()
        if current and eng.pool.version == version:
            # no mutation since the last upload: the table is reused
            assert eng.counters["table_uploads"] == before
    uploads = eng.counters["table_uploads"]
    # each upload reads a pool version no earlier upload read
    assert 1 <= uploads <= eng.pool.version + 1
    assert uploads < eng.counters["decode_steps"]


def test_cow_copies_counted_in_a_prefix_cache_run():
    eng = _engine(prefix_cache=True)
    prompt = _prompt(eng, 16, 3)                  # 4 whole pages
    ua = eng.submit(prompt, 10)
    eng.run(max_steps=4)                          # A prefilled, decoding
    ub = eng.submit(prompt, 6)                    # warm: tail re-score COW
    eng.run()
    assert eng.pool.cow_copies >= 1
    assert eng.counters["cow_copies"] == eng.pool.cow_copies
    assert len(eng.results[ua]) == 10 and len(eng.results[ub]) == 6


def test_request_times_are_in_order():
    eng = _engine()
    uids = [eng.submit(_prompt(eng, n, n), m)
            for n, m in ((11, 3), (4, 1), (17, 5))]
    eng.run()
    assert set(eng.request_times) == set(uids)
    for u in uids:
        t = eng.request_times[u]
        assert t["submitted"] <= t["admitted"] <= t["first_token"] \
            <= t["finished"]


def _host_spans(path):
    from jax._src.lib import _profile_data
    data = _profile_data.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out += [(e.start_ns, e.end_ns, e.name, i, dict(e.stats))
                        for e in line.events
                        if e.name.startswith("engine.")]
    return out


def _parent(span, spans):
    """The innermost other span on the same line that holds ``span``."""
    s, e, _, line, _ = span
    holders = [p for p in spans if p is not span and p[3] == line
               and p[0] <= s and e <= p[1]]
    return min(holders, key=lambda p: p[1] - p[0])[2] if holders else None


def test_profiler_trace_holds_the_engine_spans_nested(tmp_path):
    import glob
    eng = _engine(prefix_cache=True)
    prompt = _prompt(eng, 16, 7)
    jax.profiler.start_trace(str(tmp_path))
    eng.submit(prompt, 4)
    eng.run(max_steps=3)
    eng.submit(prompt, 3)                         # warm: a COW copy
    eng.submit(_prompt(eng, 5, 8), 1)             # done at its first token
    eng.run()
    jax.profiler.stop_trace()
    spans = _host_spans(glob.glob(f"{tmp_path}/**/*.xplane.pb",
                                  recursive=True)[0])
    parents = {}
    for sp in spans:
        parents.setdefault(sp[2], set()).add(_parent(sp, spans))
    assert parents["engine.step"] == {None}
    for name in ("engine.admit", "engine.prefill", "engine.decode",
                 "engine.record", "engine.finish"):
        assert parents[name] == {"engine.step"}, name
    assert parents["engine.first_token"] == {"engine.prefill"}
    assert parents["engine.decode_drain"] == {"engine.decode"}
    assert parents["engine.cow_copy"] <= {"engine.prefill", "engine.decode"}
    assert parents["engine.table_upload"] <= {"engine.prefill",
                                              "engine.decode"}
    steps = [sp for sp in spans if sp[2] == "engine.step"]
    assert len(steps) == eng.counters["steps"]
    assert {"step_num"} <= set(steps[0][4])
    prefill = next(sp for sp in spans if sp[2] == "engine.prefill")
    assert {"uid", "slot"} <= set(prefill[4])
    finish = next(sp for sp in spans if sp[2] == "engine.finish")
    assert {"uid", "slot"} <= set(finish[4])


def test_decode_step_hlo_carries_the_scopes():
    eng = _engine()
    text = eng.compile_step("decode").as_text()
    assert text.startswith("HloModule jit_decode_step")
    for scope in ("embed", "layers", "attn_proj", "kv_cache", "attn", "mlp",
                  "unembed_sample"):
        assert f"/{scope}/" in text, scope
