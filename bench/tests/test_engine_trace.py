"""engine_trace.py: the engine's spans and named scopes, reduced.

``testdata/qwen2_chat_engine_slice.json.gz`` is 0.4 s of a traced window
of qwen2-1.5b under the chat-bursty mix at 0.96 req/s on one TPU v5 lite,
in the form ``engine_trace.read`` returns (long HLO type text cut from the
op names; each device's ``op_names`` kept for the ops in the slice). It
holds engine spans around a prompt's last chunk and the decode steps
beside it.
"""
import gzip
import json
from pathlib import Path

import pytest

import engine_report
import engine_trace as E
import readers
import serve_loop
import tiny
import trace_reduce as T
from test_readers import GRANITE

DATA = Path(__file__).resolve().parents[1] / "testdata"


def load():
    return json.loads(gzip.decompress(
        (DATA / "qwen2_chat_engine_slice.json.gz").read_bytes()))


def test_scope_of_names_the_innermost_scope():
    assert E.scope_of("jit(decode_step)/layers/while/body/kv_cache/"
                      "scatter") == "kv_cache"
    assert E.scope_of("jit(decode_step)/layers/while/body/attn/"
                      "jit(consmax_decode_paged_op)/pallas_call") == "attn"
    assert E.scope_of("jit(decode_step)/layers/while/body/add") == "layers"
    assert E.scope_of("jit(decode_step)/select_n") == "other"
    assert E.scope_of(None) == "unscoped"


def test_hlo_op_names_reads_each_instructions_metadata():
    text = ('  %fusion.3 = bf16[2]{0} fusion(%a), kind=kLoop, metadata='
            '{op_name="jit(f)/layers/kv_cache/dynamic_update_slice" '
            'source_file="x.py"}\n'
            '  ROOT %copy.1 = bf16[2]{0} copy(%fusion.3)\n'
            "  %add.2 = f32[] add(%p, %q), metadata={op_name="
            '"jit(f)/caches[\\"k\\"]/add"}\n')
    assert E.hlo_op_names(text) == {
        "fusion.3": "jit(f)/layers/kv_cache/dynamic_update_slice",
        "add.2": 'jit(f)/caches[\\"k\\"]/add'}
    assert E.instr_name("%fusion.3 = bf16[2]{0:T(...)") == "fusion.3"


def _span(s, e, name, line=0):
    return (s, e, name, line, {})


def test_self_time_is_the_span_less_its_children():
    spans = [_span(0, 100, "engine.step"), _span(0, 10, "engine.admit"),
             _span(10, 90, "engine.decode"),
             _span(20, 85, "engine.decode_drain"),
             _span(30, 40, "gc", line=1),     # another thread: no child
             _span(120, 150, "engine.step")]
    t = E.span_table(spans, 0, 200)
    assert t["engine.step"]["count"] == 2
    assert abs(t["engine.step"]["self_s"] - 40e-9) < 1e-15
    assert abs(t["engine.decode"]["self_s"] - 15e-9) < 1e-15
    assert abs(t["gc"]["self_s"] - 10e-9) < 1e-15
    assert [round(x * 1e9) for x in E.step_host_s(spans, 0, 200)] == [35, 30]
    # spans cut by the window's edges are left out
    assert "engine.decode" not in E.span_table(spans, 15, 200)


def test_gaps_go_to_the_innermost_engine_span():
    ev = load()
    s = E.summarize(ev, ev["spans"])
    gaps = s["idle_gaps"]
    assert gaps and gaps == sorted(gaps, key=lambda g: -g[1])
    names = {n for *_, n, _, _ in ev["engine"]}
    for name, secs in gaps:
        assert name in names | set(serve_loop.SPAN_NAMES) | {"untracked"}
    # the engine's spans lie inside the harness's engine_step span, so no
    # gap the engine spans cover is put down to the harness's phase
    lo, hi = ev["window"]
    busy = T.union([(a, b) for a, b, _ in
                    ev["devices"]["/device:TPU:0"]["ops"]], lo, hi)
    spans = [(a, b, n) for a, b, n, *_ in ev["engine"]]
    for g in T.gaps(busy, lo, hi):
        inner = T.attribute(g, spans)
        if inner != "untracked":
            assert T.attribute(g, spans + [tuple(x) for x in ev["spans"]]) \
                == inner


def test_scope_table_sums_to_the_module_op_time():
    ev = load()
    scopes = E.summarize(ev)["scopes"]
    per_op = T.summarize(ev, top=10_000)["device_ops"]
    for mod, table in scopes.items():
        total = sum(v for k, v in per_op if k.startswith(f"{mod}/"))
        assert abs(sum(table.values()) - total) < 1e-9, mod
    dec = scopes["jit_decode_step"]
    assert dec.get("kv_cache", 0) > 0 and dec.get("attn", 0) > 0


def test_new_readers_on_the_slice_and_on_a_cpu_run():
    ev = load()
    eng = E.summarize(ev, ev["spans"])
    run = E.EngineRunData(
        stats={}, rec=serve_loop.Records(), trace=T.summarize(ev),
        sizes=GRANITE, peaks={}, max_slots=24,
        counters={"decode_steps": 2, "decode_active_slots": 30},
        request_times={0: {"submitted": 1.0, "admitted": 1.05},
                       1: {"submitted": 2.0}}, window=(0.5, 2.5),
        engine=eng)
    got = {n: readers.load(n)(run) for n in engine_report.METRICS}
    assert abs(got["decode_active_share"] - 62.5) < 1e-9
    # uid 1 is still queued at the window's close: its wait so far, 500 ms
    assert abs(got["admit_wait_p50_ms"] - 275.0) < 1e-6
    assert 0 < got["engine_host_ms.chat"] == got["engine_host_ms.decode"]
    assert 0 < got["kv_cache_ms.chat"] == got["kv_cache_ms.decode"]
    # the harness's RunData of a CPU run (no device trace, no engine
    # fields): nothing to read
    cpu = readers.RunData(stats={}, rec=serve_loop.Records(), trace={},
                          sizes=GRANITE, peaks={}, max_slots=24)
    for n in engine_report.METRICS:
        assert readers.load(n)(cpu) is None, n


def test_report_reads_the_engine_around_the_harness_run():
    cell = tiny.cell("granite-3-2b.chat-bursty", (0.12, 0.003))
    window = serve_loop.Runner.window
    out = engine_report.report(cell, 2147483923, 3.0, require_tpu=False)
    assert serve_loop.Runner.window is window       # put back
    assert out["correct"]
    c = out["counters"]
    assert 0 < c["decode_steps"] <= c["steps"] and c["prefill_chunks"] > 0
    assert c["host_syncs"] >= c["decode_steps"]
    m = out["metrics"]
    assert 0 < m["decode_active_share"] <= 100
    assert m["admit_wait_p50_ms"] >= 0
    # no device plane on the CPU: nothing for the trace's readers
    assert m["kv_cache_ms.chat"] is None and m["engine_host_ms.chat"] is None
    assert set(out["span_cost_us"]) == {"off", "on"}


@pytest.mark.parametrize("name,metric", [
    ("qwen2-1.5b.decode-long", "decode_active_share"),
    ("granite-3-2b.chat-bursty", "admit_wait_p50_ms")])
def test_traced_run_reports_the_engine_metrics(name, metric):
    """``run.py``'s own traced run keeps the engine's counters and request
    times around its window: the cell's engine metrics that need no device
    trace read on the CPU too; those of the trace give None there and are
    left out of the line."""
    r = tiny.run(tiny.cell(name, (0.12, 0.003)), 2147483951, trace=True)
    assert r["correct"], r["check"]
    assert r["metrics"][metric]["value"] >= 0
    assert not {"engine_host_ms.chat", "engine_host_ms.decode",
                "kv_cache_ms.chat", "kv_cache_ms.decode"} & set(r["metrics"])
