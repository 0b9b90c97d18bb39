"""trace_reduce.py against a recorded slice of a v5e trace.

``testdata/granite_chat_slice.json.gz`` is 0.4 s of a traced
granite-3-2b.chat-bursty window on one TPU v5 lite, in the form
``trace_reduce.read`` returns (long HLO type text cut from the op names).
Read by hand: it holds one whole prefill run and one whole decode run, each
with one kernel op per layer (40), inside a scan's ``while`` op.
"""
import gzip
import json
from pathlib import Path

import numpy as np

import trace_reduce as T

DATA = Path(__file__).resolve().parents[1] / "testdata"


def load():
    return json.loads(gzip.decompress(
        (DATA / "granite_chat_slice.json.gz").read_bytes()))


def test_busy_is_the_union_of_ops():
    ev = load()
    lo, hi = ev["window"]
    s = T.summarize(ev)
    # independent count on a 1 us grid
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for a, b, _ in ev["devices"]["/device:TPU:0"]["ops"]:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            grid[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    assert abs(s["window_s"] - 0.4) < 1e-9
    assert abs(s["busy_s"] - grid.sum() * 1e-6) < 2e-4
    assert 0 < s["busy_s"] <= s["window_s"]


def test_program_runs_and_kernels():
    s = T.summarize(load())
    assert len(s["modules"]["jit_prefill_chunk_step"]) == 1
    assert len(s["modules"]["jit_decode_step"]) == 1
    ops = s["ops"]
    # the whole runs hold 40 kernel ops each; the slice's edges cut into a
    # second run of each program, whose ops inside the window also count
    assert ops[("jit_decode_step", "consmax_decode_paged_op")][1] >= 40
    assert ops[("jit_prefill_chunk_step", "consmax_prefill_paged_op")][1] >= 40
    assert not any(k.endswith("/while") for k, _ in s["device_ops"])


def test_idle_gaps_are_attributed_to_host_spans():
    ev = load()
    s = T.summarize(ev)
    lo, hi = ev["window"]
    busy = T.union([(a, b) for a, b, _ in
                    ev["devices"]["/device:TPU:0"]["ops"]], lo, hi)
    idle = sum(b - a for a, b in T.gaps(busy, lo, hi)) / 1e9
    assert abs(idle + s["busy_s"] - s["window_s"]) < 1e-9
    names = {n for n, _ in s["idle_gaps"]}
    assert names <= {"untracked"} | {n for *_, n in ev["spans"]}
    assert s["idle_gaps"] == sorted(s["idle_gaps"], key=lambda g: -g[1])


def test_op_names_and_owners():
    assert T.op_name("%consmax_decode_paged_op.10 = f32[1] custom-call()") \
        == "consmax_decode_paged_op"
    assert T.op_name("%fusion.158.remat_uncompressed = bf16[2] copy(x)") \
        == "fusion"
    assert T.module_name("jit_decode_step(1234)") == "jit_decode_step"
    ops = [(0, 1, "a"), (5, 6, "b"), (11, 12, "c")]
    mods = [(4, 7, "m1"), (10, 20, "m2")]
    assert T.owners(ops, mods) == ["", "m1", "m2"]


def test_read_finds_window_and_spans_in_a_real_xplane(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("decode_step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = T.read(T.find_xplane(str(tmp_path)), ["decode_step"])
    assert ev["window"] is not None
    assert [n for *_, n in ev["spans"]] == ["decode_step"]
    # no device plane on the CPU: nothing to summarize, no zero reported
    assert T.summarize(ev) == {}
