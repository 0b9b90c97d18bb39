"""Every per-layer metric has a reader, and the readers read a recorded
v5e trace slice (see test_trace_reduce) into shares that stay in range."""
import json

import costs
import readers
import reference
import serve_loop
import spec
import trace_reduce
from test_trace_reduce import load

GRANITE = reference.Sizes(d=2048, h=32, hkv=8, dk=64, ff=8192, vocab=49155,
                          layers=40, qkv_bias=False, theta=10000.0,
                          eps=1e-6)


def test_every_metric_has_a_reader():
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(readers.load(m["name"])), m["name"]


def test_readers_on_a_recorded_slice():
    rec = serve_loop.Records()
    # the slice's decode run had 16 slots at about 1.5k rows, its prefill
    # run one 512-token chunk at row 512 (what the harness logs per call)
    rec.decode_log = [[1500] * 16]
    rec.prefill_log = [(512, 512, False)]
    run = readers.RunData(stats={"queue_wait_p50_ms": 12.0}, rec=rec,
                          trace=trace_reduce.summarize(load()),
                          sizes=GRANITE, peaks=costs.peaks("TPU v5 lite"),
                          max_slots=16)
    got = {n: readers.load(n)(run) for n in (
        "decode_step_ms", "prefill_step_ms", "decode_kernel_roofline",
        "prefill_kernel_roofline", "mfu.decode", "mfu.prefill",
        "idle_share.chat", "decode_occupancy", "queue_wait_p50_ms")}
    assert 130 < got["decode_step_ms"] < 140        # read by hand: 133.7
    assert 130 < got["prefill_step_ms"] < 140       # read by hand: 134.6
    for k in ("decode_kernel_roofline", "prefill_kernel_roofline",
              "mfu.decode", "mfu.prefill", "idle_share.chat"):
        assert 0 < got[k] <= 100, (k, got[k])
    assert got["decode_occupancy"] == 100.0
    assert got["queue_wait_p50_ms"] == 12.0


def test_nothing_to_read_gives_none():
    run = readers.RunData(stats={}, rec=serve_loop.Records(), trace={},
                          sizes=GRANITE, peaks=costs.peaks("TPU v5 lite"),
                          max_slots=16)
    for n in ("decode_step_ms", "decode_kernel_roofline", "mfu.prefill",
              "idle_share.decode", "decode_occupancy"):
        assert readers.load(n)(run) is None, n
