"""Every per-layer metric has a reader, and the readers read a recorded
v5e trace slice (see test_trace_reduce) into shares that stay in range,
and into the readings they gave before the costs moved into the model
module."""
import json

import pytest

import costs
import readers
import serve_loop
import spec
import tiny
import trace_reduce
from test_trace_reduce import load

DENSE = tiny.model("dense")
GRANITE = DENSE.Sizes(d=2048, h=32, hkv=8, dk=64, ff=8192, vocab=49155,
                      layers=40, qkv_bias=False, theta=10000.0, eps=1e-6)


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
                          max_slots=16, model=DENSE)
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


# Readings of the same slice and logs, recorded before the step and kernel
# counts moved from costs.py into bench/models/dense.py and the kernel ops
# came to be named by each reader.
RECORDED = {
    "qwen2-1.5b": {"decode_kernel_roofline": 6.91339302459398,
                   "prefill_kernel_roofline": 4.090013727752589,
                   "mfu.decode": 0.11426397910138973,
                   "mfu.prefill": 4.153927108935816},
    "granite-3-2b": {"decode_kernel_roofline": 19.726319690740553,
                     "prefill_kernel_roofline": 7.888579726299696,
                     "mfu.decode": 0.18985723980610944,
                     "mfu.prefill": 7.718349816436419},
}
SLICE = {"decode_step_ms": 133.748006, "prefill_step_ms": 134.64609199999998,
         "idle_share.chat": 4.092441000000003, "decode_occupancy": 56.25,
         "queue_wait_p50_ms": 12.0}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_readings_are_the_recorded_ones(name):
    cfg = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    rec = serve_loop.Records()
    rec.decode_log = [[1500] * 16, [900, 2100]]
    rec.prefill_log = [(512, 512, False), (0, 300, True)]
    run = readers.RunData(stats={"queue_wait_p50_ms": 12.0}, rec=rec,
                          trace=trace_reduce.summarize(load()),
                          sizes=DENSE.sizes(cfg),
                          peaks=costs.peaks("TPU v5 lite"), max_slots=16,
                          model=DENSE)
    for n, want in {**RECORDED[name], **SLICE}.items():
        # a step's least time is now a sum over its calls, not one call's
        # times the layers: the last bits may differ
        assert readers.load(n)(run) == pytest.approx(want, rel=1e-12), n


def test_nothing_to_read_gives_none():
    run = readers.RunData(stats={}, rec=serve_loop.Records(), trace={},
                          sizes=GRANITE, peaks=costs.peaks("TPU v5 lite"),
                          max_slots=16, model=DENSE)
    for n in ("decode_step_ms", "decode_kernel_roofline", "mfu.prefill",
              "idle_share.decode", "decode_occupancy"):
        assert readers.load(n)(run) is None, n
