"""costs.py and the dense model's step counts against hand counts at one
small shape, and against the counts recorded at both configurations'
published sizes before the counts moved into the model module."""
import json
import types

import pytest

import costs
import spec
import tiny

DENSE = tiny.model("dense")
S = DENSE.Sizes(d=8, h=4, hkv=2, dk=2, ff=16, vocab=10, layers=3,
                qkv_bias=False, theta=10000.0, eps=1e-6)
PK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_decode_kernel_hand_count():
    # two active slots attending 3 and 5 rows; per (head, row): q.k and
    # p.v, dk multiply-adds each, 2 FLOPs per multiply-add
    flops, nbytes = costs.decode_kernel([3, 5], S)
    assert flops == 4 * (3 + 5) * (2 * 2 + 2 * 2)
    # K and V rows at 2 bytes, 2 kv heads x dk 2 each; q in and out per slot
    assert nbytes == (3 + 5) * 2 * (2 * 2) * 2 + 2 * 2 * (4 * 2) * 2
    # a window of 4 rows: the 5-row slot attends its last 4
    flops, nbytes = costs.decode_kernel([3, 5], S, window=4)
    assert flops == 4 * (3 + 4) * (2 * 2 + 2 * 2)
    assert nbytes == (3 + 4) * 2 * (2 * 2) * 2 + 2 * 2 * (4 * 2) * 2


def test_prefill_kernel_hand_count():
    # 3 tokens at rows 4, 5, 6 attend 5, 6 and 7 rows
    pairs = sum(range(5, 8))
    flops, nbytes = costs.prefill_kernel(4, 3, S)
    assert flops == 4 * pairs * (2 * 2 + 2 * 2)
    assert nbytes == 7 * 2 * (2 * 2) * 2 + 3 * 2 * (4 * 2) * 2


@pytest.mark.parametrize("start,n,window", [(4, 3, 2), (0, 5, 3), (2, 9, 4),
                                            (10, 6, 100), (0, 1, 1)])
def test_prefill_kernel_window_counts_each_pair(start, n, window):
    pairs = sum(min(r + 1, window) for r in range(start, start + n))
    rows = len(range(max(0, start - window + 1), start + n))
    flops, nbytes = costs.prefill_kernel(start, n, S, window=window)
    assert flops == 4 * pairs * (2 * 2 + 2 * 2)
    assert nbytes == rows * 2 * (2 * 2) * 2 + n * 2 * (4 * 2) * 2


def test_steps_hand_count():
    per_layer = (8 * 4 * 2 + 2 * 8 * 2 * 2 + 4 * 2 * 8 + 3 * 8 * 16)
    lin = 2 * per_layer * 3
    assert DENSE._linear_flops_per_token(S) == lin
    unembed = 2 * 8 * 10
    attn = 3 * 4 * (3 + 5) * 4 * 2
    assert DENSE.decode_step_flops([3, 5], S) == 2 * (lin + unembed) + attn
    pre = 3 * lin + 3 * 4 * sum(range(5, 8)) * 4 * 2
    assert DENSE.prefill_step_flops(4, 3, False, S) == pre
    assert DENSE.prefill_step_flops(4, 3, True, S) == pre + unembed
    # one kernel call per layer, each the kernel's whole count
    assert DENSE.decode_kernel_calls([3, 5], S) == [
        costs.decode_kernel([3, 5], S)] * 3
    assert DENSE.prefill_kernel_calls(4, 3, S) == [
        costs.prefill_kernel(4, 3, S)] * 3


# Counts at the published sizes, recorded from the formulas as they stood
# in costs.py before the step counts moved into bench/models/dense.py:
# per layer (FLOPs, bytes) of the kernels, and whole-step model FLOPs.
PUBLISHED = {
    "qwen2-1.5b": {
        "decode": {(3000, 4500, 7000): ((89088000.0, 14866432.0),
                                        11755880448.0),
                   (100,): ((614400.0, 108544.0), 3104342016.0),
                   (2049,) * 24: ((302137344.0, 50503680.0),
                                  82551177216.0)},
        "prefill": {(0, 512, False): ((806879232.0, 3670016.0),
                                      1364233027584.0),
                    (512, 300, True): ((1221120000.0, 2674688.0),
                                       820775534592.0),
                    (3584, 512, True): ((12081168384.0, 7340032.0),
                                        1680379871232.0)}},
    "granite-3-2b": {
        "decode": {(3000, 4500, 7000): ((118784000.0, 29720576.0),
                                        19951554560.0),
                   (100,): ((819200.0, 212992.0), 5099499520.0),
                   (2049,) * 24: ((402849792.0, 100909056.0),
                                  137715548160.0)},
        "prefill": {(0, 512, False): ((1075838976.0, 5242880.0),
                                      2534114590720.0),
                    (512, 300, True): ((1628160000.0, 4120576.0),
                                       1524945530880.0),
                    (3584, 512, True): ((16108224512.0, 12582912.0),
                                        3135611351040.0)}},
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_counts_at_published_sizes_are_the_recorded_ones(name):
    cfg = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    s = DENSE.sizes(cfg)
    want = PUBLISHED[name]
    for ctx, (kernel, step) in want["decode"].items():
        assert DENSE.decode_kernel_calls(list(ctx), s) == [kernel] * s.layers
        assert DENSE.decode_step_flops(list(ctx), s) == step
    for (start, n, final), (kernel, step) in want["prefill"].items():
        assert DENSE.prefill_kernel_calls(start, n, s) == \
            [kernel] * s.layers
        assert DENSE.prefill_step_flops(start, n, final, s) == step


def test_kernel_counts_take_any_widths():
    """A kernel's count needs only the widths it is called at."""
    w = types.SimpleNamespace(h=4, hkv=2, dk=2)
    assert costs.decode_kernel([3, 5], w) == costs.decode_kernel([3, 5], S)


def test_least_time_names_its_bound():
    assert costs.least_time(1000.0, 1.0, PK) == (10.0, "compute")
    assert costs.least_time(1.0, 1000.0, PK) == (100.0, "bandwidth")


def test_peaks_refuse_an_unknown_device():
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    try:
        costs.peaks("cpu")
    except KeyError:
        return
    raise AssertionError("an unknown device kind must be an error")
