"""costs.py against hand counts at one small shape."""
import costs
import reference

S = reference.Sizes(d=8, h=4, hkv=2, dk=2, ff=16, vocab=10, layers=3,
                    qkv_bias=False, theta=10000.0, eps=1e-6)
PK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_decode_kernel_hand_count():
    # two active slots attending 3 and 5 rows; per (head, row): q.k and
    # p.v, dk multiply-adds each, 2 FLOPs per multiply-add
    flops, nbytes = costs.decode_kernel([3, 5], S)
    assert flops == 4 * (3 + 5) * (2 * 2 + 2 * 2)
    # K and V rows at 2 bytes, 2 kv heads x dk 2 each; q in and out per slot
    assert nbytes == (3 + 5) * 2 * (2 * 2) * 2 + 2 * 2 * (4 * 2) * 2


def test_prefill_kernel_hand_count():
    # 3 tokens at rows 4, 5, 6 attend 5, 6 and 7 rows
    pairs = sum(range(5, 8))
    flops, nbytes = costs.prefill_kernel(4, 3, S)
    assert flops == 4 * pairs * (2 * 2 + 2 * 2)
    assert nbytes == 7 * 2 * (2 * 2) * 2 + 3 * 2 * (4 * 2) * 2


def test_steps_hand_count():
    per_layer = (8 * 4 * 2 + 2 * 8 * 2 * 2 + 4 * 2 * 8 + 3 * 8 * 16)
    lin = 2 * per_layer * 3
    assert costs.linear_flops_per_token(S) == lin
    unembed = 2 * 8 * 10
    attn = 3 * 4 * (3 + 5) * 4 * 2
    assert costs.decode_step_flops([3, 5], S) == 2 * (lin + unembed) + attn
    pre = 3 * lin + 3 * 4 * sum(range(5, 8)) * 4 * 2
    assert costs.prefill_step_flops(4, 3, False, S) == pre
    assert costs.prefill_step_flops(4, 3, True, S) == pre + unembed


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
