"""The generator: every seed runs the same work, a set holds exactly its
distributions' quantiles, and an open-loop window offers the cell's rate."""
import numpy as np

import loadgen

CHAT = {"loop": "open",
        "arrivals": {"dist": "gamma", "shape": 0.25},
        "prompt": {"dist": "lognormal", "median": 1024, "sigma": 1.0,
                   "clip_from_cell": "prompt_clip"},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "clip_from_cell": "output_clip"}}
CELL = {"rate_per_s": 4.0, "prompt_clip": [64, 4096],
        "output_clip": [16, 512]}
LONG = {"loop": "closed",
        "prompt": {"dist": "uniform", "bounds_from_cell": "p"},
        "output": {"dist": "uniform", "bounds_from_cell": "o"}}
LONG_CELL = {"p": [2048, 4096], "o": [1024, 4096]}


def test_a_set_holds_the_quantiles_in_golden_ratio_order():
    for kind in loadgen.MULT:
        u = loadgen.ordered_quantiles(7, 40, kind)
        assert sorted(u) == sorted((np.arange(40) + 0.5) / 40)
    a = loadgen.request_set(CHAT, CELL, 0, 40)
    assert a == loadgen.request_set(CHAT, CELL, 0, 40)
    # the largest prompt and the largest answer go to different requests,
    # and large prompts are spread through the set, not bunched
    p = [r.prompt_len for r in a]
    o = [r.output_len for r in a]
    assert int(np.argmax(p)) != int(np.argmax(o))
    top = sorted(range(40), key=lambda j: -p[j])[:8]
    assert max(np.diff(sorted(top))) < 12
    assert all(64 <= x <= 4096 for x in p) and all(16 <= x <= 512 for x in o)
    assert np.median(p) == 1024 or abs(np.median(p) - 1024) < 40


def test_open_loop_window_offers_the_rate():
    for rate, secs in ((0.4, 51.0), (0.8, 51.0), (1.2, 51.0), (4.0, 10.0)):
        n = loadgen.open_count(rate, secs)
        rs = loadgen.request_set(CHAT, CELL, 100, n, seconds=secs)
        due = np.array([r.due_s for r in rs])
        assert len(rs) == round(rate * secs)
        assert due[0] == 0.0 and np.all(np.diff(due) >= 0)
        assert due[-1] < secs
        gaps = np.append(np.diff(due), secs - due[-1])
        assert abs(gaps.sum() - secs) < 1e-9
        # bursty: Gamma(0.25) gaps, coefficient of variation near 2
        assert 1.3 < gaps.std() / gaps.mean() < 2.5


def test_closed_loop_first_wave_is_part_served():
    fresh = loadgen.request_set(LONG, LONG_CELL, 0, 24)
    wave = loadgen.request_set(LONG, LONG_CELL, 0, 24, part_served=True)
    for f, w in zip(fresh, wave):
        served = w.prompt_len - f.prompt_len
        assert 0 <= served < f.output_len
        assert w.output_len == f.output_len - served >= 1
        assert w.prompt_len + w.output_len == f.prompt_len + f.output_len
        assert w.due_s is None
    share = [(w.prompt_len - f.prompt_len) / f.output_len
             for f, w in zip(fresh, wave)]
    assert 0.4 < np.mean(share) < 0.6
    assert all(2048 <= r.prompt_len <= 4096 and 1024 <= r.output_len <= 4096
               for r in fresh)


def test_prompt_tokens_are_seeded():
    a = loadgen.prompt_tokens(5, 3, 100, 1000)
    assert a == loadgen.prompt_tokens(5, 3, 100, 1000)
    assert a != loadgen.prompt_tokens(6, 3, 100, 1000)
    assert a != loadgen.prompt_tokens(2**33 + 5, 3, 100, 1000)
    assert max(a) < 1000
