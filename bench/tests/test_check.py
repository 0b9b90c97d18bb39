"""The output check against a broken timed path, and its control.

Each test drives a whole run at smoke size on the CPU (the look for a chip
skipped, the kernels interpreted) and reads ``correct``. The limits here
are set, as the cells' are on the chip, between the program's readings and
the control's at this size (bench/tests, 4 seeds x 2 mixes): program
max_logit_gap <= 0.030 and mean_logit_gap <= 0.00048; the float8 control
max_logit_gap >= 0.30 and mean_logit_gap >= 0.0125.
"""
import functools

import jax
import pytest

import check
import loadgen
import serve_loop
import tiny

LIMITS = (0.12, 0.003)
CELLS = ["qwen2-1.5b.decode-long", "granite-3-2b.chat-bursty"]


def _bend(engine, attr, bend):
    """Rebuild the jitted step ``attr`` with ``bend(out, caches_in)``
    applied inside it (the caches stay donated, as in the engine)."""
    step = getattr(engine, attr).__wrapped__
    setattr(engine, attr, jax.jit(
        lambda p, c, *a: bend(step(p, c, *a), c), donate_argnums=(1,)))


def decode_keeps_state(engine):
    """A decode step that returns its state unchanged."""
    _bend(engine, "_decode", lambda out, old: (out[0], old))


def prefill_keeps_state(engine):
    """A prefill step that returns its state unchanged."""
    _bend(engine, "_prefill", lambda out, old: (out[0], old))


def decode_alters_token(engine):
    """A token altered where it is produced."""
    v = engine.cfg.vocab_size
    _bend(engine, "_decode", lambda out, old: ((out[0] + 1) % v, out[1]))


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    c = tiny.cell(name, LIMITS)
    r = tiny.run(c, 2**31 + 7)
    assert r["correct"] is True, r["check"]
    if c.traffic["loop"] == "open":     # the window's whole set is due in it
        assert r["attempted"] == loadgen.open_count(c.cell["rate_per_s"], 3.0)
    assert list(r["check"])[0] == "max_logit_gap"


@pytest.mark.parametrize("fault", [decode_keeps_state, prefill_keeps_state,
                                   decode_alters_token])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault):
    r = tiny.run(tiny.cell(name, LIMITS), 11, hook=fault)
    assert r["correct"] is False, r["check"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The float8 reference in the program's place, judged by the same
    ``check.compare`` at the same limits, comes out not correct."""
    import run as bench_run
    c = tiny.cell(name, LIMITS)
    for seed in (1, 2, 3):
        params, eng = bench_run.build(c, seed)
        r = serve_loop.Runner(eng, c.traffic, c.cell, seed)
        r.warm(float(c.traffic.get("warm_seconds", 0)))
        r.window(3.0)
        fin = {u: list(t) for u, t in eng.results.items()}
        gaps = functools.partial(c.model.logit_gaps, params,
                                 c.model.sizes(c.config))
        v = eng.cfg.vocab_size
        ok, numbers, _ = check.compare(gaps, fin, r.req_of, seed, v,
                                       c.cell["limits"])
        assert ok is True, numbers
        ctl = check.control_tokens(gaps, fin, r.req_of, seed, v)
        ok, numbers, _ = check.compare(gaps, ctl, r.req_of, seed, v,
                                       c.cell["limits"])
        assert ok is False, numbers
        eng.caches = None
