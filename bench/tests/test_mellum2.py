"""The Mellum2 model module (``bench/models/mellum2.py``).

* At smoke size (``mellum2.tiny``), laid out in a root of its own with its
  configuration, cell, mix and the two expert metrics, it runs through
  ``run.run_cell`` and passes the check; the engine counts the routed rows
  and experts hit, and the two metrics read them with the trace's scopes
  and kernel times. The same served tokens fail the check against the
  float8 control and against references mutated to drop YaRN or one expert
  of each token's eight.
* Its layout is the program's, and its published values are the
  program's arch.
* Its kernel, step and expert costs at the published size equal numbers
  worked by hand.
"""
import functools
import json
import shutil

import jax
import pytest

import check
import engine_trace
import reference as R
import spec

CELL = "mellum2-12b-a2.5b.decode-long"
# the check's limits at smoke size, where 64 experts over a 128-wide
# residual stream tie often: the program reads max/mean 1.8-2.9 / 0.34-0.50
# on three seeds, the float8 control 5.1-5.5 / 2.6-3.0, the reference
# without YaRN 3.7 / 1.21 and with one expert dropped 4.5 / 1.68
LIMITS = {"max_logit_gap": 4.0, "mean_logit_gap": 0.8}


def mellum2_root(tmp_path):
    """A checkout's layout holding the tiny configuration, the model, the
    cell, the mix and the two expert metrics."""
    bench = tmp_path / "bench"
    for sub in ("configs", "models", "cells", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True)
    src = spec.load_cell(CELL)
    cfg = src.model.tiny(src.config)
    cfg["max_slots"] = 4
    cfg["engine"].update(max_seq=256, page_size=16, prefill_chunk=32)
    (bench / "configs" / "mellum2-12b-a2.5b.json").write_text(
        json.dumps(cfg))
    (bench / "cells" / f"{CELL}.json").write_text(json.dumps(
        {"prompt_tokens": [40, 100], "output_tokens": [20, 60],
         "limits": LIMITS}))
    shutil.copy(spec.BENCH / "models" / "mellum2.py", bench / "models")
    shutil.copy(spec.BENCH / "traffic" / "decode-long.json",
                bench / "traffic")
    for m in ("moe_ms.decode", "moe_expert_roofline"):
        shutil.copy(spec.BENCH / "metrics" / f"{m}.py", bench / "metrics")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "mellum2-12b-a2.5b",
                     "file": "bench/configs/mellum2-12b-a2.5b.json"}],
        "workloads": [{"name": CELL, "config": "mellum2-12b-a2.5b",
                       "traffic": "decode-long", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "moe_ms.decode", "unit": "ms"},
                      {"name": "moe_expert_roofline", "unit": "%"}]}))
    return tmp_path


def _mutated(M, params, s, **patch):
    """The module's ``logit_gaps`` with module functions replaced, traced
    afresh (a new function object: jit's trace cache is keyed by it); and
    what was replaced."""
    saved = {k: getattr(M, k) for k in (*patch, "_gaps")}
    for k, v in patch.items():
        setattr(M, k, v)
    inner = saved["_gaps"].__wrapped__
    M._gaps = jax.jit(lambda *a, **k: inner(*a, **k),
                      static_argnames=("s", "prec"))
    return functools.partial(M.logit_gaps, params, s), saved


def test_mellum2_runs_correct_and_its_mutations_do_not(tmp_path):
    import run as bench_run
    c = spec.load_cell(CELL, root=mellum2_root(tmp_path))
    M = c.model
    seen = {}

    def after(gaps, finished, req_of, vocab, seed=2**31 + 7):
        toks = check.control_tokens(gaps, finished, req_of, seed, vocab)
        seen["fp8"] = check.compare(gaps, toks, req_of, seed, vocab,
                                    LIMITS)[:2]
        params, s = gaps.args
        experts = M._experts
        for name, patch in (
                ("no_yarn", {"_rope_yarn":
                             lambda x, pos, s: R._rope(x, pos, s.theta)}),
                ("dropped", {"_experts": lambda s, prec, x, m: experts(
                    s._replace(top_k=s.top_k - 1), prec, x, m)})):
            g, saved = _mutated(M, params, s, **patch)
            seen[name] = check.compare(g, finished, req_of, seed, vocab,
                                       LIMITS)[:2]
            for k, v in saved.items():
                setattr(M, k, v)

    def on_trace(run, ev):
        seen["run"] = run

    r = bench_run.run_cell(c, 2**31 + 7, 3.0, True, require_tpu=False,
                           after_check=after, on_trace=on_trace)
    assert r["correct"] is True, r["check"]
    for name in ("fp8", "no_yarn", "dropped"):
        ok, numbers = seen[name]
        assert not ok and numbers["mean_logit_gap"]["value"] > \
            LIMITS["mean_logit_gap"], (name, numbers)

    # the engine's counters: every active slot's token routes to 8 experts
    # in each of the 8 layers
    run = seen["run"]
    cnt = run.counters
    assert cnt["moe_rows"] == cnt["decode_active_slots"] * 8 * 8 > 0
    assert 0 < cnt["moe_experts_hit"] <= cnt["decode_steps"] * 8 * 64
    # a CPU run has no device trace: both metrics stay out of the line
    assert "moe_ms.decode" not in r["metrics"]
    assert "moe_expert_roofline" not in r["metrics"]
    # the readers, given what a v5e trace holds: the step's ops under the
    # mlp scope (where the expert layer is), and the gmm kernels' time
    steps = cnt["decode_steps"]
    run.engine = {"scopes": {"jit_decode_step": {"mlp": 0.25 * steps,
                                                 "attn": 1.0}},
                  "runs": {"jit_decode_step": steps}}
    run.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    gmm_s = 0.001 * steps
    run.trace = {"modules": {"jit_decode_step": [0.01] * steps},
                 "ops": {("jit_decode_step", "gmm"): (gmm_s, 3 * 8 * steps),
                         ("jit_decode_step", "fusion"): (5.0, steps)}}
    read = {m: spec.load_module(c.bench / "metrics" / f"{m}.py").read
            for m in ("moe_ms.decode", "moe_expert_roofline")}
    assert read["moe_ms.decode"](run) == pytest.approx(250.0)
    f, b = M.moe_expert_cost(cnt["moe_rows"], cnt["moe_experts_hit"],
                             run.sizes)
    least = max(f / 197e12, b / 819e9) / steps          # per step
    assert read["moe_expert_roofline"](run) == pytest.approx(
        100 * least / 0.001)
    # a run without expert counters reads nothing
    run.counters = {"decode_steps": steps}
    assert read["moe_ms.decode"](run) is None
    assert read["moe_expert_roofline"](run) is None


def test_layout_and_published_values_are_the_programs():
    import serve_loop
    from repro.models import transformer as T
    c = spec.load_cell(CELL)
    cfg = c.model.tiny(c.config)
    mcfg = serve_loop.model_config(cfg, c.model.PUBLISHED)
    params = jax.eval_shape(lambda: c.model.make_params(cfg, 5))
    want = jax.tree.map(lambda a: a.shape, T.lm_abstract(mcfg))
    assert jax.tree.map(lambda a: a.shape, params) == want
    # the nested values a field-by-field check cannot reach
    prog = serve_loop.model_config(c.config, c.model.PUBLISHED)
    s = c.model.sizes(c.config)
    assert (prog.moe.n_experts, prog.moe.top_k, prog.moe.capacity_factor,
            prog.d_ff) == (s.experts, s.top_k, 0.0, s.ff) == (64, 8, 0.0,
                                                              896)
    y = prog.rope_yarn
    assert (y.factor, y.original_max_position_embeddings, y.beta_fast,
            y.beta_slow, y.attention_factor) == s.yarn
    assert prog.rope_theta == s.theta == 500000.0
    kinds = {"sliding_attention": "local_moe", "full_attention": "global_moe"}
    assert prog.block_pattern * (prog.n_layers // len(prog.block_pattern)) \
        == tuple(kinds[k] for k in s.kinds)
    assert not prog.tie_embeddings and c.config["norm_topk_prob"]


def test_costs_at_published_size_by_hand():
    c = spec.load_cell(CELL)
    M, s = c.model, c.model.sizes(c.config)
    assert (s.d, s.h, s.hkv, s.dk, s.ff, s.layers, s.window) == (
        2304, 32, 4, 128, 896, 8, 1024)
    ctx = [3000, 600]
    calls = M.decode_kernel_calls(ctx, s)
    # 6 window layers attend 1024 + 600 rows, 2 full layers 3600
    assert [f for f, _ in calls] == [4 * 32 * 128 * 1624] * 3 + [
        4 * 32 * 128 * 3600] + [4 * 32 * 128 * 1624] * 3 + [
        4 * 32 * 128 * 3600]
    # K and V of those rows in bf16, q in and out for 2 slots
    assert calls[0][1] == 2 * 1624 * 4 * 128 * 2 + 2 * 2 * 32 * 128 * 2
    # per token and layer: q 2304*4096, k and v 2304*512 each, o
    # 4096*2304, router 2304*64, 8 experts of 3*2304*896
    per_layer = (2304 * 4096 * 2 + 2 * 2304 * 512 + 2304 * 64
                 + 8 * 3 * 2304 * 896)
    assert per_layer == 70_926_336
    want = (2 * (2.0 * per_layer * 8 + 2 * 2304 * 98304)
            + sum(f for f, _ in calls))
    assert M.decode_step_flops(ctx, s) == want
    # 128 rows on 56 (layer, expert) pairs: 6 d ff FLOPs a row; each hit
    # expert's 3 matrices in bf16, rows in (x twice, h once, bf16) and
    # out (gate and up at ff, down at d, float32)
    f, b = M.moe_expert_cost(128, 56, s)
    assert f == 6 * 2304 * 896 * 128 == 1_585_446_912
    assert b == 56 * 3 * 2304 * 896 * 2 + 128 * (
        (2 * 2304 + 896) * 2 + (2 * 896 + 2304) * 4)


def test_engine_trace_scopes_hold_the_expert_layer_under_mlp():
    """Ops under ``.../mlp/moe/moe_experts/...`` are given ``mlp``: the
    scope ``moe_ms.decode`` reads."""
    assert engine_trace.scope_of(
        "jit(decode_step)/layers/while/body/mlp/moe/moe_experts/gmm") \
        == "mlp"
