"""True-positive + clean-pass tests for the serving-path static analysis.

Every lint rule and kernel contract check is exercised BOTH ways: a
deliberately seeded violation it must flag (a rule that only ever passes on
clean code is untested) and a clean case it must not flag — including the
real serving steps and the real kernel launches, which is the zero-findings
half the ``repro.launch.analyze`` CI gate relies on.
"""
import jax
import jax.numpy as jnp
import pytest
from jax import random

from repro.analysis.jaxpr_lint import (LAYOUT_PRIMS, QuantScaleContract,
                                       StepTarget, cache_sized_ops,
                                       iter_eqns, run_rules,
                                       vocab_sized_avals)
from repro.analysis.kernel_contracts import (BlockInfo, KernelLaunch,
                                             capture_launches, check_launch,
                                             check_scalar_prefetch,
                                             check_vmem, check_write_races,
                                             serving_launches)
from repro.analysis.trace_guard import TraceGuard
from repro.configs.base import ServeConfig
from repro.configs.registry import get_config
from repro.launch import analyze
from repro.models import transformer as T
from repro.nn.module import Ctx
from repro.serve.engine import ContinuousBatchingEngine

CACHE = jax.ShapeDtypeStruct((4, 4096, 1, 32), jnp.bfloat16)   # 524288 elems
CELLS = 4 * 4096 * 1 * 32


def _rules_fired(findings):
    return {f.rule for f in findings}


# ------------------------------------------------------------ jaxpr lint ----
def test_iter_eqns_reaches_pjit_and_scan_bodies():
    @jax.jit
    def inner(x):
        def body(c, _):
            return c.swapaxes(1, 2).swapaxes(1, 2), ()
        return jax.lax.scan(body, x, None, length=2)[0]
    jaxpr = jax.make_jaxpr(inner)(jnp.zeros(CACHE.shape, CACHE.dtype))
    prims = {e.primitive.name for e in iter_eqns(jaxpr)}
    assert "transpose" in prims            # inside scan inside pjit
    assert cache_sized_ops(jaxpr, CELLS, prims=("transpose",))


def test_layout_rule_flags_each_prim_and_spares_small_ops():
    def step(cache):
        t = cache.swapaxes(1, 2)                         # transpose
        p = jnp.pad(cache, ((0, 0), (0, 1), (0, 0), (0, 0)))   # pad
        c = cache.astype(jnp.float32)                    # convert
        small = jnp.zeros((8, 8)).T                      # under threshold
        return t, p, c, small
    jaxpr = jax.make_jaxpr(step)(CACHE)
    bad = cache_sized_ops(jaxpr, CELLS)
    assert {prim for prim, _ in bad} == {"transpose", "pad",
                                         "convert_element_type"}
    findings = run_rules(StepTarget("s", jaxpr, cache_cells=CELLS))
    # the cache-sized WIDENING astype is double-flagged on purpose: it is
    # both a layout materialization and a dequantized-full-cache HBM copy
    assert _rules_fired(findings) == {"no-cache-sized-layout-ops",
                                      "quant-scale-contract"}
    # raising the threshold above the cache size silences it
    assert not cache_sized_ops(jaxpr, CELLS * 8)


def test_layout_rule_ignores_pallas_kernel_bodies():
    """Per-block ops inside a Pallas kernel are VMEM compute, not an HBM
    cache materialization — the kernel-contracts layer owns those."""
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...].astype(jnp.float32)

    def step(x):
        return pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
            interpret=True)(x)
    jaxpr = jax.make_jaxpr(step)(jnp.zeros((1024, 1024), jnp.bfloat16))
    assert not cache_sized_ops(jaxpr, 1024 * 1024)


def test_vocab_rule_flags_logits_and_spares_tokens():
    def step(x):
        return jnp.zeros((4,), jnp.int32), x @ jnp.zeros((8, 512))
    jaxpr = jax.make_jaxpr(step)(jnp.zeros((4, 8)))
    t = StepTarget("s", jaxpr, vocab_size=512)
    findings = run_rules(t)
    assert _rules_fired(findings) == {"no-vocab-sized-outputs"}
    assert vocab_sized_avals(list(jaxpr.out_avals), 512) == [(4, 512)]
    # legacy logits steps (vocab_size=None) are exempt on purpose
    assert not run_rules(StepTarget("s", jaxpr))


def test_callback_rule_flags_debug_and_pure_callbacks():
    def dbg(x):
        jax.debug.print("x={}", x.sum())
        return x
    jaxpr = jax.make_jaxpr(dbg)(jnp.zeros((4,)))
    assert "no-host-callbacks" in _rules_fired(
        run_rules(StepTarget("s", jaxpr)))

    def pure(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
    jaxpr = jax.make_jaxpr(pure)(jnp.zeros((4,)))
    assert "no-host-callbacks" in _rules_fired(
        run_rules(StepTarget("s", jaxpr)))


def test_dtype_stability_rule_flags_upcast_and_arity_change():
    jaxpr = jax.make_jaxpr(lambda x: x)(jnp.zeros((4,)))
    up = StepTarget("s", jaxpr, cache_in=(CACHE,),
                    cache_out=(jax.ShapeDtypeStruct(CACHE.shape,
                                                    jnp.float32),))
    assert _rules_fired(run_rules(up)) == {"cache-dtype-stability"}
    arity = StepTarget("s", jaxpr, cache_in=(CACHE, CACHE),
                       cache_out=(CACHE,))
    assert _rules_fired(run_rules(arity)) == {"cache-dtype-stability"}
    assert not run_rules(StepTarget("s", jaxpr, cache_in=(CACHE,),
                                    cache_out=(CACHE,)))


def test_quant_scale_rule_flags_nonf32_scales_and_widening_convert():
    """Both violation halves of the quantized-KV contract: a scale leaf
    stored below fp32 (dtype-stable, so only this rule sees it) and a
    cache-sized widening convert — a dequantized full-cache HBM copy."""
    jaxpr = jax.make_jaxpr(lambda x: x)(jnp.zeros((4,)))
    qcache = jax.ShapeDtypeStruct(CACHE.shape, jnp.int8)
    bad_scale = jax.ShapeDtypeStruct((4, 4096, 1), jnp.bfloat16)
    t = StepTarget("s", jaxpr, cache_in=(qcache, bad_scale),
                   cache_out=(qcache, bad_scale), scale_leaves=(1,))
    findings = run_rules(t)
    assert _rules_fired(findings) == {"quant-scale-contract"}
    assert len(findings) == 2              # flagged on the way in AND out
    # a widening astype over the whole quantized cache = dequant in HBM
    wide = jax.make_jaxpr(lambda c: c.astype(jnp.float32))(qcache)
    t = StepTarget("s", wide, cache_cells=CELLS)
    assert "quant-scale-contract" in _rules_fired(run_rules(t))
    # the quantize write direction (narrowing) is the sanctioned path
    narrow = jax.make_jaxpr(
        lambda c: c.astype(jnp.int8))(jnp.zeros(CACHE.shape, jnp.float32))
    t = StepTarget("s", narrow, cache_cells=CELLS)
    assert not QuantScaleContract().check(t)


def test_quant_scale_rule_clean_on_real_int8_steps():
    """The zero-findings half on a REAL quantized config: the engine's
    decode + prefill jaxprs carry int8 K/V plus fp32 scale leaves and pass
    every rule — per-block VMEM dequant never materializes a cache-sized
    widened copy."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    params = T.lm_init(Ctx(random.key(0)), cfg)
    scfg = analyze._matrix(("bfloat16", "int8"))["contig_fused_bounded_int8"]
    assert scfg.kv_cache_dtype == "int8"
    eng = ContinuousBatchingEngine(cfg, scfg, params)
    targets = list(analyze._step_targets(cfg, scfg, eng))
    stepped = [t for t in targets if t.name in ("decode", "prefill")]
    assert stepped and all(t.scale_leaves for t in stepped), (
        "quantized step targets must carry scale-leaf indices")
    for target in targets:
        assert not run_rules(target), target.name


def test_real_serving_steps_lint_clean():
    """The gate's zero-findings half, on one fused contiguous config: the
    engine's real decode + prefill jaxprs pass every rule with the full
    LAYOUT_PRIMS set (incl. copy / convert_element_type)."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    params = T.lm_init(Ctx(random.key(0)), cfg)
    scfg = analyze._matrix()["contig_fused_bounded"]
    eng = ContinuousBatchingEngine(cfg, scfg, params)
    for target in analyze._step_targets(cfg, scfg, eng):
        assert tuple(LAYOUT_PRIMS) == ("transpose", "pad", "copy",
                                       "convert_element_type")
        assert not run_rules(target), target.name


# ------------------------------------------------------ kernel contracts ----
def _race_launch(semantics):
    # grid dim 1 never reaches the output index -> race iff 'parallel'
    return KernelLaunch(
        name="k", grid=(4, 8), dimension_semantics=semantics,
        out_blocks=[BlockInfo((128, 128), "float32", 128 * 128 * 4, "vmem",
                              index_map=lambda i, j: (i, 0))])


def test_write_race_flags_parallel_reduce_dim():
    bad = check_write_races(_race_launch(("parallel", "parallel")))
    assert bad and bad[0].rule == "parallel-write-race"
    assert bad[0].detail[0] == 1                     # the offending dim


def test_write_race_spares_arbitrary_reduce_dim_and_disjoint_writes():
    assert not check_write_races(_race_launch(("parallel", "arbitrary")))
    disjoint = KernelLaunch(
        name="k", grid=(4, 8), dimension_semantics=("parallel", "parallel"),
        out_blocks=[BlockInfo((128, 128), "float32", 4, "vmem",
                              index_map=lambda i, j: (i, j))])
    assert not check_write_races(disjoint)


def test_vmem_budget_flags_oversized_block_and_working_set():
    fat = KernelLaunch(
        name="k", grid=(2,), dimension_semantics=("parallel",),
        in_blocks=[BlockInfo((1024, 1024), "float32", 4 << 20, "vmem")])
    bad = check_vmem(fat)
    assert bad and all(f.rule == "vmem-budget" for f in bad)
    assert "per-block cap" in bad[0].message
    # scratch alone can blow the whole working set
    hog = KernelLaunch(name="k", grid=(2,),
                       dimension_semantics=("parallel",),
                       scratch_bytes=32 << 20)
    assert any("working set" in f.message for f in check_vmem(hog))
    # SMEM scalars never count against VMEM
    smem = KernelLaunch(
        name="k", grid=(2,), dimension_semantics=("parallel",),
        in_blocks=[BlockInfo((1,), "int32", 64 << 20, "smem")])
    assert not check_vmem(smem)


def test_scalar_prefetch_flags_dtype_and_arity():
    launch = KernelLaunch(
        name="k", grid=(2,), dimension_semantics=("arbitrary",),
        num_scalar_prefetch=2, n_specs=3, n_operands=4,   # 2 + 3 != 4
        scalar_avals=[((4,), "int32"), ((4, 8), "float32")])
    bad = check_scalar_prefetch(launch)
    kinds = [f.message for f in bad]
    assert any("operands" in m for m in kinds)            # arity
    assert any("int32" in m for m in kinds)               # dtype
    ok = KernelLaunch(name="k", grid=(2,),
                      dimension_semantics=("arbitrary",),
                      num_scalar_prefetch=1, n_specs=2, n_operands=3,
                      scalar_avals=[((4,), "int32")])
    assert not check_scalar_prefetch(ok)


def test_missing_dimension_semantics_is_flagged():
    naked = KernelLaunch(name="k", grid=(4, 8), dimension_semantics=None)
    assert _rules_fired(check_launch(naked)) == {"grid-semantics-declared"}


@pytest.mark.parametrize("paged", [False, True])
def test_real_serving_kernel_launches_pass_all_contracts(paged):
    """capture_launches introspects the four real kernels without running
    them: grids resolve concretely, scalar prefetch matches, no races, and
    the VMEM estimate stays under budget at the analyzer shapes."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    scfg = ServeConfig(max_seq=4096, prefill_chunk=64, max_slots=4,
                       decode_kernel=True, prefill_kernel=True,
                       paged_kv=paged, page_size=64, score_norm="consmax")
    launches = serving_launches(cfg, scfg)
    kind = "paged" if paged else "contiguous"
    assert set(launches) == {f"decode_{kind}", f"prefill_{kind}"}
    hkv, d = cfg.n_kv_heads, cfg.head_dim_
    for label, launch in launches.items():
        assert launch.grid and all(isinstance(g, int) for g in launch.grid)
        assert not check_launch(launch), label
        # one program per (slot or slot-group, q-block, KV shard or page)
        # covering ALL kv heads: no hkv grid axis, and every K/V block ends
        # in the full (hkv, dk) extents the TPU tiling rule accepts
        assert len(launch.grid) == (2 if label.startswith("decode") else 3)
        kv = [b for b in launch.in_blocks if len(b.block_shape) == 4
              and b.block_shape[-2:] == (hkv, d) and b.dtype != "float32"]
        assert len(kv) == 2, (label, [b.block_shape for b in
                                      launch.in_blocks])
        assert all(b.memory_space == "smem" for b in launch.in_blocks
                   if len(b.block_shape) == 1)      # beta/gamma (+ scalars)
    if paged:
        assert launches[f"decode_{kind}"].num_scalar_prefetch == 2
        assert launches[f"prefill_{kind}"].num_scalar_prefetch == 3
        assert launches[f"prefill_{kind}"].dimension_semantics[-1] == \
            "arbitrary"
        assert launches[f"prefill_{kind}"].scratch_bytes > 0


def test_paged_decode_page_runs_pass_contracts_at_published_width():
    """At qwen2-1.5b's widths (two bf16 KV heads a 32-bit word) the paged
    decode kernel leaves both pools in HBM and DMAs runs of pages into VMEM
    scratch itself: the launch records the pools as "any" blocks, which
    hold no VMEM, and its scratch (pages and DMA semaphores) fits the
    budget with the partials block."""
    cfg = get_config("qwen2-1.5b")
    scfg = ServeConfig(max_seq=2048, prefill_chunk=512, max_slots=4,
                       decode_kernel=True, prefill_kernel=True,
                       paged_kv=True, page_size=256, score_norm="consmax")
    launch = serving_launches(cfg, scfg)["decode_paged"]
    assert not check_launch(launch)
    assert [b.memory_space for b in launch.in_blocks[-2:]] == ["any", "any"]
    pages = launch.in_blocks[-1].block_shape[0]
    assert pages == scfg.num_pages
    assert 0 < launch.scratch_bytes < launch.vmem_working_set() < 16 << 20


def test_capture_launches_restores_pallas_call():
    from jax.experimental import pallas as pl
    real = pl.pallas_call
    with capture_launches():
        assert pl.pallas_call is not real
    assert pl.pallas_call is real


# ------------------------------------------------------------ trace guard ----
def test_trace_guard_flags_retrace_and_passes_single_shape():
    fn = jax.jit(lambda x: x * 2)
    guard = TraceGuard().track("step", fn, limit=1)
    fn(jnp.zeros((2,)))
    fn(jnp.zeros((2,)))                    # same shape: cached, no retrace
    assert not guard.findings()
    fn(jnp.zeros((3,)))                    # second shape leaks in
    bad = guard.findings()
    assert bad and bad[0].rule == "one-trace-per-step"
    assert guard.counts()["step"] == 2
    with pytest.raises(AssertionError):
        guard.assert_ok()


def test_trace_guard_baseline_is_attach_time():
    fn = jax.jit(lambda x: x + 1)
    fn(jnp.zeros((2,)))                    # warm BEFORE attach
    guard = TraceGuard().track("step", fn, limit=0)
    fn(jnp.zeros((2,)))                    # cache hit only
    assert guard.counts()["step"] == 0 and not guard.findings()


def test_trace_guard_for_engine_tracks_both_steps():
    cfg = get_config("qwen2-1.5b", smoke=True)
    params = T.lm_init(Ctx(random.key(0)), cfg)
    scfg = ServeConfig(max_seq=24, prefill_chunk=4, max_slots=2)
    eng = ContinuousBatchingEngine(cfg, scfg, params)
    guard = TraceGuard.for_engine(eng, limit=1)
    assert set(guard.counts()) == {"prefill_step", "decode_step"}
    for pr, mx in zip([[3, 1, 4], [2, 7]], [2, 3]):
        eng.submit(pr, mx)
    eng.run(max_steps=60)
    guard.assert_ok()                      # one compiled shape per step


# -------------------------------------------------------------- the gate ----
def test_analyze_self_test_exits_nonzero(tmp_path):
    """The acceptance loop: seeded violations route through the real
    pipeline, every rule fires, the process exit code is non-zero."""
    out = tmp_path / "ANALYSIS.json"
    assert analyze.main(["--self-test", "--json-out", str(out)]) != 0
    import json
    report = json.loads(out.read_text())
    assert report["violations"] == len(report["findings"]) > 0
    fired = {f["rule"] for f in report["findings"]}
    assert fired == set(report["rules"])


def test_analyze_config_clean_and_schema(tmp_path):
    """One real config through analyze_config: zero findings, and the
    entry carries steps + kernels the schema assert demands."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    params = T.lm_init(Ctx(random.key(0)), cfg)
    scfg = analyze._matrix()["paged_fused_bounded"]
    entry, findings = analyze.analyze_config(
        "paged_fused_bounded", cfg, params, scfg, trace_guard=False)
    assert findings == []
    assert set(entry["steps"]) == {"decode", "prefill"}
    assert set(entry["kernels"]) == {"decode_paged", "prefill_paged"}
    for launch in entry["kernels"].values():
        assert launch["vmem_working_set_bytes"] > 0
        assert launch["findings"] == []


def test_analyze_threshold_must_dominate_param_surfaces():
    """The rule is only sound if cache-sized strictly exceeds every
    parameter surface; shrunk analyzer shapes must refuse to run."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    scfg = ServeConfig(max_seq=512, prefill_chunk=64, max_slots=4,
                       decode_kernel=True, prefill_kernel=True,
                       score_norm="consmax")
    with pytest.raises(RuntimeError, match="dominate"):
        analyze._cache_threshold(cfg, scfg, "prefill")
    ok = analyze._matrix()["contig_fused_bounded"]
    assert analyze._cache_threshold(cfg, ok, "prefill") > \
        cfg.vocab_size * cfg.d_model
