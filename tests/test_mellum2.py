"""Mellum2-12B-A2.5B through the serving engine, against a plain float32
reference (``tests/mellum2_reference.py``), at a small size on the CPU with
seeded random weights: window and full layers with dropless top-8 expert
layers, YaRN on full layers only, an untied head.

The engine test drives ``ContinuousBatchingEngine`` itself: chunked prefill
(8-token chunks across the 8-row window) into the paged pool, then decode
through it, with both Pallas kernels in interpret mode. Every logits row
the engine's steps produce (each prefill chunk's last row, each decode
step's active rows) is compared with the reference's full forward over the
request's served sequence at the same position.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

import mellum2_reference as REF
from repro.configs.base import MoEConfig, ServeConfig
from repro.configs.registry import get_config
from repro.models import transformer as T
from repro.nn import rope as R
from repro.nn.module import Ctx
from repro.serve import engine as E

ARCH = "mellum2-12b-a2.5b"
# |engine - reference| over every compared logit (logits of spread about
# 1). The engine computes in bfloat16 (activations, weights and the KV
# pool), the reference in float32 on the same weights: rounding moves a
# logit by about 0.01 on average, and where a token's 8th and 9th experts
# nearly tie the bfloat16 router input may pick the other one, which moves
# that row further. On this configuration the engine reads a mean of 0.011
# and a largest of 0.077; the reference with its weights in float8 (e4m3,
# one scale per output channel) reads 0.068 and 0.655. Each limit lies
# between, with room on both sides; the float8 reading fails both.
MEAN_TOL, MAX_TOL = 0.03, 0.25


def _cfg():
    """The smoke config with two KV heads and 8 of 16 experts per token,
    in bfloat16 like the served model."""
    return get_config(ARCH, smoke=True).replace(
        n_kv_heads=2, d_ff=64, param_dtype="bfloat16",
        moe=MoEConfig(n_experts=16, top_k=8, capacity_factor=0.0))


def _fp8(params):
    """Every matrix rounded to float8 e4m3, one scale per output channel
    (the contracted axis is the second-to-last; a table's rows are
    tokens, so it scales per row)."""
    def q(path, a):
        name = jax.tree_util.keystr(path)
        if a.ndim < 2 or "score_norm" in name or "scale" in name:
            return a
        ax = a.ndim - 1 if "table" in name else a.ndim - 2
        a = jnp.asarray(a, jnp.float32)
        s = jnp.max(jnp.abs(a), axis=ax, keepdims=True) / 448.0
        s = jnp.where(s > 0, s, 1.0)
        return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return jax.tree_util.tree_map_with_path(q, params)


def _served_logits(cfg, params, prompts, new_tokens):
    """Run the engine (host sampling, greedy) and keep every logits row its
    steps return: {uid: {position: logits}}, and the served tokens."""
    scfg = ServeConfig(score_norm="consmax", max_slots=2, max_seq=64,
                       paged_kv=True, page_size=8, prefill_chunk=8,
                       decode_kernel=True, prefill_kernel=True,
                       fused_sampling=False)
    eng = E.ContinuousBatchingEngine(cfg, scfg, params)
    rows: dict = {}
    prefill, decode = eng._prefill, eng._decode

    def uid_of(slot):
        return eng.scheduler.slots[slot].request.uid

    def rec_prefill(params, caches, slot, chunk, lengths, bank, page_row):
        start = int(T.cache_index(caches)[int(slot)])
        uid = uid_of(int(slot))
        out, caches = prefill(params, caches, slot, chunk, lengths, bank,
                              page_row)
        rows.setdefault(uid, {})[start + int(lengths[0]) - 1] = \
            np.asarray(out[0], np.float32)
        return out, caches

    def rec_decode(params, caches, inputs, bank):
        index = np.asarray(T.cache_index(caches))
        live = np.flatnonzero(np.asarray(inputs["active"]))
        uids = {int(s): uid_of(int(s)) for s in live}
        out, caches = decode(params, caches, inputs, bank)
        for s in live:
            rows.setdefault(uids[int(s)], {})[int(index[s])] = \
                np.asarray(out[s], np.float32)
        return out, caches

    eng._prefill, eng._decode = rec_prefill, rec_decode
    uids = [eng.submit(p, new_tokens) for p in prompts]
    results = eng.run()
    return rows, {u: list(prompts[i]) + results[u]
                  for i, u in enumerate(uids)}


def _deltas(cfg, params, rows, seqs):
    out = []
    for uid, at in rows.items():
        ref = np.asarray(REF.logits(params, cfg, seqs[uid]))
        out += [np.abs(lg - ref[pos]) for pos, lg in sorted(at.items())]
    return np.concatenate(out)


def test_engine_matches_the_float32_reference_on_logits():
    cfg = _cfg()
    params = T.lm_init(Ctx(random.key(0)), cfg)
    prompts = [list(map(int, random.randint(random.key(10 + i), (n,), 0,
                                            cfg.vocab_size)))
               for i, n in enumerate((21, 13))]
    rows, seqs = _served_logits(cfg, params, prompts, 5)
    # every chunk's last row and every decode row was compared: 3 + 2
    # chunks and 4 decode steps of each request
    assert sorted(len(v) for v in rows.values()) == [6, 7]
    got = _deltas(cfg, params, rows, seqs)
    assert got.mean() <= MEAN_TOL and got.max() <= MAX_TOL, (got.mean(),
                                                             got.max())
    # the same comparison catches the reference in a lower precision
    low = _deltas(cfg, _fp8(params), rows, seqs)
    assert low.mean() > MEAN_TOL and low.max() > MAX_TOL, (low.mean(),
                                                           low.max())


def test_yarn_frequencies_follow_the_published_formula():
    """Mellum2's full layers: theta 5e5, factor 16, original length 8192,
    beta 32 / 1 at head_dim 128 put the correction range at [18, 35]."""
    y = get_config(ARCH).rope_yarn
    inv, mscale = R.yarn_freqs(128, 500000.0, y)
    i = np.arange(64)
    extrap = 500000.0 ** (-2 * i / 128)
    e = 1 - np.clip((i - 18) / (35 - 18), 0, 1)
    want = extrap / 16 * (1 - e) + extrap * e
    np.testing.assert_allclose(np.asarray(inv), want, rtol=2e-6)
    np.testing.assert_allclose(REF.yarn_inv_freq(128, 500000.0, y), want,
                               rtol=1e-12)
    assert mscale == y.attention_factor == 1.2772588722239782


@pytest.mark.parametrize("kind,moved", [("local_moe", False),
                                        ("global_moe", True)])
def test_only_full_layers_take_yarn(kind, moved):
    """One layer of each kind, with and without the YaRN settings: the
    sliding layer's output is the same (plain RoPE), the full layer's is
    not."""
    from repro.models import blocks as B
    cfg = _cfg().replace(block_pattern=(kind,), n_layers=1)
    p = B.block_init(Ctx(random.key(1)), "b", cfg, kind)
    x = random.normal(random.key(2), (1, 16, cfg.d_model)).astype(
        jnp.bfloat16)

    def run(c):
        return np.asarray(B.block_apply(p, x, c, kind)[0], np.float32)

    same = np.array_equal(run(cfg), run(cfg.replace(rope_yarn=None)))
    assert same != moved


def test_untied_head_is_its_own_table_and_tied_trees_are_unchanged():
    cfg = _cfg()
    p = T.lm_init(Ctx(random.key(0)), cfg)
    assert p["lm_head"]["table"].shape == (cfg.vocab_size, cfg.d_model)
    toks = jnp.arange(8)[None]
    base = T.lm_apply(p, cfg, tokens=toks)[0]
    bumped = dict(p, lm_head={"table": p["lm_head"]["table"] * 2})
    np.testing.assert_allclose(np.asarray(T.lm_apply(bumped, cfg,
                                                     tokens=toks)[0],
                                          np.float32),
                               2 * np.asarray(base, np.float32), rtol=2e-2,
                               atol=2e-2)
    for arch in ("qwen2-1.5b", "granite-3-2b", "phi3.5-moe-42b-a6.6b"):
        tied = T.lm_init(Ctx(random.key(0)), get_config(arch, smoke=True))
        assert set(tied) == {"embed", "blocks", "final_norm"}


def test_paged_caches_and_the_engine_take_windowed_expert_kinds():
    cfg = _cfg()
    caches = T.init_paged_caches(cfg, 2, 8, 8)
    assert set(caches) == {"b0", "b1", "b2", "b3"}
    assert caches["b0"]["attn"]["k"].shape == (2, 8, 8, 2, 32)
    assert E._attention_only(cfg) and E._counts_experts(cfg)
    assert not E._counts_experts(get_config("phi3.5-moe-42b-a6.6b"))


def test_decode_counts_routed_rows_and_experts_in_the_same_drain():
    """The fused decode step returns the expert layers' counts beside the
    tokens; the engine adds them to its counters (rows = active slots x
    top-k x layers). A model without experts keeps its counters as they
    were."""
    cfg = _cfg()
    params = T.lm_init(Ctx(random.key(0)), cfg)
    scfg = ServeConfig(score_norm="consmax", max_slots=3, max_seq=64,
                       paged_kv=True, page_size=8, prefill_chunk=8,
                       decode_kernel=True, prefill_kernel=True)
    eng = E.ContinuousBatchingEngine(cfg, scfg, params)
    for n in (9, 12):
        eng.submit(list(range(1, n + 1)), 4)
    eng.run()
    c = eng.counters
    assert c["moe_rows"] == c["decode_active_slots"] * 8 * cfg.n_layers
    assert 0 < c["moe_experts_hit"] <= c["decode_steps"] * 16 * cfg.n_layers
    assert c["host_syncs"] == c["decode_steps"] + 2
    dense = E.ContinuousBatchingEngine(
        get_config("qwen2-1.5b", smoke=True), ServeConfig(max_slots=2,
                                                          max_seq=32),
        T.lm_init(Ctx(random.key(0)), get_config("qwen2-1.5b", smoke=True)))
    assert "moe_rows" not in dense.counters


def test_registry_entry_is_the_published_config():
    cfg = get_config(ARCH)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.window) == (
        28, 2304, 32, 4, 128, 98304, 1024)
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.d_ff,
            cfg.moe.capacity_factor) == (64, 8, 896, 0.0)
    assert cfg.block_pattern == ("local_moe",) * 3 + ("global_moe",)
    assert not cfg.tie_embeddings and cfg.rope_theta == 500000.0
    smoke = get_config(ARCH, smoke=True)
    assert smoke.moe.capacity_factor == 0.0 and smoke.moe.dropless
    assert not get_config("grok-1-314b", smoke=True).moe.dropless
    assert smoke.rope_yarn == cfg.rope_yarn
