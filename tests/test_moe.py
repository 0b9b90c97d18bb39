"""MoE dispatch invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

from repro.configs.base import MoEConfig
from repro.configs.registry import get_config
from repro.models import moe as M
from repro.nn.module import Ctx


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True)
    p = M.moe_init(Ctx(random.key(0)), "moe", cfg)
    x = random.normal(random.key(1), (2, 16, cfg.d_model)).astype(jnp.bfloat16)
    return cfg, p, x


def test_moe_shapes_and_finite(setup):
    cfg, p, x = setup
    y, aux = M.moe_apply(p, x, cfg)
    assert y.shape == x.shape
    assert not bool(jnp.isnan(y.astype(jnp.float32)).any())
    assert float(aux) >= 0


def test_moe_matches_dense_expert_mixture(setup):
    """With generous capacity (no drops), sort-based dispatch must equal the
    dense weighted mixture over the top-k experts."""
    cfg, p, x = setup
    m = cfg.moe
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, -1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    w = w / w.sum(-1, keepdims=True)
    cdt = jnp.bfloat16

    def expert(e, t):  # t: (d,)
        h = jax.nn.silu(t @ p["gate"][e].astype(cdt)) * (t @ p["up"][e].astype(cdt))
        return h @ p["down"][e].astype(cdt)

    def token(t, idxs, ws):
        outs = jnp.stack([expert(idxs[j], t) for j in range(m.top_k)])
        return (outs * ws[:, None].astype(cdt)).sum(0)

    dense = jax.vmap(jax.vmap(token))(x.astype(cdt), idx, w)
    y, _ = M.moe_apply(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y.astype(jnp.float32)),
                               np.asarray(dense.astype(jnp.float32)),
                               atol=3e-2)


def test_capacity_drops_tokens():
    cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True)
    tight = MoEConfig(n_experts=4, top_k=2, d_ff_expert=256,
                      capacity_factor=0.01)
    cfg_t = cfg.replace(moe=tight)
    p = M.moe_init(Ctx(random.key(0)), "moe", cfg_t)
    x = random.normal(random.key(1), (1, 64, cfg.d_model)).astype(jnp.bfloat16)
    y, _ = M.moe_apply(p, x, cfg_t)
    # with capacity 8 slots for 128 assignments most tokens are dropped -> 0 rows
    zeros = (jnp.abs(y.astype(jnp.float32)).sum(-1) == 0).mean()
    assert float(zeros) > 0.3


def test_consmax_router_preserves_topk_selection():
    cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True)
    cs = MoEConfig(n_experts=4, top_k=2, d_ff_expert=256,
                   router_norm="consmax")
    cfg_c = cfg.replace(moe=cs)
    p = M.moe_init(Ctx(random.key(0)), "moe", cfg_c)
    x = random.normal(random.key(2), (2, 8, cfg.d_model)).astype(jnp.bfloat16)
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    _, idx_sm = jax.lax.top_k(jax.nn.softmax(logits, -1), 2)
    probs_cs = jnp.exp(logits - p["beta"]) / p["gamma"]
    _, idx_cs = jax.lax.top_k(probs_cs, 2)
    np.testing.assert_array_equal(np.asarray(idx_sm), np.asarray(idx_cs))


def test_aux_loss_balanced_vs_skewed():
    cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True)
    p = M.moe_init(Ctx(random.key(0)), "moe", cfg)
    # uniform logits -> aux ~ weight*1.0; skewed router -> larger aux
    x = random.normal(random.key(3), (2, 32, cfg.d_model)).astype(jnp.bfloat16)
    _, aux_u = M.moe_apply(p, x, cfg)
    p_skew = dict(p, router=p["router"] * 0 +
                  jnp.eye(cfg.d_model, cfg.moe.n_experts) * 50)
    _, aux_s = M.moe_apply(p_skew, x, cfg)
    assert float(aux_s) > float(aux_u)


# ------------------------------------------------------------- dropless ----
def _dropless():
    cfg = get_config("mellum2-12b-a2.5b", smoke=True).replace(
        d_ff=64, moe=MoEConfig(n_experts=16, top_k=8, capacity_factor=0.0))
    p = M.moe_init(Ctx(random.key(0)), "moe", cfg)
    x = random.normal(random.key(1), (2, 24, cfg.d_model)).astype(
        jnp.bfloat16)
    return cfg, p, x


def test_dropless_equals_the_per_token_sum_over_its_top_k_experts():
    """Every token reaches all k of its experts: the layer is the dense
    weighted mixture, at float32 products on the bfloat16 operands."""
    cfg, p, x = _dropless()
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.moe.top_k)
    w = w / w.sum(-1, keepdims=True)
    f32 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731

    def token(t, idxs, ws):
        out = 0.0
        for j in range(cfg.moe.top_k):
            e = idxs[j]
            h = jax.nn.silu(t @ f32(p["gate"][e])) * (t @ f32(p["up"][e]))
            out = out + ws[j] * (f32(h) @ f32(p["down"][e]))
        return out

    dense = jax.vmap(jax.vmap(token))(f32(x), idx, w)
    y, _, counts = M.moe_apply(p, x, cfg, counts=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(dense), atol=2e-2, rtol=2e-2)
    assert int(counts[0]) == 2 * 24 * cfg.moe.top_k
    assert int(counts[1]) == len(np.unique(np.asarray(idx)))


def test_dropless_row_does_not_depend_on_the_rest_of_its_chunk():
    """A row's output is the same, bit for bit, whether its chunk is whole,
    split in two, permuted, or has rows masked out (pad rows and idle
    decode slots route nowhere and are not counted)."""
    cfg, p, x = _dropless()
    whole, _ = M.moe_apply(p, x, cfg)
    halves = jnp.concatenate([M.moe_apply(p, x[:, :10], cfg)[0],
                              M.moe_apply(p, x[:, 10:], cfg)[0]], axis=1)
    np.testing.assert_array_equal(np.asarray(halves), np.asarray(whole))
    perm = np.random.default_rng(0).permutation(24)
    shuffled, _ = M.moe_apply(p, x[:, perm], cfg)
    np.testing.assert_array_equal(np.asarray(shuffled),
                                  np.asarray(whole[:, perm]))
    mask = jnp.arange(24)[None, :] < jnp.asarray([[7], [19]])
    masked, _, counts = M.moe_apply(p, x, cfg, row_mask=mask, counts=True)
    keep = np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(masked)[keep],
                                  np.asarray(whole)[keep])
    assert not np.asarray(masked, np.float32)[~keep].any()
    assert int(counts[0]) == (7 + 19) * cfg.moe.top_k
