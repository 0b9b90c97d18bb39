"""Mellum2-12B-A2.5B: GQA layers, three sliding-window layers then one full
layer in each period, every layer's MLP a dropless mixture of 64 SiLU-gated
experts with 8 per token (softmax router, the chosen weights renormalized),
rotary positions plain on window layers and YaRN-scaled on full layers, an
untied output head.

It gives what ``models/dense.py`` lists, and ``moe_expert_cost``: the
grouped expert matmuls' (FLOPs, bytes) from the engine's counters of rows
routed and experts hit. The reference follows the published configuration
with the harness's shared parts (``reference.py``), and YaRN written out
here as transformers' ``_compute_yarn_parameters`` (``truncate`` true): pair
i's frequency theta^(-2i/dk) is divided by ``factor`` below the correction
range, kept above it, ramped between, and cos and sin are scaled by
``attention_factor``. Each expert runs only on the rows routed to it, in
blocks of EXPERT_BLOCK rows of one expert, its weights widened to float32 a
block at a time, so a request of 8k tokens checks in seconds beside the
bfloat16 weights. Departures (the configuration's ``departures``): ConSmax
in place of softmax in attention; RMSNorm gains stored zero-centred.
"""
from __future__ import annotations

import copy
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import costs
import reference as R
import weights

PUBLISHED = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
             "n_heads": "num_attention_heads",
             "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
             "d_ff": "moe_intermediate_size", "vocab_size": "vocab_size",
             "window": "sliding_window",
             "tie_embeddings": "tie_word_embeddings"}
EXPERT_BLOCK = 128     # rows of one expert per reference block


class Sizes(NamedTuple):
    d: int
    h: int
    hkv: int
    dk: int
    ff: int
    experts: int
    top_k: int
    vocab: int
    layers: int
    window: int
    kinds: tuple          # "sliding_attention" / "full_attention", per layer
    theta: float
    yarn: tuple           # factor, original length, beta_fast, beta_slow,
                          # attention_factor
    eps: float


def sizes(config: dict) -> Sizes:
    n = config["num_hidden_layers"]
    rope = config["rope_parameters"]
    full, slide = rope["full_attention"], rope["sliding_attention"]
    if full["rope_theta"] != slide["rope_theta"]:
        raise ValueError("one rope_theta for both kinds of layer")
    return Sizes(d=config["hidden_size"], h=config["num_attention_heads"],
                 hkv=config["num_key_value_heads"], dk=config["head_dim"],
                 ff=config["moe_intermediate_size"],
                 experts=config["num_experts"],
                 top_k=config["num_experts_per_tok"],
                 vocab=config["vocab_size"], layers=n,
                 window=config["sliding_window"],
                 kinds=tuple(config["layer_types"][:n]),
                 theta=float(full["rope_theta"]),
                 yarn=(float(full["factor"]),
                       int(full["original_max_position_embeddings"]),
                       float(full["beta_fast"]), float(full["beta_slow"]),
                       float(full["attention_factor"])),
                 eps=float(config["rms_norm_eps"]))


def _period(s: Sizes) -> int:
    """Layers per period of the pattern (the program's ``block_pattern``):
    the sliding layers up to and including the first full one."""
    p = s.kinds.index("full_attention") + 1
    if s.layers % p or s.kinds != s.kinds[:p] * (s.layers // p):
        raise ValueError(f"layer_types {s.kinds} are not whole periods")
    return p


def _windows(s: Sizes) -> list[int]:
    """Each layer's window, in order (0: full attention)."""
    return [s.window if k == "sliding_attention" else 0 for k in s.kinds]


def tiny(config: dict) -> dict:
    """Every width shrunk, in the published keys and the program's: two
    periods of four layers, a window of 24 rows that 40-100-token prompts
    cross; the 64 experts and 8 a token kept."""
    cfg = copy.deepcopy(config)
    cfg.update(hidden_size=128, moe_intermediate_size=64,
               num_hidden_layers=8, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, vocab_size=512,
               sliding_window=24)
    cfg["program"]["overrides"].update(
        n_layers=8, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=64, vocab_size=512, window=24)
    return cfg


# --------------------------------------------------------------- weights ----
def _shapes(s: Sizes) -> dict:
    n, d, h, hkv, dk, ff, E = (s.layers // _period(s), s.d, s.h, s.hkv,
                               s.dk, s.ff, s.experts)
    block = {"attn_norm": {"scale": (n, d)},
             "attn": {"q": {"w": (n, d, h, dk)}, "k": {"w": (n, d, hkv, dk)},
                      "v": {"w": (n, d, hkv, dk)}, "o": {"w": (n, h, dk, d)},
                      "score_norm": {"beta": (n, h), "gamma": (n, h)}},
             "mlp_norm": {"scale": (n, d)},
             "moe": {"router": (n, d, E), "gate": (n, E, d, ff),
                     "up": (n, E, d, ff), "down": (n, E, ff, d)}}
    return {"embed": {"table": (s.vocab, d)},
            "blocks": {f"b{i}": block for i in range(_period(s))},
            "final_norm": {"scale": (d,)},
            "lm_head": {"table": (s.vocab, d)}}


@functools.partial(jax.jit, static_argnames=("s",), donate_argnums=(0,))
def _fan_in(params, s: Sizes):
    """``weights.make`` draws a (n, E, d_in, d_out) matrix at 1/sqrt(E):
    scale each expert's matrices to 1/sqrt(d_in)."""
    def fix(blk):
        m = dict(blk["moe"])
        for name, fan in (("gate", s.d), ("up", s.d), ("down", s.ff)):
            m[name] = m[name] * jnp.bfloat16(math.sqrt(s.experts / fan))
        return dict(blk, moe=m)
    return dict(params, blocks={k: fix(b)
                                for k, b in params["blocks"].items()})


def make_params(config: dict, seed_word: int):
    s = sizes(config)
    return _fan_in(weights.make(_shapes(s), seed_word), s)


# ------------------------------------------------------------- reference ----
def yarn_inv_freq(s: Sizes) -> np.ndarray:
    """Full layers' rotary frequencies (dk/2,), float32."""
    factor, orig, fast, slow, _ = s.yarn
    dim = s.dk

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(s.theta))

    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)),
                                                    dim - 1)
    if low == high:
        high += 0.001
    extrap = 1.0 / s.theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (extrap / factor * (1 - keep) + extrap * keep).astype(np.float32)


def _rope_yarn(x, pos, s: Sizes):
    """R._rope with YaRN's frequencies, cos and sin times attention_factor."""
    dk = x.shape[-1]
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(yarn_inv_freq(s))
    c = jnp.cos(ang)[:, None] * s.yarn[4]
    sn = jnp.sin(ang)[:, None] * s.yarn[4]
    x1, x2 = x[..., :dk // 2], x[..., dk // 2:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def _experts(s: Sizes, prec, x, m):
    """The dropless mixture over x (t, d): softmax router, top-k, weights
    renormalized; each expert on its routed rows only, in blocks of
    EXPERT_BLOCK rows of one expert."""
    f32 = lambda a: a.astype(jnp.float32)                 # noqa: E731
    t, d = x.shape
    k, E, B = s.top_k, s.experts, EXPERT_BLOCK
    probs = jax.nn.softmax(R._mm("td,de->te", x, f32(m["router"]), (1,),
                                 (0,), prec), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / w.sum(-1, keepdims=True)
    n = t * k
    e = idx.reshape(n)
    order = jnp.argsort(e, stable=True)
    count = jnp.bincount(e, length=E)
    padded = (count + B - 1) // B * B
    ends = jnp.cumsum(padded)
    es = e[order]
    dst = (ends - padded)[es] + jnp.arange(n) - (jnp.cumsum(count)
                                                 - count)[es]
    nb = -(-(n + E * (B - 1)) // B)
    tok = jnp.full(nb * B, t, jnp.int32).at[dst].set(order // k)
    wt = jnp.zeros(nb * B, jnp.float32).at[dst].set(w.reshape(n)[order])
    blk_e = jnp.minimum(jnp.searchsorted(ends, jnp.arange(nb) * B,
                                         side="right"), E - 1)
    xp = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])

    def block(args):
        eb, tb, wb = args
        xb = xp[tb]
        g = R._mm("bd,df->bf", xb, f32(m["gate"][eb]), (1,), (0,), prec)
        u = R._mm("bd,df->bf", xb, f32(m["up"][eb]), (1,), (0,), prec)
        y = R._mm("bf,fd->bd", jax.nn.silu(g) * u, f32(m["down"][eb]), (1,),
                  (0,), prec)
        return y * wb[:, None]

    ys = jax.lax.map(block, (blk_e, tok.reshape(nb, B), wt.reshape(nb, B)))
    return jnp.zeros((t + 1, d), jnp.float32).at[tok].add(
        ys.reshape(nb * B, d))[:t]


def _layer(s: Sizes, prec, window, x, lp):
    f32 = lambda a: a.astype(jnp.float32)                 # noqa: E731
    a = lp["attn"]
    pos = jnp.arange(x.shape[0])
    hN = R._rms(x, lp["attn_norm"]["scale"], s.eps)

    def proj(p):
        return R._mm("td,dhk->thk", hN, f32(p["w"]), (1,), (0,), prec)

    def rope(y):
        return (R._rope(y, pos, s.theta) if window
                else _rope_yarn(y, pos, s))

    q = rope(proj(a["q"])) / np.sqrt(s.dk)
    k = rope(proj(a["k"]))
    o = R._attention(q, k, proj(a["v"]), a["score_norm"]["beta"],
                     a["score_norm"]["gamma"], prec, window)
    x = x + R._mm("thk,hkd->td", o, f32(a["o"]["w"]), (1, 2), (0, 1), prec)
    h2 = R._rms(x, lp["mlp_norm"]["scale"], s.eps)
    return x + _experts(s, prec, h2, lp["moe"])


def hidden(params, s: Sizes, tokens, prec="fp32"):
    """Final-norm hidden states (t, d) of one sequence, t a multiple of
    ``reference.Q_BLOCK``."""
    wins = _windows(s)[:_period(s)]

    def period(x, bp):
        for i, w in enumerate(wins):
            x = _layer(s, prec, w, x, bp[f"b{i}"])
        return x, None

    x = params["embed"]["table"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(period, x, params["blocks"])
    return R._rms(x, params["final_norm"]["scale"], s.eps)


@functools.partial(jax.jit, static_argnames=("s", "prec"))
def _gaps(params, tokens, rows, probe, *, s: Sizes, prec: str):
    x = hidden(params, s, tokens, prec)[rows]
    return R.readout(x, params["lm_head"]["table"], probe, prec)


def logit_gaps(params, s: Sizes, prompt, served, prec="fp32"):
    return R.gaps_at(functools.partial(_gaps, params, s=s, prec=prec),
                     prompt, served)


# ----------------------------------------------------------------- costs ----
def decode_kernel_calls(ctx, s: Sizes, kv_bytes: int = 2):
    """One paged decode kernel call per layer; a window layer attends only
    its window."""
    return [costs.decode_kernel(ctx, s, kv_bytes, window=w)
            for w in _windows(s)]


def prefill_kernel_calls(start, n, s: Sizes, kv_bytes: int = 2):
    return [costs.prefill_kernel(start, n, s, kv_bytes, window=w)
            for w in _windows(s)]


def _linear_flops_per_token(s: Sizes) -> float:
    """q, k, v, o, the router and the token's top-k experts, every layer."""
    per_layer = (s.d * s.h * s.dk + 2 * s.d * s.hkv * s.dk
                 + s.h * s.dk * s.d + s.d * s.experts
                 + s.top_k * 3 * s.d * s.ff)
    return 2.0 * per_layer * s.layers


def _unembed_flops(s: Sizes) -> float:
    return 2.0 * s.d * s.vocab


def decode_step_flops(ctx, s: Sizes) -> float:
    attn = sum(f for f, _ in decode_kernel_calls(ctx, s))
    return len(ctx) * (_linear_flops_per_token(s) + _unembed_flops(s)) \
        + attn


def prefill_step_flops(start, n, final, s: Sizes) -> float:
    attn = sum(f for f, _ in prefill_kernel_calls(start, n, s))
    return (n * _linear_flops_per_token(s) + attn
            + (_unembed_flops(s) if final else 0.0))


def moe_expert_cost(rows: int, hit: int, s: Sizes, act_bytes: int = 2,
                    out_bytes: int = 4) -> tuple[float, float]:
    """The grouped expert matmuls over ``rows`` routed rows, with ``hit``
    (layer, expert) pairs holding at least one (both summed over layers):
    (FLOPs, bytes). Each hit expert's three matrices are read once; the
    rows are read by gate and up (d wide) and by down (ff wide), and the
    float32 products written (2 x ff, and d)."""
    flops = 6.0 * s.d * s.ff * rows
    nbytes = (hit * 3.0 * s.d * s.ff * 2
              + rows * ((2 * s.d + s.ff) * act_bytes
                        + (2 * s.ff + s.d) * out_bytes))
    return flops, nbytes
