"""A test-only model module: GQA layers of two kinds, sliding-window
("local") and full ("global"), repeating in the configuration's
``layer_pattern``, with ``head_dim`` its own width and not
``hidden_size / num_attention_heads``. It gives what ``models/dense.py``
lists, from the harness's shared parts alone, so a test can show that a
configuration brings such a model without an edit to the harness."""
from __future__ import annotations

import copy
import functools
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
             "d_ff": "intermediate_size", "vocab_size": "vocab_size",
             "rope_theta": "rope_theta", "window": "sliding_window",
             "block_pattern": "layer_pattern"}


class Sizes(NamedTuple):
    d: int
    h: int
    hkv: int
    dk: int
    ff: int
    vocab: int
    layers: int
    window: int
    pattern: tuple
    theta: float
    eps: float


def sizes(config: dict) -> Sizes:
    return Sizes(d=config["hidden_size"], h=config["num_attention_heads"],
                 hkv=config["num_key_value_heads"], dk=config["head_dim"],
                 ff=config["intermediate_size"], vocab=config["vocab_size"],
                 layers=config["num_hidden_layers"],
                 window=config["sliding_window"],
                 pattern=tuple(config["layer_pattern"]),
                 theta=float(config["rope_theta"]),
                 eps=float(config["rms_norm_eps"]))


def tiny(config: dict) -> dict:
    return copy.deepcopy(config)           # written at smoke size


def _windows(s: Sizes) -> list[int]:
    """Each layer's window, in order (0: full attention)."""
    per = [s.window if k == "local" else 0 for k in s.pattern]
    return per * (s.layers // len(s.pattern))


# --------------------------------------------------------------- weights ----
def make_params(config: dict, seed_word: int):
    s = sizes(config)
    n, d, h, hkv, dk, ff = (s.layers // len(s.pattern), s.d, s.h, s.hkv,
                            s.dk, s.ff)
    block = {"attn_norm": {"scale": (n, d)},
             "attn": {"q": {"w": (n, d, h, dk)}, "k": {"w": (n, d, hkv, dk)},
                      "v": {"w": (n, d, hkv, dk)}, "o": {"w": (n, h, dk, d)},
                      "score_norm": {"beta": (n, h), "gamma": (n, h)}},
             "mlp_norm": {"scale": (n, d)},
             "mlp": {"gate": {"w": (n, d, ff)}, "up": {"w": (n, d, ff)},
                     "down": {"w": (n, ff, d)}}}
    return weights.make(
        {"embed": {"table": (s.vocab, d)},
         "blocks": {f"b{i}": block for i in range(len(s.pattern))},
         "final_norm": {"scale": (d,)}}, seed_word)


# ------------------------------------------------------------- reference ----
def _layer(s: Sizes, prec, window, x, lp):
    f32 = lambda a: a.astype(jnp.float32)                 # noqa: E731
    a = lp["attn"]
    pos = jnp.arange(x.shape[0])
    hN = R._rms(x, lp["attn_norm"]["scale"], s.eps)

    def proj(p):
        return R._mm("td,dhk->thk", hN, f32(p["w"]), (1,), (0,), prec)

    q = R._rope(proj(a["q"]), pos, s.theta) / np.sqrt(s.dk)
    k = R._rope(proj(a["k"]), pos, s.theta)
    o = R._attention(q, k, proj(a["v"]), a["score_norm"]["beta"],
                     a["score_norm"]["gamma"], prec, window)
    x = x + R._mm("thk,hkd->td", o, f32(a["o"]["w"]), (1, 2), (0, 1), prec)
    m = lp["mlp"]
    h2 = R._rms(x, lp["mlp_norm"]["scale"], s.eps)
    gate = R._mm("td,df->tf", h2, f32(m["gate"]["w"]), (1,), (0,), prec)
    up = R._mm("td,df->tf", h2, f32(m["up"]["w"]), (1,), (0,), prec)
    return x + R._mm("tf,fd->td", jax.nn.silu(gate) * up,
                     f32(m["down"]["w"]), (1,), (0,), prec)


@functools.partial(jax.jit, static_argnames=("s", "prec"))
def _gaps(params, tokens, rows, probe, *, s: Sizes, prec: str):
    def period(x, bp):
        for i, kind in enumerate(s.pattern):
            x = _layer(s, prec, s.window if kind == "local" else 0, x,
                       bp[f"b{i}"])
        return x, None

    x = params["embed"]["table"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(period, x, params["blocks"])
    x = R._rms(x, params["final_norm"]["scale"], s.eps)[rows]
    return R.readout(x, params["embed"]["table"], probe, prec)


def logit_gaps(params, s: Sizes, prompt, served, prec="fp32"):
    return R.gaps_at(functools.partial(_gaps, params, s=s, prec=prec),
                     prompt, served)


# ----------------------------------------------------------------- costs ----
def decode_kernel_calls(ctx, s: Sizes, kv_bytes: int = 2):
    return [costs.decode_kernel(ctx, s, kv_bytes, window=w)
            for w in _windows(s)]


def prefill_kernel_calls(start, n, s: Sizes, kv_bytes: int = 2):
    return [costs.prefill_kernel(start, n, s, kv_bytes, window=w)
            for w in _windows(s)]


def _linear_flops_per_token(s: Sizes) -> float:
    per_layer = (s.d * s.h * s.dk + 2 * s.d * s.hkv * s.dk
                 + s.h * s.dk * s.d + 3 * s.d * s.ff)
    return 2.0 * per_layer * s.layers


def decode_step_flops(ctx, s: Sizes) -> float:
    attn = sum(f for f, _ in decode_kernel_calls(ctx, s))
    return len(ctx) * (_linear_flops_per_token(s) + 2.0 * s.d * s.vocab) \
        + attn


def prefill_step_flops(start, n, final, s: Sizes) -> float:
    attn = sum(f for f, _ in prefill_kernel_calls(start, n, s))
    return (n * _linear_flops_per_token(s) + attn
            + (2.0 * s.d * s.vocab if final else 0.0))
