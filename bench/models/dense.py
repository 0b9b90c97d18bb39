"""The dense decoder: pre-norm GQA layers all alike, a SiLU-gated MLP and a
tied unembedding (qwen2-1.5b, granite-3-2b).

A configuration names its model module by ``"model"`` in its file
(``bench/configs/<config>.json`` -> ``bench/models/<model>.py``). The module
is the only part of the harness that reads the configuration's model shape.
It gives:

* ``sizes(config)``: the shape as a hashable ``NamedTuple`` (``vocab``
  among its fields), handed back to every function below;
* ``make_params(config, seed_word)``: the weights, in the pytree layout the
  program reads, drawn from the seed (``weights.make``);
* ``logit_gaps(params, sizes, prompt, served, prec)``: the plain
  reference, ``prec`` ``"fp32"`` or the control's ``"fp8"``;
* ``PUBLISHED``: program config field -> published config key, checked
  when the engine is built;
* ``tiny(config)``: the configuration at smoke size, for the CPU tests;
* ``decode_kernel_calls(ctx, sizes, kv_bytes)`` and
  ``prefill_kernel_calls(start, n, sizes, kv_bytes)``: one (FLOPs, bytes)
  per attention-kernel call in one decode step or prefill chunk;
* ``decode_step_flops(ctx, sizes)`` and ``prefill_step_flops(start, n,
  final, sizes)``: the model FLOPs of the whole step.

The reference follows the published equations: RMSNorm (gain stored
zero-centred, 1 + scale), rotary positions on q and k (half-split pairs,
theta from the configuration), grouped-query attention normalized by
ConSmax, a SiLU-gated MLP and a tied unembedding, in float32 at
``HIGHEST`` (``reference.py``).
"""
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
             "n_kv_heads": "num_key_value_heads",
             "d_ff": "intermediate_size", "vocab_size": "vocab_size",
             "rope_theta": "rope_theta"}


class Sizes(NamedTuple):
    d: int
    h: int
    hkv: int
    dk: int
    ff: int
    vocab: int
    layers: int
    qkv_bias: bool
    theta: float
    eps: float


def sizes(config: dict) -> Sizes:
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return Sizes(d=d, h=h, hkv=config["num_key_value_heads"], dk=d // h,
                 ff=config["intermediate_size"], vocab=config["vocab_size"],
                 layers=config["num_hidden_layers"],
                 qkv_bias=bool(config.get("qkv_bias",
                                          config.get("attention_bias",
                                                     False))),
                 theta=float(config["rope_theta"]),
                 eps=float(config["rms_norm_eps"]))


def tiny(config: dict) -> dict:
    """Every width shrunk, in the published keys and the program's."""
    cfg = copy.deepcopy(config)
    cfg.update(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
    cfg["program"]["overrides"].update(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, head_dim=0)
    return cfg


# --------------------------------------------------------------- weights ----
def _shapes(s: Sizes) -> dict:
    n, d, h, hkv, dk, ff = s.layers, s.d, s.h, s.hkv, s.dk, s.ff
    attn = {"q": {"w": (n, d, h, dk)}, "k": {"w": (n, d, hkv, dk)},
            "v": {"w": (n, d, hkv, dk)}, "o": {"w": (n, h, dk, d)},
            "score_norm": {"beta": (n, h), "gamma": (n, h)}}
    if s.qkv_bias:
        attn["q"]["b"] = (n, h, dk)
        attn["k"]["b"] = (n, hkv, dk)
        attn["v"]["b"] = (n, hkv, dk)
    block = {"attn_norm": {"scale": (n, d)}, "attn": attn,
             "mlp_norm": {"scale": (n, d)},
             "mlp": {"gate": {"w": (n, d, ff)}, "up": {"w": (n, d, ff)},
                     "down": {"w": (n, ff, d)}}}
    return {"embed": {"table": (s.vocab, d)}, "blocks": {"b0": block},
            "final_norm": {"scale": (d,)}}


def make_params(config: dict, seed_word: int):
    return weights.make(_shapes(sizes(config)), seed_word)


# ------------------------------------------------------------- reference ----
def _layer(s: Sizes, prec, x, lp):
    f32 = lambda a: a.astype(jnp.float32)                 # noqa: E731
    a = lp["attn"]
    t = x.shape[0]
    pos = jnp.arange(t)
    hN = R._rms(x, lp["attn_norm"]["scale"], s.eps)

    def proj(p):
        y = R._mm("td,dhk->thk", hN, f32(p["w"]), (1,), (0,), prec)
        return y + f32(p["b"]) if "b" in p else y

    q = R._rope(proj(a["q"]), pos, s.theta) / np.sqrt(s.dk)
    k = R._rope(proj(a["k"]), pos, s.theta)
    v = proj(a["v"])
    o = R._attention(q, k, v, a["score_norm"]["beta"],
                     a["score_norm"]["gamma"], prec)
    x = x + R._mm("thk,hkd->td", o, f32(a["o"]["w"]), (1, 2), (0, 1), prec)
    m = lp["mlp"]
    h2 = R._rms(x, lp["mlp_norm"]["scale"], s.eps)
    gate = R._mm("td,df->tf", h2, f32(m["gate"]["w"]), (1,), (0,), prec)
    up = R._mm("td,df->tf", h2, f32(m["up"]["w"]), (1,), (0,), prec)
    x = x + R._mm("tf,fd->td", jax.nn.silu(gate) * up, f32(m["down"]["w"]),
                  (1,), (0,), prec)
    return x, None


def hidden(params, s: Sizes, tokens, prec="fp32"):
    """Final-norm hidden states (t, d) of one sequence, t a multiple of
    ``reference.Q_BLOCK``."""
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, s, prec), x,
                        params["blocks"]["b0"])
    return R._rms(x, params["final_norm"]["scale"], s.eps)


@functools.partial(jax.jit, static_argnames=("s", "prec"))
def _gaps(params, tokens, rows, probe, *, s: Sizes, prec: str):
    x = hidden(params, s, tokens, prec)[rows]             # (n, d)
    return R.readout(x, params["embed"]["table"], probe, prec)


def logit_gaps(params, s: Sizes, prompt, served, prec="fp32"):
    """For each served token i: (best logit - logit of served[i], argmax)."""
    return R.gaps_at(functools.partial(_gaps, params, s=s, prec=prec),
                     prompt, served)


# ----------------------------------------------------------------- costs ----
def decode_kernel_calls(ctx: list[int], s: Sizes, kv_bytes: int = 2):
    """One paged decode kernel call per layer, each over every active
    slot's whole context."""
    return [costs.decode_kernel(ctx, s, kv_bytes)] * s.layers


def prefill_kernel_calls(start: int, n: int, s: Sizes, kv_bytes: int = 2):
    """One paged prefill kernel call per layer."""
    return [costs.prefill_kernel(start, n, s, kv_bytes)] * s.layers


def _linear_flops_per_token(s: Sizes) -> float:
    """q, k, v, o and the gated MLP of every layer, per token."""
    per_layer = (s.d * s.h * s.dk + 2 * s.d * s.hkv * s.dk
                 + s.h * s.dk * s.d + 3 * s.d * s.ff)
    return 2.0 * per_layer * s.layers


def _unembed_flops(s: Sizes) -> float:
    return 2.0 * s.d * s.vocab


def decode_step_flops(ctx: list[int], s: Sizes) -> float:
    """One decode step: every active slot's token through the model, its
    attention over its own context, and its logits row."""
    attn = sum(f for f, _ in decode_kernel_calls(ctx, s))
    return len(ctx) * (_linear_flops_per_token(s) + _unembed_flops(s)) + attn


def prefill_step_flops(start: int, n: int, final: bool, s: Sizes) -> float:
    """One prefill chunk: ``n`` real tokens through the model, their causal
    attention, and a logits row only where the prompt ends (the row that
    seeds the first token)."""
    attn = sum(f for f, _ in prefill_kernel_calls(start, n, s))
    return (n * _linear_flops_per_token(s) + attn
            + (_unembed_flops(s) if final else 0.0))
