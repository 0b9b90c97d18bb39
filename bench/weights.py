"""Random weights from the seed, made on the device in one jitted call.

The pytree has the layout the program's decoder reads (``embed``, a
``blocks`` stack with a leading layer axis, ``final_norm``), built here from
the configuration's sizes alone, so the reference and the program read the
same arrays and neither made them. Matrices are bfloat16 (both published
checkpoints store bfloat16); ConSmax's per-head beta and gamma stay float32.

Distributions: embedding N(0, 1/d) (tied logits of order 1), projections
N(0, 1/fan_in), biases N(0, 0.05^2), RMSNorm gains 1 + N(0, 0.1^2) stored
zero-centred, beta ~ U[0.5, 2.5] and gamma = 100 (the paper's init).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def sizes(config: dict) -> dict:
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return dict(d=d, h=h, hkv=config["num_key_value_heads"], dk=d // h,
                ff=config["intermediate_size"], vocab=config["vocab_size"],
                layers=config["num_hidden_layers"],
                qkv_bias=bool(config.get("qkv_bias",
                                         config.get("attention_bias", False))),
                theta=float(config["rope_theta"]),
                eps=float(config["rms_norm_eps"]))


def _shapes(s: dict) -> dict:
    n, d, h, hkv, dk, ff = (s["layers"], s["d"], s["h"], s["hkv"], s["dk"],
                            s["ff"])
    attn = {"q": {"w": (n, d, h, dk)}, "k": {"w": (n, d, hkv, dk)},
            "v": {"w": (n, d, hkv, dk)}, "o": {"w": (n, h, dk, d)},
            "score_norm": {"beta": (n, h), "gamma": (n, h)}}
    if s["qkv_bias"]:
        attn["q"]["b"] = (n, h, dk)
        attn["k"]["b"] = (n, hkv, dk)
        attn["v"]["b"] = (n, hkv, dk)
    block = {"attn_norm": {"scale": (n, d)}, "attn": attn,
             "mlp_norm": {"scale": (n, d)},
             "mlp": {"gate": {"w": (n, d, ff)}, "up": {"w": (n, d, ff)},
                     "down": {"w": (n, ff, d)}}}
    return {"embed": {"table": (s["vocab"], d)}, "blocks": {"b0": block},
            "final_norm": {"scale": (d,)}}


def _leaf(key, path: str, shape: tuple):
    name = path.rsplit("/", 1)[-1]
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if name == "beta":
        return jax.random.uniform(k, shape, jnp.float32, 0.5, 2.5)
    if name == "gamma":
        return jnp.full(shape, 100.0, jnp.float32)
    if name == "scale":
        std = 0.1
    elif name == "b":
        std = 0.05
    elif name == "table":
        std = shape[-1] ** -0.5
    elif path.endswith("o/w"):              # (n, h, dk, d): fan-in h * dk
        std = (shape[1] * shape[2]) ** -0.5
    else:                                   # (n, d_in, ...)
        std = shape[1] ** -0.5
    # drawn in bfloat16 directly: no float32 copy of a matrix is ever held
    return jax.random.normal(k, shape, jnp.bfloat16) * jnp.bfloat16(std)


def _build(key, shapes, prefix=""):
    if isinstance(shapes, tuple):
        return _leaf(key, prefix, shapes)
    return {k: _build(key, v, f"{prefix}/{k}" if prefix else k)
            for k, v in shapes.items()}


def make_params(config: dict, seed_word: int):
    """The whole pytree from one key, in one jitted call on the default
    device. The key is the hardware generator's (``rbg``): the same seed
    gives the same weights on the same backend, without threefry's cost
    over billions of elements."""
    shapes = _shapes(sizes(config))
    fn = jax.jit(lambda k: _build(k, shapes))
    return fn(jax.random.key(seed_word, impl="rbg"))
