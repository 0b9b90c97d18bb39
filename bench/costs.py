"""Operations and bytes that the algorithm needs, from shapes and filled
rows alone.

Counted: only the rows actually filled (never capacity, page rounding,
padding or inactive slots); K and V at their storage dtype; weights at
theirs; matrix-product FLOPs (2 per multiply-add), not the elementwise exp
of ConSmax or the norms. The same work is counted whatever implements it,
so a roofline or an ``mfu`` reads the same for any kernel that does it.

A roofline share is (least time the chip could take) / (measured time),
where the least time is the larger of FLOPs / peak FLOP/s and bytes / peak
bytes/s; ``bound`` names the larger.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    tc = flops / pk["bf16_flops_per_s"]
    tb = nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "bandwidth")


# ---------------------------------------------------------------- kernels ----
def decode_kernel(ctx: list[int], s, kv_bytes: int = 2,
                  act_bytes: int = 2) -> tuple[float, float]:
    """One layer's paged decode attention over active slots with contexts
    ``ctx`` (rows attended, the new token's included): (FLOPs, bytes).
    Reads each slot's K and V rows once, q in and the output out."""
    rows = sum(ctx)
    flops = 4.0 * s.h * s.dk * rows                   # q.k and p.v
    nbytes = (2.0 * rows * s.hkv * s.dk * kv_bytes
              + 2.0 * len(ctx) * s.h * s.dk * act_bytes)
    return flops, nbytes


def prefill_kernel(start: int, n: int, s, kv_bytes: int = 2,
                   act_bytes: int = 2) -> tuple[float, float]:
    """One layer's chunked prefill attention of ``n`` real tokens at rows
    [start, start + n), each attending causally to the rows up to its own:
    (FLOPs, bytes). K and V of the start + n rows are read once."""
    pairs = n * start + n * (n + 1) / 2                # (query, key) pairs
    flops = 4.0 * s.h * s.dk * pairs
    nbytes = (2.0 * (start + n) * s.hkv * s.dk * kv_bytes
              + 2.0 * n * s.h * s.dk * act_bytes)
    return flops, nbytes


# ------------------------------------------------------------ model steps ----
def linear_flops_per_token(s) -> float:
    """q, k, v, o and the gated MLP of every layer, per token."""
    per_layer = (s.d * s.h * s.dk + 2 * s.d * s.hkv * s.dk
                 + s.h * s.dk * s.d + 3 * s.d * s.ff)
    return 2.0 * per_layer * s.layers


def unembed_flops(s) -> float:
    return 2.0 * s.d * s.vocab


def decode_step_flops(ctx: list[int], s) -> float:
    """One decode step: every active slot's token through the model, its
    attention over its own context, and its logits row."""
    attn = s.layers * decode_kernel(ctx, s)[0]
    return len(ctx) * (linear_flops_per_token(s) + unembed_flops(s)) + attn


def prefill_step_flops(start: int, n: int, final: bool, s) -> float:
    """One prefill chunk: ``n`` real tokens through the model, their causal
    attention, and a logits row only where the prompt ends (the row that
    seeds the first token)."""
    attn = s.layers * prefill_kernel(start, n, s)[0]
    return (n * linear_flops_per_token(s) + attn
            + (unembed_flops(s) if final else 0.0))
