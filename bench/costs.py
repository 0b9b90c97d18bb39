"""Operations and bytes that the algorithm needs, from shapes and filled
rows alone: the chip's peaks, the least time, and the paged ConSmax
attention kernels' counts from the widths they are called at (an object
with ``h``, ``hkv`` and ``dk``). What a step holds, and so how many calls
of which kernel at which widths and lengths, is a model module's to count
(``bench/models/<name>.py``).

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
                  act_bytes: int = 2, window: int = 0) -> tuple[float, float]:
    """One call of the paged decode attention over active slots with
    contexts ``ctx`` (rows filled, the new token's included): (FLOPs,
    bytes). Reads each slot's attended K and V rows once, q in and the
    output out. ``window`` > 0 (a sliding-window layer) attends only the
    last ``window`` rows."""
    rows = sum(min(c, window) for c in ctx) if window else sum(ctx)
    flops = 4.0 * s.h * s.dk * rows                   # q.k and p.v
    nbytes = (2.0 * rows * s.hkv * s.dk * kv_bytes
              + 2.0 * len(ctx) * s.h * s.dk * act_bytes)
    return flops, nbytes


def prefill_kernel(start: int, n: int, s, kv_bytes: int = 2,
                   act_bytes: int = 2, window: int = 0) -> tuple[float, float]:
    """One call of the chunked prefill attention of ``n`` real tokens at
    rows [start, start + n), each attending causally to the rows up to its
    own: (FLOPs, bytes). K and V of the rows attended are read once.
    ``window`` > 0 (a sliding-window layer): the row at position r attends
    only rows r - window < k <= r."""
    if window:
        pairs = _sum_min(start + 1, start + n, window)
        rows = start + n - max(0, start - window + 1)
    else:
        pairs = n * start + n * (n + 1) / 2            # (query, key) pairs
        rows = start + n
    flops = 4.0 * s.h * s.dk * pairs
    nbytes = (2.0 * rows * s.hkv * s.dk * kv_bytes
              + 2.0 * n * s.h * s.dk * act_bytes)
    return flops, nbytes


def _sum_min(a: int, b: int, w: int) -> float:
    """sum of min(x, w) for x = a .. b."""
    if b <= w:
        return (a + b) * (b - a + 1) / 2
    if a >= w:
        return float(w * (b - a + 1))
    return (a + w) * (w - a + 1) / 2 + w * (b - w)
