"""Jitted public wrappers for the fused ConSmax prefill kernels.

Both wrappers consume the model's serving layouts directly — q chunk
(b, c, H, dk), contiguous cache (b, L, hkv, dk) or stacked page pools
(n_layers, P, ps, hkv, dk) plus a layer index and a page table — so the
hot path pays no layout copy (mirror of ../consmax_decode/ops.py). On CPU
(this container) the kernel body executes in interpret mode; on a real TPU
backend it compiles through Mosaic.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.consmax_prefill.kernel import (consmax_prefill,
                                                  consmax_prefill_paged)


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("window", "softcap", "merged", "scale",
                                   "bq", "bk", "fill_bound", "interpret"))
def consmax_prefill_op(q, k, v, index, lengths, beta, gamma, *, window=0,
                       softcap=0.0, merged=True, scale=None, bq=128, bk=512,
                       fill_bound=True, interpret=None, k_scale=None,
                       v_scale=None):
    """q: (b, c, H, dk) chunk at per-slot cache positions index + [0, c);
    k, v: (b, L, hkv, dk) caches *after* the chunk's K/V were written;
    index, lengths: (b,) int32. Returns (b, c, H, dk) in q.dtype; rows
    >= lengths are pad rows whose output the caller discards.

    ``scale=1.0`` when q is pre-scaled (the model path); None applies
    1/sqrt(dk) (the standalone convention). ``fill_bound`` (default True)
    bounds KV-shard grid work by the traced fill level instead of cache
    capacity — fill stays a value, one compiled chunk step for all fills.
    ``k_scale``/``v_scale``: (b, L, hkv) fp32 row scales for a quantized
    (int8/fp8) cache — traced operands, dequantized per-block in VMEM.
    """
    interp = _on_cpu() if interpret is None else interpret
    return consmax_prefill(q, k, v, index, lengths, beta, gamma,
                           window=window, softcap=softcap, merged=merged,
                           scale=scale, bq=bq, bk=bk, fill_bound=fill_bound,
                           interpret=interp, k_scale=k_scale,
                           v_scale=v_scale)


@partial(jax.jit, static_argnames=("window", "softcap", "merged", "scale",
                                   "bq", "fill_bound", "interpret"))
def consmax_prefill_paged_op(q, kp, vp, layer, page_table, index, lengths,
                             beta, gamma, *, window=0, softcap=0.0,
                             merged=True, scale=None, bq=128, fill_bound=True,
                             interpret=None, k_scale=None, v_scale=None):
    """Paged-pool variant. kp, vp: every layer's shared pools, stacked
    (n_layers, P, ps, hkv, dk) in the model's cache layout and read at
    ``layer`` (int32 scalar) — never sliced or copied: the kernel walks
    page-table entries via scalar prefetch; page_table: (b, max_pages)
    int32. Returns (b, c, H, dk) in q.dtype. ``fill_bound`` bounds the
    page walk by the traced batch-max fill instead of the table's
    capacity. ``k_scale``/``v_scale``: (n_layers, P, ps, hkv) fp32 scale
    pools for a quantized KV pool, gathered through the same index map.
    """
    interp = _on_cpu() if interpret is None else interpret
    return consmax_prefill_paged(q, kp, vp, layer, page_table, index,
                                 lengths, beta, gamma, window=window,
                                 softcap=softcap, merged=merged, scale=scale,
                                 bq=bq, fill_bound=fill_bound,
                                 interpret=interp,
                                 k_scale=k_scale, v_scale=v_scale)
