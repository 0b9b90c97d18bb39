"""Jitted public wrappers for the split-KV ConSmax decode kernel.

Both kernels consume the model's cache layout — q (b, 1, H, dk), cache k/v
(b, L, hkv, dk) (or the stacked (n_layers, P, ps, hkv, dk) page pools and
a layer index), per-slot cache
``index`` (b,) — directly: the contiguous kernel blocks the hkv axis inside
its grid, and the paged kernel walks runs of table columns, fetching whole
pages of every head (by its own DMAs where a bf16 pool packs two heads a
word, then one pass over each page), so a decode step never pays a
full-cache ``swapaxes`` (or pad) copy. The valid-kv count
per slot is ``index + 1`` (the current token's k/v is written into the
cache before attention). On a CPU backend the kernel body executes in
interpret mode; on a TPU it compiles through Mosaic.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.consmax_decode.kernel import (consmax_decode,
                                                consmax_decode_paged)


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("window", "softcap", "merged", "scale",
                                   "bk", "fill_bound", "interpret"))
def consmax_decode_op(q, k, v, index, beta, gamma, *, window=0, softcap=0.0,
                      merged=True, scale=None, bk=256, fill_bound=True,
                      interpret=None, k_scale=None, v_scale=None):
    """q: (b, 1, H, dk); k, v: (b, L, hkv, dk) — the cache, consumed in its
    stored layout (the kernel blocks the hkv axis, so no per-step transpose
    copy); index: (b,) current position.

    Returns (b, 1, H, dk) in q.dtype. ``scale=1.0`` when q is pre-scaled
    (the model path); None applies 1/sqrt(dk) (the standalone convention).
    ``fill_bound`` (default True) bounds KV grid work by the traced fill
    level instead of cache capacity — ``index`` stays a value, so the
    compiled step is shared across every fill level.
    ``k_scale``/``v_scale``: (b, L, hkv) fp32 row scales for a quantized
    (int8/fp8) cache — traced operands, dequantized per-block in VMEM.
    """
    interp = _on_cpu() if interpret is None else interpret
    out = consmax_decode(q[:, 0], k, v, index + 1, beta, gamma,
                         window=window, softcap=softcap, merged=merged,
                         scale=scale, bk=bk, fill_bound=fill_bound,
                         interpret=interp, k_scale=k_scale, v_scale=v_scale)
    return out[:, None]


@partial(jax.jit, static_argnames=("window", "softcap", "merged", "scale",
                                   "fill_bound", "interpret"))
def consmax_decode_paged_op(q, kp, vp, layer, page_table, lengths, beta,
                            gamma, *, window=0, softcap=0.0, merged=True,
                            scale=None, fill_bound=True, interpret=None,
                            k_scale=None, v_scale=None):
    """Paged-pool variant. q: (b, 1, H, dk); kp, vp: every layer's shared
    page pools, stacked (n_layers, P, ps, hkv, dk) in the model's cache
    layout, read at ``layer`` (int32 scalar) in place, each program
    walking a run of table columns (the kernel says how it fetches and
    reads a page); page_table: (b, max_pages) int32; lengths: (b,) valid
    logical rows (index + active, already counting the token written this
    step).

    Returns (b, 1, H, dk) in q.dtype. ``fill_bound`` bounds the page-table
    walk by the traced batch-max fill instead of the table's capacity.
    ``k_scale``/``v_scale``: (n_layers, P, ps, hkv) fp32 scale pools for a
    quantized KV pool, fetched with their pages through the table.
    """
    interp = _on_cpu() if interpret is None else interpret
    out = consmax_decode_paged(q[:, 0], kp, vp, layer, page_table, lengths,
                               beta, gamma, window=window, softcap=softcap,
                               merged=merged, scale=scale,
                               fill_bound=fill_bound, interpret=interp,
                               k_scale=k_scale, v_scale=v_scale)
    return out[:, None]
