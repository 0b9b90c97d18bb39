"""The four serving kernels compile for a TPU v5e at published widths.

Interpret mode (every other kernel test) runs the kernel bodies on the CPU
and checks none of the TPU's tiling rules. Here Mosaic compiles each kernel
for a *described* v5e chip — nothing runs, so this says nothing about
results or speed — at the widths of qwen2-1.5b (H=12, hkv=2, dk=128) and
gemma2-2b (H=8, hkv=4, dk=256, window 4096, softcap 50), with a bf16 and an
int8 KV cache, at the serving shapes the chip smoke test uses: 8 slots of
4096 rows, 256-row pages, 512-token prefill chunks. The paged decode kernel
also compiles at the benchmark's decode-long table (24 slots x 32 pages) at
qwen2-1.5b, granite-3-2b and mellum2-12b-a2.5b widths.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every pytest-xdist worker
imports every test file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels import cache_layout as CL
from repro.kernels.consmax_decode.kernel import (consmax_decode,
                                                 consmax_decode_paged)
from repro.kernels.consmax_prefill.kernel import (consmax_prefill,
                                                  consmax_prefill_paged)

SLOTS, MAX_SEQ, PAGE, CHUNK = 8, 4096, 256, 512
PAGES = SLOTS * MAX_SEQ // PAGE


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _launch(kernel, cfg, kv_dtype, shape):
    """(fn, argument shapes) for one kernel at ``cfg``'s widths."""
    H, hkv, dk = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    dt = CL.kv_cache_dtype(kv_dtype)
    quant = CL.kv_quantized(dt)
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    kw = dict(window=cfg.window, softcap=cfg.attn_softcap, interpret=False)
    params = [shape((H,), f32), shape((H,), f32)]              # beta, gamma
    if kernel in ("decode", "prefill"):
        b = SLOTS if kernel == "decode" else 1
        cache = [shape((b, MAX_SEQ, hkv, dk), dt)] * 2
        scales = [shape((b, MAX_SEQ, hkv), f32)] * 2 if quant else []
    else:
        b = SLOTS if kernel == "decode_paged" else 1
        cache = [shape((PAGES, PAGE, hkv, dk), dt)] * 2
        scales = [shape((PAGES, PAGE, hkv), f32)] * 2 if quant else []
        table = shape((b, MAX_SEQ // PAGE), i32)
    if kernel.startswith("decode"):
        q = shape((b, H, dk), bf16)
        rows = [shape((b,), i32)]                              # lengths
    else:
        q = shape((b, CHUNK, H, dk), bf16)
        rows = [shape((b,), i32)] * 2                          # index, lengths
    fn = {"decode": consmax_decode, "decode_paged": consmax_decode_paged,
          "prefill": consmax_prefill,
          "prefill_paged": consmax_prefill_paged}[kernel]
    pos = ([q, *cache] + ([] if kernel in ("decode", "prefill") else [table])
           + rows + params)

    def call(*args):
        n = len(pos)
        ks, vs = args[n:] if quant else (None, None)
        return fn(*args[:n], k_scale=ks, v_scale=vs, **kw)

    return call, pos + scales


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "gemma2-2b"])
@pytest.mark.parametrize("kernel", ["decode", "decode_paged", "prefill",
                                    "prefill_paged"])
def test_serving_kernel_compiles_for_v5e(one_chip, kernel, arch, kv_dtype):
    cfg = get_config(arch)
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn, args = _launch(kernel, cfg, kv_dtype, shape)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


CELL_SLOTS, CELL_COLUMNS = 24, 32      # the decode-long cells' table
CELL_WIDTHS = {"qwen2-1.5b": (12, 2, 128, 0), "granite-3-2b": (32, 8, 64, 0),
               "mellum2-12b-a2.5b": (32, 4, 128, 1024)}   # H, hkv, dk, window


def _sources(hlo: str, name: str) -> list[str]:
    """Opcodes from operand ``%name`` of a compiled module back through
    bitcasts (free reinterpretations) to what made it."""
    defs = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*)", line)
        if m:
            defs[m.group(1)] = m.group(2)
    chain = []
    while True:
        rest = defs[name]
        op = re.search(r"\s([\w\-]+)\(", rest).group(1)
        chain.append(op)
        if op != "bitcast":
            return chain
        name = re.search(r"bitcast\(%([\w.\-]+)\)", rest).group(1)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("arch", sorted(CELL_WIDTHS))
def test_paged_decode_compiles_at_the_cell_shape_for_v5e(one_chip, arch,
                                                         kv_dtype):
    """The paged decode kernel at the decode-long cells' table (24 slots x
    32 pages of 256 rows). At qwen2-1.5b's widths, and at mellum2's 4 heads
    of dk 128 with its 1024-row window, in bf16 the pools reach the kernel
    as bitcasts of the arguments: the (P, ps * hkv, dk) word view is free
    and no copy of a pool feeds the custom call. (An int8 pool and
    granite's 8 heads of dk 64 are relaid out by XLA first.)"""
    H, hkv, dk, window = CELL_WIDTHS[arch]
    dt = CL.kv_cache_dtype(kv_dtype)
    quant = CL.kv_quantized(dt)
    pages = CELL_SLOTS * CELL_COLUMNS
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    args = [shape((CELL_SLOTS, H, dk), jnp.bfloat16),
            *[shape((pages, PAGE, hkv, dk), dt)] * 2,
            shape((CELL_SLOTS, CELL_COLUMNS), jnp.int32),
            shape((CELL_SLOTS,), jnp.int32), *[shape((H,), jnp.float32)] * 2]
    scales = [shape((pages, PAGE, hkv), jnp.float32)] * 2 if quant else []

    def call(*a):
        ks, vs = a[7:] if quant else (None, None)
        return consmax_decode_paged(*a[:7], k_scale=ks, v_scale=vs,
                                    window=window, interpret=False)

    text = jax.jit(call).lower(*args, *scales).compile().as_text()
    kernel = next(line for line in text.splitlines()
                  if "tpu_custom_call" in line)
    operands = re.findall(r"%([\w.\-]+)", kernel.split("custom-call(", 1)[1]
                          .split("custom_call_target", 1)[0])[1:]
    pools = [o for o in operands
             if f"[{pages}," in text.split(f"%{o} = ", 1)[1].split("\n")[0]]
    assert len(pools) == (4 if quant else 2)
    if arch != "granite-3-2b" and not quant:
        assert all(_sources(text, o)[-1] == "parameter" for o in pools), \
            [_sources(text, o) for o in pools]


def test_engine_decode_step_keeps_its_names_for_v5e(one_chip, monkeypatch):
    """Named scopes live in the ops' metadata only: the engine's paged
    decode step, compiled for a v5e at qwen2-1.5b's attention widths (two
    layers, a small vocabulary), keeps its module name and the kernel's
    custom-call name, which a device trace is read by, and its ops carry
    the ``kv_cache`` scope."""
    from repro.configs.base import ServeConfig
    from repro.kernels.consmax_decode import ops as decode_ops
    from repro.models import transformer as T
    from repro.nn import module as nn
    from repro.serve.engine import ContinuousBatchingEngine
    monkeypatch.setattr(decode_ops, "_on_cpu", lambda: False)
    cfg = get_config("qwen2-1.5b", n_layers=2, vocab_size=1024,
                     param_dtype="bfloat16")
    scfg = ServeConfig(score_norm=cfg.score_norm, max_slots=SLOTS,
                       max_seq=1024, paged_kv=True, page_size=PAGE,
                       prefill_chunk=CHUNK, decode_kernel=True,
                       prefill_kernel=True)
    eng = ContinuousBatchingEngine(cfg, scfg, nn.abstract_init(T.lm_init,
                                                                cfg))
    place = functools.partial(jax.tree.map, lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip))
    inputs = eng._decode_inputs(eng._last, [False] * SLOTS)
    text = eng._decode.lower(place(eng.params), place(eng.caches),
                             place(inputs), place(eng.bank)).compile(
                             ).as_text()
    assert text.startswith("HloModule jit_decode_step")
    assert "%consmax_decode_paged_op" in text
    assert "tpu_custom_call" in text
    assert "/kv_cache/" in text


def test_mellum2_decode_step_keeps_its_names_for_v5e(one_chip, monkeypatch):
    """Mellum2's paged decode step (one period of four layers at published
    widths, a small vocabulary), compiled for a v5e: the grouped expert
    matmuls are three ``gmm`` kernels a layer under the ``moe_experts``
    scope, inside ``moe``; the decode kernel keeps its name; the step's
    outputs are the tokens and the expert counts."""
    from repro.configs.base import ServeConfig
    from repro.kernels.consmax_decode import ops as decode_ops
    from repro.models import moe
    from repro.models import transformer as T
    from repro.nn import module as nn
    from repro.serve.engine import ContinuousBatchingEngine
    monkeypatch.setattr(decode_ops, "_on_cpu", lambda: False)
    monkeypatch.setattr(moe, "_on_cpu", lambda: False)
    cfg = get_config("mellum2-12b-a2.5b", n_layers=4, vocab_size=1024,
                     param_dtype="bfloat16")
    scfg = ServeConfig(score_norm=cfg.score_norm, max_slots=SLOTS,
                       max_seq=1024, paged_kv=True, page_size=PAGE,
                       prefill_chunk=CHUNK, decode_kernel=True,
                       prefill_kernel=True)
    eng = ContinuousBatchingEngine(cfg, scfg, nn.abstract_init(T.lm_init,
                                                                cfg))
    place = functools.partial(jax.tree.map, lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip))
    inputs = eng._decode_inputs(eng._last, [False] * SLOTS)
    lowered = eng._decode.lower(place(eng.params), place(eng.caches),
                                place(inputs), place(eng.bank))
    tokens, counts = lowered.out_info[0]
    assert tokens.shape == (SLOTS,) and counts.shape == (2,)
    text = lowered.compile().as_text()
    assert text.startswith("HloModule jit_decode_step")
    gmm = [line for line in text.splitlines() if re.match(r"\s*%gmm", line)]
    assert len(gmm) == 3 * cfg.n_layers
    assert all("tpu_custom_call" in g for g in gmm)
    assert all("/moe/moe_experts/" in g for g in gmm)
    assert "%consmax_decode_paged_op" in text
