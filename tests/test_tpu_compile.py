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
import math
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
        cache = [shape((1, PAGES, PAGE, hkv, dk), dt)] * 2
        scales = [shape((1, PAGES, PAGE, hkv), f32)] * 2 if quant else []
        table = [shape((), i32), shape((b, MAX_SEQ // PAGE), i32)]
    if kernel.startswith("decode"):
        q = shape((b, H, dk), bf16)
        rows = [shape((b,), i32)]                              # lengths
    else:
        q = shape((b, CHUNK, H, dk), bf16)
        rows = [shape((b,), i32)] * 2                          # index, lengths
    fn = {"decode": consmax_decode, "decode_paged": consmax_decode_paged,
          "prefill": consmax_prefill,
          "prefill_paged": consmax_prefill_paged}[kernel]
    pos = ([q, *cache] + ([] if kernel in ("decode", "prefill") else table)
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
    32 pages of 256 rows), reading one layer of two stacked pools. At
    qwen2-1.5b's, granite-3-2b's and mellum2's widths (with its 1024-row
    window), bf16 or int8, every pool and scale pool reaches the kernel as
    a bitcast of the argument: the view the kernel reads (qwen2's bf16
    (n_layers, P, ps * hkv, dk) words, or ``CL.page_view``'s order) is
    the stored layout, and no copy of a pool feeds the custom call."""
    H, hkv, dk, window = CELL_WIDTHS[arch]
    dt = CL.kv_cache_dtype(kv_dtype)
    quant = CL.kv_quantized(dt)
    pages = CELL_SLOTS * CELL_COLUMNS
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    args = [shape((CELL_SLOTS, H, dk), jnp.bfloat16),
            *[shape((2, pages, PAGE, hkv, dk), dt)] * 2,
            shape((), jnp.int32),
            shape((CELL_SLOTS, CELL_COLUMNS), jnp.int32),
            shape((CELL_SLOTS,), jnp.int32), *[shape((H,), jnp.float32)] * 2]
    scales = [shape((2, pages, PAGE, hkv), jnp.float32)] * 2 if quant else []

    def call(*a):
        ks, vs = a[8:] if quant else (None, None)
        return consmax_decode_paged(*a[:8], k_scale=ks, v_scale=vs,
                                    window=window, interpret=False)

    text = jax.jit(call).lower(*args, *scales).compile().as_text()
    kernel = next(line for line in text.splitlines()
                  if "tpu_custom_call" in line)
    operands = re.findall(r"%([\w.\-]+)", kernel.split("custom-call(", 1)[1]
                          .split("custom_call_target", 1)[0])[1:]
    pools = [o for o in operands
             if f"[2,{pages}," in text.split(f"%{o} = ", 1)[1].split("\n")[0]]
    assert len(pools) == (4 if quant else 2)
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


@pytest.mark.parametrize("dtype", ["bfloat16", "int8", "float32"])
def test_page_view_is_the_layout_xla_stores_for_v5e(one_chip, dtype):
    """``CL.page_view`` names the order XLA's TPU layout stores a stacked
    pool in, compiled for a v5e, wherever it claims one: K/V pages (bf16,
    int8) with whole 128-lane head rows or of 128-row multiples, and fp32
    scale pages (``float32``) of 128-row multiples. Where XLA puts pages
    or heads outermost instead, ``page_view`` says row-major (the view the
    kernels then read, relaid out)."""
    orders = {(0, 1, 2, 3, 4): CL.ROW_MAJOR, (0, 1, 3, 2, 4): CL.HEAD_MAJOR,
              (0, 1, 3, 4, 2): CL.ROWS_MINOR, (0, 1, 2, 3): CL.ROW_MAJOR,
              (0, 1, 3, 2): CL.ROWS_MINOR}
    dt = jnp.dtype(dtype)
    checked = 0
    for hkv in (1, 2, 3, 4, 6, 8, 12):
        for ps in (64, 256):
            for dk in ((None,) if dtype == "float32" else (64, 128)):
                page = (ps, hkv) if dk is None else (ps, hkv, dk)
                if ps % 128 and dk != 128:
                    continue                     # not claimed
                stored = jax.jit(lambda x: x).lower(jax.ShapeDtypeStruct(
                    (2, 768, *page), dt, sharding=one_chip)).compile(
                    ).input_formats[0][0].layout.major_to_minor
                want = orders.get(tuple(stored), CL.ROW_MAJOR)
                got = CL.page_view(dt, page)
                if hkv == 1 and {got, want} <= {CL.ROW_MAJOR,
                                                CL.HEAD_MAJOR}:
                    want = got                   # one head: the same bytes
                assert got == want, (page, stored)
                checked += 1
    assert checked


# the AOT cases of the carried-pool check: (arch, super-layers, overrides,
# page rows). Each has at least two super-layers, so the layer loop is a
# real loop: the cells' three widths in pages of 256, a dk-64 pool of
# another head count in pages of 128, which XLA also stores rows-minor, and
# qwen2's int8 pool, stored head-major, with its fp32 scale pools
CARRY_CASES = {"qwen2-1.5b": ("qwen2-1.5b", 2, {}, PAGE, "bfloat16"),
               "granite-3-2b": ("granite-3-2b", 2, {}, PAGE, "bfloat16"),
               "mellum2-12b-a2.5b": ("mellum2-12b-a2.5b", 8, {}, PAGE,
                                     "bfloat16"),
               "granite-3-2b.kv2_page128": ("granite-3-2b", 2,
                                            {"n_kv_heads": 2}, 128,
                                            "bfloat16"),
               "qwen2-1.5b.int8": ("qwen2-1.5b", 2, {}, PAGE, "int8")}
MOVES = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice",
         "transpose")


def _module(hlo: str):
    """(instructions, computations, entry) of a compiled module's text:
    name -> (result types, opcode, operand names, called computation,
    tuple index, computation it sits in); computation name -> the opcodes
    in it, its root's first; and the entry computation's name."""
    insts, comps, cur, entry = {}, {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = head.group(2)
            comps.setdefault(cur, [])
            entry = cur if head.group(1) else entry
            continue
        m = re.match(r"\s*(ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)", line)
        if not m:
            continue
        root, name, types, op, rest = m.groups()
        calls = re.search(r"(?:calls|body)=%([\w.\-]+)", rest)
        index = re.search(r"\bindex=(\d+)", rest.split(")", 1)[1])
        insts[name] = (types, op, re.findall(r"%([\w.\-]+)",
                                             rest.split(")", 1)[0]),
                       calls and calls.group(1),
                       index and int(index.group(1)), cur)
        if cur is not None:
            comps[cur].insert(0, op) if root else comps[cur].append(op)
    return insts, comps, entry


def _pool_sized(types: str, sizes) -> bool:
    """Whether a result type holds a buffer of one layer's pool or of the
    stack in any shape: a view, a slice or a re-stack of it. ``sizes``
    holds (element type, element count) pairs."""
    return any((etype, math.prod(int(d) for d in dims.split(",") if d))
               in sizes
               for etype, dims in re.findall(r"(\w+)\[([\d,]*)\]", types))


def _in_place(inst, comps) -> bool:
    """A scatter, or a fusion whose root is a scatter: it updates its first
    operand's buffer in place."""
    op, calls = inst[1], inst[3]
    return op == "scatter" or (op == "fusion" and comps[calls][0] ==
                               "scatter")


def _pool_chain(insts, comps, entry, name) -> list[str]:
    """The opcodes from kernel operand ``name`` back to the entry
    computation's parameter it reads, through bitcasts, tuple reads,
    in-place scatters and the layer loop: a read of a loop body's
    parameter continues at the same element of the loop's operand."""
    chain, element = [], None
    while True:
        types, op, operands, _, index, comp = insts[name]
        if op == "parameter" and comp == entry:
            return chain
        if op == "parameter":            # a while body's: go to its loop
            loop = [n for n, i in insts.items()
                    if i[1] == "while" and i[3] == comp]
            assert len(loop) == 1 and element is not None, chain
            chain.append("while")
            name = insts[loop[0]][2][0]
            continue
        chain.append(op)
        if op == "get-tuple-element" and insts[operands[0]][1] == "parameter":
            element = index
        elif op == "tuple":
            name, element = operands[element], None
            continue
        else:
            assert op in ("bitcast", "get-tuple-element") or _in_place(
                insts[name], comps), chain
        name = operands[0]


@pytest.mark.parametrize("case", list(CARRY_CASES))
def test_engine_steps_move_no_whole_pool_for_v5e(one_chip, monkeypatch,
                                                 case):
    """The engine's paged decode step and prefill chunk step, compiled for
    a v5e at each case's widths (a small vocabulary, 24 slots of 8192
    rows): the stacked pools ride the layer loop in place. No copy,
    dynamic-slice, dynamic-update-slice or transpose, fused or not, makes
    a buffer of a layer pool's or the stack's size, in any shape, and
    every paged kernel's pool operands come from the step's cache argument
    through bitcasts, tuple reads, in-place scatters and the loop only.
    Each pool is read in the view of the order XLA stores it in
    (``CL.page_view``): granite's rows-minor, qwen2's int8 pool
    head-major. (Its fp32 scale stacks, 1/128 of the pool's bytes, are
    not checked: XLA stages them through on-chip memory around each
    layer's page write.)"""
    from repro.configs.base import ServeConfig
    from repro.kernels.consmax_decode import ops as decode_ops
    from repro.kernels.consmax_prefill import ops as prefill_ops
    from repro.models import moe
    from repro.models import transformer as T
    from repro.nn import module as nn
    from repro.serve.engine import ContinuousBatchingEngine
    arch, n_layers, overrides, page, kv_dtype = CARRY_CASES[case]
    for mod in (decode_ops, prefill_ops, moe):
        monkeypatch.setattr(mod, "_on_cpu", lambda: False)
    shapes_only = T.init_paged_caches
    monkeypatch.setattr(T, "init_paged_caches", lambda *a, **k: jax.eval_shape(
        functools.partial(shapes_only, *a, **k)))
    cfg = get_config(arch, n_layers=n_layers, vocab_size=1024,
                     param_dtype="bfloat16", **overrides)
    scfg = ServeConfig(score_norm=cfg.score_norm, max_slots=CELL_SLOTS,
                       max_seq=CELL_COLUMNS * PAGE, paged_kv=True,
                       page_size=page, prefill_chunk=CHUNK,
                       decode_kernel=True, prefill_kernel=True,
                       kv_cache_dtype=kv_dtype)
    eng = ContinuousBatchingEngine(cfg, scfg, nn.abstract_init(T.lm_init,
                                                                cfg))
    place = functools.partial(jax.tree.map, lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip))
    eng.params, eng.caches = place(eng.params), place(eng.caches)
    eng.bank, eng._last = place(eng.bank), place(eng._last)
    leaves = eng.caches["b0"]["attn"]
    stack = leaves["k"].shape
    assert stack[0] >= 2 and stack[2] == page
    etype = {"bfloat16": "bf16", "int8": "s8"}[kv_dtype]
    pools = ((etype, math.prod(stack)), (etype, math.prod(stack[1:])))
    for step in ("decode", "prefill"):
        insts, comps, entry = _module(eng.compile_step(step).as_text())
        # a move anywhere, fused or not, or a fusion holding one that is
        # not an in-place scatter (whose moves touch its small operands)
        moved = [name for name, (types, op, _, calls, *_) in insts.items()
                 if (op in MOVES or op == "fusion"
                     and set(comps[calls]) & set(MOVES)
                     and not _in_place(insts[name], comps))
                 and _pool_sized(types, pools)]
        assert not moved, (step, moved)
        kernels = [name for name, i in insts.items()
                   if i[1] == "custom-call" and name.startswith(
                       ("consmax_decode_paged_op",
                        "consmax_prefill_paged_op"))]
        assert kernels, step
        for kernel in kernels:
            operands = [o for o in insts[kernel][2]
                        if _pool_sized(insts[o][0], pools)]
            assert len(operands) == 2, (step, kernel, operands)
            for name in operands:
                assert "while" in _pool_chain(insts, comps, entry, name), \
                    (step, kernel)
