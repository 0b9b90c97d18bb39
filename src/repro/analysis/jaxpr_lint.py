"""Generic jaxpr traversal engine + pluggable serving-step lint rules.

The traversal (``iter_eqns``) walks a jaxpr and every sub-jaxpr reachable
through equation params — ``pjit`` calls, ``scan``/``while`` bodies,
``cond`` branches, custom-derivative rules — so a rule sees the whole
program a serving step traces to, not just its top level. Rules are small
objects with a ``name``, a one-line ``doc`` (the rule catalog in README /
``ANALYSIS.json`` is generated from these), and a ``check(target)`` that
returns :class:`Finding`\\ s. A :class:`StepTarget` bundles what the rules
need to know about one serving step: its closed jaxpr, the element-count
threshold above which an array counts as *cache-sized*, the vocab size when
fused sampling promises token-only outputs, and the cache leaf avals going
in and coming out (dtype stability).

The concrete rules encode the serving-path contract:

* ``no-cache-sized-layout-ops`` — no ``transpose`` / ``pad`` / ``copy`` /
  ``convert_element_type`` of a cache-sized operand anywhere in a serving
  step. The cache-layout kernels exist so that no step ever materializes a
  relaid-out copy of the KV cache; one stray ``swapaxes`` reintroduces a
  full-cache copy per token.
* ``no-vocab-sized-outputs`` — with fused sampling, the steps return
  ``(b,)`` int32 tokens; a vocab-sized output aval means a per-token
  ``(b, vocab)`` host transfer crept back in.
* ``no-host-callbacks`` — no ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` inside a jitted serving step: a callback serializes
  the step on the host and breaks the device-resident decode loop.
* ``cache-dtype-stability`` — every cache leaf must come out of a step
  with the dtype it went in with: an accidental upcast doubles KV HBM, a
  downcast silently re-quantizes the cache each step.
* ``quant-scale-contract`` — quantized-KV scale leaves stay fp32 across a
  step, and no cache-sized *widening* convert materializes in HBM: the
  whole point of an int8/fp8 cache is that dequantization happens
  per-block in VMEM, never as a full-cache fp32/bf16 copy.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

from jax.extend.core import ClosedJaxpr, Jaxpr

# cache-layout ops that must never touch a cache-sized operand in a serving
# step (each one is a full-cache copy per token / per chunk)
LAYOUT_PRIMS = ("transpose", "pad", "copy", "convert_element_type")

# host-boundary primitives that must not appear inside a jitted serving step
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback", "host_callback", "outside_call",
})


@dataclass(frozen=True)
class Finding:
    """One rule violation: ``rule`` names the rule, ``target`` the step or
    kernel it fired on, ``detail`` is a small json-able tuple (primitive
    names, shapes, dtypes) locating the violation."""
    rule: str
    target: str
    message: str
    detail: tuple = ()

    def to_json(self) -> dict:
        return {"rule": self.rule, "target": self.target,
                "message": self.message, "detail": _jsonify(self.detail)}


def _jsonify(v):
    if isinstance(v, (tuple, list)):
        return [_jsonify(x) for x in v]
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    return v if isinstance(v, (str, int, float, bool, type(None))) else str(v)


# ------------------------------------------------------------ traversal ----
def iter_eqns(jaxpr, skip_into=frozenset()):
    """Yield every equation in ``jaxpr`` (a ``Jaxpr`` or ``ClosedJaxpr``)
    and, depth-first, in every sub-jaxpr reachable through equation params:
    ``pjit`` bodies, ``scan``/``while`` carries, ``cond`` branches,
    ``custom_jvp``/``custom_vjp`` rules — wherever jax nests a program.

    Equations whose primitive name is in ``skip_into`` are still yielded
    but their sub-jaxprs are not entered — e.g. a rule about HBM-level
    array ops passes ``{"pallas_call"}`` because a kernel body's per-block
    VMEM compute is deliberately blocked (and is the kernel-contracts
    layer's jurisdiction, not the jaxpr lint's)."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name in skip_into:
            continue
        for v in eqn.params.values():
            yield from _iter_param(v, skip_into)


def _iter_param(v, skip_into=frozenset()):
    if isinstance(v, ClosedJaxpr):
        yield from iter_eqns(v.jaxpr, skip_into)
    elif isinstance(v, Jaxpr):
        yield from iter_eqns(v, skip_into)
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _iter_param(x, skip_into)
    elif isinstance(v, dict):
        for x in v.values():
            yield from _iter_param(x, skip_into)


def _aval_elems(aval) -> int:
    shape = tuple(getattr(aval, "shape", ()))
    return int(np.prod(shape)) if shape else 1


def cache_sized_ops(jaxpr, threshold: int,
                    prims=LAYOUT_PRIMS) -> list[tuple[str, tuple]]:
    """All ``(primitive_name, operand_shape)`` pairs where a primitive in
    ``prims`` consumes an operand of >= ``threshold`` elements, anywhere in
    ``jaxpr`` or its sub-jaxprs — except inside Pallas kernel bodies, whose
    per-block ops live in VMEM by construction. The first input var is the
    operand for every primitive in :data:`LAYOUT_PRIMS` (``pad``'s second
    input is the scalar padding value)."""
    bad = []
    for eqn in iter_eqns(jaxpr, skip_into=frozenset({"pallas_call"})):
        if eqn.primitive.name in prims and eqn.invars:
            aval = getattr(eqn.invars[0], "aval", None)
            if aval is not None and _aval_elems(aval) >= threshold:
                bad.append((eqn.primitive.name, tuple(aval.shape)))
    return bad


def vocab_sized_avals(tree, vocab_size: int) -> list[tuple]:
    """Shapes of leaves in ``tree`` (avals / ShapeDtypeStructs / arrays)
    that carry ``vocab_size`` along any axis — the fused-sampling steps
    must produce none."""
    return [tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(tree)
            if vocab_size in tuple(getattr(leaf, "shape", ()))]


# --------------------------------------------------------------- target ----
@dataclass
class StepTarget:
    """One serving step under lint.

    ``cache_cells`` — element count above which an operand counts as
    cache-sized (``None`` disables the layout rule). ``vocab_size`` — set
    iff the step promises token-only outputs (fused sampling); ``None``
    disables the vocab rule (the legacy logits steps return vocab-sized
    logits on purpose). ``cache_in`` / ``cache_out`` — flat, same-order
    cache leaf avals entering and leaving the step (anything with
    ``.shape``/``.dtype``); empty disables the dtype-stability rule.
    ``scale_leaves`` — indices into ``cache_in``/``cache_out`` naming the
    quantization-scale leaves of a quantized KV cache; empty disables the
    scale half of the quant-scale rule (the widening-convert half still
    runs whenever ``cache_cells`` is set)."""
    name: str
    jaxpr: ClosedJaxpr
    cache_cells: int | None = None
    vocab_size: int | None = None
    cache_in: tuple = ()
    cache_out: tuple = ()
    scale_leaves: tuple = ()


# ---------------------------------------------------------------- rules ----
@dataclass(frozen=True)
class NoCacheSizedLayoutOps:
    name = "no-cache-sized-layout-ops"
    doc = ("no transpose/pad/copy/convert_element_type of a cache-sized "
           "operand in a serving step (each is a full-cache copy per token)")
    prims: tuple = LAYOUT_PRIMS

    def check(self, t: StepTarget) -> list[Finding]:
        if not t.cache_cells:
            return []
        return [Finding(self.name, t.name,
                        f"{prim} of cache-sized operand {shape} "
                        f"(>= {t.cache_cells} elements)", (prim, shape))
                for prim, shape in cache_sized_ops(t.jaxpr, t.cache_cells,
                                                   self.prims)]


@dataclass(frozen=True)
class NoVocabSizedOutputs:
    name = "no-vocab-sized-outputs"
    doc = ("fused-sampling steps return (b,) int32 tokens — a vocab-sized "
           "output aval is a per-token logits transfer reintroduced")

    def check(self, t: StepTarget) -> list[Finding]:
        if not t.vocab_size:
            return []
        return [Finding(self.name, t.name,
                        f"vocab-sized output aval {shape} from a "
                        f"fused-sampling step (vocab={t.vocab_size})",
                        (shape,))
                for shape in vocab_sized_avals(list(t.jaxpr.out_avals),
                                               t.vocab_size)]


@dataclass(frozen=True)
class NoHostCallbacks:
    name = "no-host-callbacks"
    doc = ("no pure_callback/io_callback/debug_callback inside a jitted "
           "serving step (host round-trip per step)")
    prims: frozenset = CALLBACK_PRIMS

    def check(self, t: StepTarget) -> list[Finding]:
        return [Finding(self.name, t.name,
                        f"host callback primitive {eqn.primitive.name!r} "
                        "inside a jitted serving step",
                        (eqn.primitive.name,))
                for eqn in iter_eqns(t.jaxpr)
                if eqn.primitive.name in self.prims]


@dataclass(frozen=True)
class CacheDtypeStability:
    name = "cache-dtype-stability"
    doc = ("every cache leaf leaves a step with the dtype it entered with "
           "(no silent KV upcast/requantize)")

    def check(self, t: StepTarget) -> list[Finding]:
        if not t.cache_in and not t.cache_out:
            return []
        found = []
        if len(t.cache_in) != len(t.cache_out):
            return [Finding(self.name, t.name,
                            f"cache tree changed arity across the step: "
                            f"{len(t.cache_in)} leaves in, "
                            f"{len(t.cache_out)} out",
                            (len(t.cache_in), len(t.cache_out)))]
        for i, (a, b) in enumerate(zip(t.cache_in, t.cache_out)):
            if np.dtype(a.dtype) != np.dtype(b.dtype):
                found.append(Finding(
                    self.name, t.name,
                    f"cache leaf {i} {tuple(a.shape)} went in {a.dtype} "
                    f"and came out {b.dtype}",
                    (i, str(np.dtype(a.dtype)), str(np.dtype(b.dtype)))))
        return found


@dataclass(frozen=True)
class QuantScaleContract:
    name = "quant-scale-contract"
    doc = ("quantized-KV scale leaves stay fp32 across a step and no "
           "cache-sized widening convert (a dequantized full-cache copy) "
           "materializes in HBM")

    def check(self, t: StepTarget) -> list[Finding]:
        found = []
        f32 = np.dtype(np.float32)
        for i in t.scale_leaves:
            if i >= len(t.cache_in) or i >= len(t.cache_out):
                continue
            for side, leaf in (("in", t.cache_in[i]),
                               ("out", t.cache_out[i])):
                if np.dtype(leaf.dtype) != f32:
                    found.append(Finding(
                        self.name, t.name,
                        f"scale leaf {i} {tuple(leaf.shape)} is "
                        f"{leaf.dtype} on the way {side} (must stay "
                        "float32: scales set the dequant precision)",
                        (i, side, str(np.dtype(leaf.dtype)))))
        if t.cache_cells:
            skip = frozenset({"pallas_call"})
            for eqn in iter_eqns(t.jaxpr, skip_into=skip):
                if eqn.primitive.name != "convert_element_type":
                    continue
                if not eqn.invars or not eqn.outvars:
                    continue
                src = getattr(eqn.invars[0], "aval", None)
                dst = getattr(eqn.outvars[0], "aval", None)
                if src is None or dst is None:
                    continue
                if (_aval_elems(src) >= t.cache_cells
                        and np.dtype(dst.dtype).itemsize
                        > np.dtype(src.dtype).itemsize):
                    found.append(Finding(
                        self.name, t.name,
                        f"cache-sized widening convert {tuple(src.shape)} "
                        f"{src.dtype} -> {dst.dtype}: a dequantized "
                        "full-cache copy materialized in HBM (dequant "
                        "belongs per-block in VMEM)",
                        (tuple(src.shape), str(np.dtype(src.dtype)),
                         str(np.dtype(dst.dtype)))))
        return found


DEFAULT_RULES = (NoCacheSizedLayoutOps(), NoVocabSizedOutputs(),
                 NoHostCallbacks(), CacheDtypeStability(),
                 QuantScaleContract())


def run_rules(target: StepTarget, rules=DEFAULT_RULES) -> list[Finding]:
    """Run every rule against one step target; returns all findings."""
    findings = []
    for rule in rules:
        findings.extend(rule.check(target))
    return findings


def rule_catalog(rules=DEFAULT_RULES) -> dict[str, str]:
    return {r.name: r.doc for r in rules}
