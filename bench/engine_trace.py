"""The serving engine's own spans, counters and named scopes, from a
profiler trace and the engine's host fields, to the numbers the engine's
per-layer readers use.

What the program records (``repro.serve.engine``, ``repro.models``):

* host spans on the profiler's clock, ``engine.step`` (a step annotation
  carrying ``step_num``) with children ``engine.admit``, ``engine.prefill``
  (``uid``, ``slot``; children ``engine.cow_copy``, ``engine.table_upload``,
  ``engine.first_token``), ``engine.decode`` (children ``engine.cow_copy``,
  ``engine.table_upload``, ``engine.decode_drain``), ``engine.record``,
  ``engine.finish`` (``uid``, ``slot``); and ``gc`` around every Python
  collection;
* ``engine.counters`` and ``engine.request_times`` (perf_counter stamps),
  which ``run.py``'s traced run keeps around its window;
* ``jax.named_scope`` names in each HLO op's ``op_name`` metadata:
  ``embed``, ``layers``, ``attn_proj``, ``kv_cache``, ``attn``, ``mlp``,
  ``unembed_sample``.

A device op's ``op_name`` is read from the trace event's stats where one
holds it (``OP_NAME_STATS``), else from the compiled program's HLO text
(``hlo_op_names``, ``fill_op_names``). An op that carries none is
``unscoped`` (XLA's own copies); one whose ``op_name`` names no scope is
``other``.

``trace_reduce`` is used as it is: this module adds the engine's events
beside what it reads, and the reductions below.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

import readers
import trace_reduce as T

SCOPES = ("embed", "layers", "attn_proj", "kv_cache", "attn", "mlp",
          "unembed_sample")
OP_NAME_STATS = ("tf_op", "op_name")
# host time of a step that waits on the device: the blocking token reads
WAITS = ("engine.decode_drain", "engine.first_token")
_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_HLO_OP = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?op_name=\"((?:[^\"\\]|\\.)*)\"",
    re.M)


def engine_span(name: str) -> bool:
    return name.startswith("engine.") or name == "gc"


def instr_name(name: str) -> str:
    """``%fusion.208 = bf16[...] ...`` -> ``fusion.208`` (numbering kept:
    the key of the op in the program's HLO)."""
    m = _INSTR.match(name)
    return m.group(1) if m else name


def hlo_op_names(text: str) -> dict:
    """Instruction name -> ``op_name``, from a compiled program's HLO text
    (``engine.compile_step(name).as_text()``)."""
    return {m.group(1): m.group(2) for m in _HLO_OP.finditer(text)}


def scope_of(op_name: str | None) -> str:
    """The innermost named scope in an ``op_name`` path."""
    if not op_name:
        return "unscoped"
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return "other"


def read(path: str, span_names=()) -> dict:
    """``trace_reduce.read`` plus the engine's events: ``engine`` holds
    (start_ns, end_ns, name, line, stats) of every ``engine.*`` and ``gc``
    host event; each device gains ``op_names``, module -> instruction ->
    ``op_name`` where the trace's stats hold one, and ``op_stats``, the
    stat names seen on its ops (read by hand to find the ``op_name``)."""
    from jax._src.lib import _profile_data
    ev = T.read(path, span_names)
    data = _profile_data.ProfileData.from_file(path)
    engine = []
    for plane in data.planes:
        if plane.name in ev["devices"]:
            dev = ev["devices"][plane.name]
            names, seen = defaultdict(dict), set()
            ops = sorted(dev["ops"])
            by_start = {}
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    stats = dict(e.stats)
                    seen.update(stats)
                    op = next((stats[k] for k in OP_NAME_STATS
                               if stats.get(k)), None)
                    if op is not None:
                        by_start[(e.start_ns, e.name)] = op
            for (s, _, name), mod in zip(ops, T.owners(ops, dev["modules"])):
                op = by_start.get((s, name))
                if op is not None and mod:
                    names[mod][instr_name(name)] = op
            dev["op_names"] = dict(names)
            dev["op_stats"] = sorted(seen)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if engine_span(e.name):
                        engine.append((e.start_ns, e.end_ns, e.name, i,
                                       {k: v for k, v in e.stats
                                        if not k.startswith("_")}))
    ev["engine"] = engine
    return ev


def fill_op_names(ev: dict, hlo: dict):
    """Fill each device's ``op_names`` from the compiled steps' HLO text
    (``hlo``: step -> text) where the trace's stats hold none; where they
    were read goes to ``ev["op_name_source"]``."""
    source = "trace_stats"
    for dev in ev["devices"].values():
        if any(dev["op_names"].values()):
            continue
        source = "hlo_text"
        for text in hlo.values():
            mod = re.search(r"HloModule (\w+)", text).group(1)
            dev["op_names"][mod] = hlo_op_names(text)
    ev["op_name_source"] = source


# ------------------------------------------------------------ reductions ----
def nest(spans):
    """Parent index of each span (None at top level), by containment on
    its own line; spans sorted by (line, start, -end)."""
    parent, stack = [], []
    for i, (s, e, _, line, _) in enumerate(spans):
        while stack and (spans[stack[-1]][3] != line
                         or spans[stack[-1]][1] < e):
            stack.pop()
        parent.append(stack[-1] if stack else None)
        stack.append(i)
    return parent


def span_table(spans, lo, hi) -> dict:
    """Per span name inside [lo, hi]: count, total seconds, self seconds
    (duration less its children's)."""
    spans = sorted((s for s in spans if s[0] >= lo and s[1] <= hi),
                   key=lambda s: (s[3], s[0], -s[1]))
    parent = nest(spans)
    child = [0] * len(spans)
    for i, p in enumerate(parent):
        if p is not None:
            child[p] += spans[i][1] - spans[i][0]
    out = {}
    for (s, e, name, *_), c in zip(spans, child):
        row = out.setdefault(name, {"count": 0, "total_s": 0.0,
                                    "self_s": 0.0})
        row["count"] += 1
        row["total_s"] += (e - s) / 1e9
        row["self_s"] += (e - s - c) / 1e9
    return out


def step_host_s(spans, lo, hi) -> list[float]:
    """Each ``engine.step`` inside [lo, hi]: its duration less the time of
    the blocking token reads (``WAITS``) under it."""
    spans = sorted((s for s in spans if s[0] >= lo and s[1] <= hi),
                   key=lambda s: (s[3], s[0], -s[1]))
    parent = nest(spans)
    top = {}
    for i, p in enumerate(parent):
        while p is not None and spans[p][2] != "engine.step":
            p = parent[p]
        top[i] = p
    host = {i: (s[1] - s[0]) for i, s in enumerate(spans)
            if s[2] == "engine.step"}
    for i, s in enumerate(spans):
        if s[2] in WAITS and top[i] is not None:
            host[top[i]] -= s[1] - s[0]
    return [v / 1e9 for _, v in sorted(host.items())]


def scope_table(dev: dict, lo, hi) -> tuple[dict, dict]:
    """Per program: device seconds inside [lo, hi] by innermost scope
    (the scan's ``while`` container left out, as in ``trace_reduce``);
    and the seconds of the ops that carry no ``op_name``, by
    ``<program>/<op>``."""
    ops = sorted(dev["ops"])
    names = dev.get("op_names", {})
    out = defaultdict(lambda: defaultdict(float))
    unscoped = defaultdict(float)
    for (s, e, name), mod in zip(ops, T.owners(ops, dev["modules"])):
        short = T.op_name(name)
        if s < lo or e > hi or short in T.CONTAINERS:
            continue
        scope = scope_of(names.get(mod, {}).get(instr_name(name)))
        out[mod][scope] += (e - s) / 1e9
        if scope == "unscoped":
            unscoped[f"{mod}/{short}"] += (e - s) / 1e9
    return {m: dict(v) for m, v in out.items()}, dict(unscoped)


def summarize(ev: dict, harness_spans=(), top: int = 10) -> dict:
    """The engine's spans and scopes inside the traced window: the span
    table, each ``engine.step``'s host seconds, idle gaps named by the
    innermost span over them (engine, ``gc`` or the harness's), and the
    device seconds of each program by scope, with its run count, and the
    ops that carry no scope."""
    if ev["window"] is None or not ev["devices"]:
        return {}
    lo, hi = ev["window"]
    spans = [tuple(s) for s in ev["engine"]]
    dev0 = next(iter(ev["devices"].values()))
    busy = T.union([(s, e) for s, e, _ in dev0["ops"]], lo, hi)
    idle = sorted(T.gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    named = [(s, e, n) for s, e, n, *_ in spans] + [
        tuple(s) for s in harness_spans]
    runs = defaultdict(int)
    for s, e, name in dev0["modules"]:
        if s >= lo and e <= hi:
            runs[name] += 1
    scopes, unscoped = scope_table(dev0, lo, hi)
    return {
        "spans": span_table(spans, lo, hi),
        "step_host_s": step_host_s(spans, lo, hi),
        "idle_gaps": [[T.attribute(g, named), (g[1] - g[0]) / 1e9]
                      for g in idle],
        "scopes": scopes,
        "unscoped_ops": sorted(unscoped.items(), key=lambda kv: -kv[1])[:top],
        "runs": dict(runs),
        "op_stats": dev0.get("op_stats", []),
    }


@dataclasses.dataclass
class EngineRunData(readers.RunData):
    """``RunData`` with what the engine records of the window: its counter
    deltas, its requests' times, the window's perf_counter bounds, and
    ``summarize``'s tables."""
    counters: dict | None = None
    request_times: dict | None = None
    window: tuple | None = None
    engine: dict | None = None
