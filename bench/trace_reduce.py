"""From a profiler trace (``.xplane.pb``) to the numbers the readers use.

What a TPU trace holds (read by hand from a v5e trace of this benchmark):
one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Modules`` has one
event per program run (``jit_decode_step(<id>)``) and whose line
``XLA Ops`` has one event per HLO op run, named by the op's whole HLO text
(``%consmax_decode_paged_op.3 = f32[...] custom-call(...)``) and carrying
no module stat: an op belongs to the program run whose interval holds it.
A Mosaic kernel is a custom-call op named after the jitted op that wraps
it. A scan's ``while`` op spans its body's ops on the same line. The host
plane ``/host:CPU`` holds the ``TraceAnnotation`` spans the harness puts
around the engine's calls, on the same clock.

Everything is clipped to the ``bench_window`` span. Busy time is the union
of op intervals on a chip; idle is the rest of the window.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench_window"
_SUFFIX = re.compile(r"\(\d+\)$")
# "%consmax_decode_paged_op.3 = bf16[...] custom-call(...)" -> the
# instruction's name, numbering dropped: "consmax_decode_paged_op"
_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\.[a-z_]+)? = ")
# ops that enclose others on the same line (a scan's loop): their time is
# their children's, so they stay out of per-op totals
CONTAINERS = ("while", "conditional", "call")


def op_name(name: str) -> str:
    m = _INSTR.match(name)
    return m.group(1) if m else name[:64]


def module_name(name: str) -> str:
    """``jit_decode_step(12)`` -> ``jit_decode_step``."""
    return _SUFFIX.sub("", name)


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def read(path: str, span_names) -> dict:
    """Events of interest, as plain tuples (start_ns, end_ns, ...)."""
    from jax._src.lib import _profile_data
    data = _profile_data.ProfileData.from_file(path)
    devices, spans, window = {}, [], None
    want = set(span_names)
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                "NON_CORE" not in plane.name:
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(e.start_ns, e.end_ns, module_name(e.name))
                            for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [(e.start_ns, e.end_ns, e.name)
                           for e in line.events]
            devices[plane.name] = {"modules": mods, "ops": ops}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.end_ns)
                    elif e.name in want:
                        spans.append((e.start_ns, e.end_ns, e.name))
    return {"devices": devices, "spans": spans, "window": window}


# ------------------------------------------------------------ reductions ----
def union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi] around merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap, spans):
    """The innermost host span covering the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for s, e, name in spans:
        if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "untracked"


def owners(ops, modules):
    """For each op (sorted by start), the name of the program run that
    holds it, or "" where none does."""
    runs = sorted(modules)
    out, j = [], 0
    for s, e, *_ in ops:
        while j < len(runs) and runs[j][1] < s:
            j += 1
        ok = j < len(runs) and runs[j][0] <= s and e <= runs[j][1]
        out.append(runs[j][2] if ok else "")
    return out


def summarize(ev: dict, top: int = 10) -> dict:
    """Busy, idle, per-module and per-op device seconds inside the window,
    averaged over the chips traced."""
    if ev["window"] is None or not ev["devices"]:
        return {}
    lo, hi = ev["window"]
    per_dev = []
    for dev in ev["devices"].values():
        ops = [(s, e) for s, e, _ in dev["ops"]] or \
            [(s, e) for s, e, _ in dev["modules"]]
        busy = union(ops, lo, hi)
        per_dev.append((dev, busy))
    n = len(per_dev)
    busy_ns = sum(e - s for _, b in per_dev for s, e in b) / n
    dev0, busy0 = per_dev[0]
    idle = sorted(gaps(busy0, lo, hi), key=lambda g: g[0] - g[1])[:top]
    op_time = defaultdict(float)
    kernel = defaultdict(lambda: [0.0, 0])
    ops0 = sorted(dev0["ops"])
    for (s, e, name), mod in zip(ops0, owners(ops0, dev0["modules"])):
        short = op_name(name)
        if s >= lo and e <= hi and short not in CONTAINERS:
            op_time[f"{mod}/{short}"] += (e - s) / 1e9
            k = kernel[(mod, short)]
            k[0] += (e - s) / 1e9
            k[1] += 1
    modules = defaultdict(list)
    for s, e, name in dev0["modules"]:
        if s >= lo and e <= hi:
            modules[name].append((e - s) / 1e9)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "modules": dict(modules),
        "ops": dict(kernel),
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[attribute(g, ev["spans"]), (g[1] - g[0]) / 1e9]
                      for g in idle],
    }
