"""Per-layer metrics: one reader per metric, found by name.

``bench/metrics/<name>.py`` defines ``read(run) -> float | None``. A reader
that finds nothing to read returns None and the metric is left out of the
result line; none returns 0 for a share of a roofline or of a peak.
``RunData`` is what a reader may read: the harness's records, the reduced
trace, the model module's costs and the chip's peaks; in a traced run of
``run.py`` also the engine's telemetry (``engine_trace.EngineRunData``).
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

import costs
import spec

BENCH = Path(__file__).resolve().parent


def load(name: str, bench: Path = BENCH):
    return spec.load_module(bench / "metrics" / f"{name}.py").read


@dataclasses.dataclass
class RunData:
    stats: dict
    rec: object
    trace: dict
    sizes: object
    peaks: dict
    max_slots: int
    kv_bytes: int = 2
    model: object = None        # the configuration's model module

    # ------------------------------------------------------------ costs ----
    def decode_calls(self):
        """Per window decode step, (FLOPs, bytes) of each attention-kernel
        call in it."""
        return [self.model.decode_kernel_calls(c, self.sizes, self.kv_bytes)
                for c in self.rec.decode_log if c]

    def prefill_calls(self):
        return [self.model.prefill_kernel_calls(s, n, self.sizes,
                                                self.kv_bytes)
                for s, n, _ in self.rec.prefill_log]

    def decode_flops(self):
        return [self.model.decode_step_flops(c, self.sizes)
                for c in self.rec.decode_log if c]

    def prefill_flops(self):
        return [self.model.prefill_step_flops(s, n, f, self.sizes)
                for s, n, f in self.rec.prefill_log]

    # ------------------------------------------------------------ trace ----
    def _runs(self, module):
        return self.trace.get("modules", {}).get(module) or []

    def kernel_seconds(self, module, op_pattern):
        """Device seconds of the module's ops whose name (numbering
        dropped) matches ``op_pattern`` whole, and their count."""
        tot, cnt = 0.0, 0
        for (mod, name), (secs, n) in self.trace.get("ops", {}).items():
            if mod == module and re.fullmatch(op_pattern, name):
                tot += secs
                cnt += n
        return tot, cnt

    def kernel_roofline(self, module, op_pattern, calls):
        """Mean least time per program run (the sum over its kernel calls,
        ``calls`` one list per run) over the mean measured time per program
        run of the ops matching ``op_pattern``, in %."""
        runs = self._runs(module)
        secs, n_ops = self.kernel_seconds(module, op_pattern)
        if not calls or not runs or not n_ops:
            return None
        least = [sum(costs.least_time(f, b, self.peaks)[0] for f, b in step)
                 for step in calls]
        return 100.0 * (sum(least) / len(least)) / (secs / len(runs))

    def step_mfu(self, module, flops):
        runs = self._runs(module)
        if not flops or not runs:
            return None
        mean_t = sum(runs) / len(runs)
        return 100.0 * (sum(flops) / len(flops)) / (
            mean_t * self.peaks["bf16_flops_per_s"])

    def idle_share(self):
        w, b = self.trace.get("window_s"), self.trace.get("busy_s")
        if not w or b is None:
            return None
        return 100.0 * (1.0 - b / w)
