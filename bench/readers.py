"""Per-layer metrics: one reader per metric, found by name.

``bench/metrics/<name>.py`` defines ``read(run) -> float | None``. A reader
that finds nothing to read returns None and the metric is left out of the
result line; none returns 0 for a share of a roofline or of a peak.
``RunData`` is what a reader may read: the harness's records, the reduced
trace, the cost model and the chip's peaks.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import re
from pathlib import Path

import costs

METRICS = Path(__file__).resolve().parent / "metrics"

# A Mosaic kernel runs as one custom-call op in the trace's "XLA Ops" line,
# named after the jitted op that wraps it (``consmax_decode_paged_op``,
# ``consmax_prefill_paged_op``); the module tells decode from prefill.
KERNEL_OP = re.compile(r"^consmax_\w*_op$")


def load(name: str):
    path = METRICS / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class RunData:
    stats: dict
    rec: object
    trace: dict
    sizes: object
    peaks: dict
    max_slots: int
    kv_bytes: int = 2

    # ------------------------------------------------------------ costs ----
    def decode_costs(self):
        return [costs.decode_kernel(c, self.sizes, self.kv_bytes)
                for c in self.rec.decode_log if c]

    def prefill_costs(self):
        return [costs.prefill_kernel(s, n, self.sizes, self.kv_bytes)
                for s, n, _ in self.rec.prefill_log]

    def decode_flops(self):
        return [costs.decode_step_flops(c, self.sizes)
                for c in self.rec.decode_log if c]

    def prefill_flops(self):
        return [costs.prefill_step_flops(s, n, f, self.sizes)
                for s, n, f in self.rec.prefill_log]

    # ------------------------------------------------------------ trace ----
    def _runs(self, module):
        return self.trace.get("modules", {}).get(module) or []

    def kernel_seconds(self, module):
        """Device seconds of the module's kernel ops, and op count."""
        tot, cnt = 0.0, 0
        for (mod, name), (secs, n) in self.trace.get("ops", {}).items():
            if mod == module and KERNEL_OP.search(name):
                tot += secs
                cnt += n
        return tot, cnt

    def kernel_roofline(self, module, per_layer_costs):
        """Mean least time per program run (one kernel call per layer) over
        mean measured kernel time per program run, in %."""
        runs = self._runs(module)
        secs, n_ops = self.kernel_seconds(module)
        if not per_layer_costs or not runs or not n_ops:
            return None
        least = [self.sizes.layers * costs.least_time(f, b, self.peaks)[0]
                 for f, b in per_layer_costs]
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
