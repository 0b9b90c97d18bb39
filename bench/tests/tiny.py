"""A cell at smoke size for the CPU tests: the real configuration and mix
files, every width shrunk by the configuration's model module
(``tiny``), the engine's shape shrunk here, the kernels in interpret mode."""
import time

import spec


def cell(name: str, limit: float) -> spec.Cell:
    c = spec.load_cell(name)
    cfg = c.model.tiny(c.config)
    cfg["max_slots"] = 4
    cfg["engine"].update(max_seq=256, page_size=16, prefill_chunk=32)
    c.config = cfg
    if c.traffic["loop"] == "closed":
        c.cell = {"prompt_tokens": [40, 100], "output_tokens": [20, 60]}
    else:
        c.traffic = dict(c.traffic, warm_seconds=1)
        c.cell = {"rate_per_s": 6.0, "prompt_clip": [16, 120],
                  "output_clip": [4, 30]}
    c.cell["limits"] = {"max_logit_gap": limit[0], "mean_logit_gap": limit[1]}
    return c


def run(c: spec.Cell, seed: int, seconds: float = 3.0, hook=None,
        trace: bool = False) -> dict:
    import run as bench_run
    return bench_run.run_cell(c, seed, seconds, trace, require_tpu=False,
                              t_start=time.perf_counter(), engine_hook=hook)


def model(name: str):
    """A model module of the benchmark, by name."""
    return spec.load_module(spec.BENCH / "models" / f"{name}.py")
