"""A cell at smoke size for the CPU tests: the real configuration and mix
files with every width shrunk, the kernels in interpret mode."""
import copy
import time

import spec


def cell(name: str, limit: float) -> spec.Cell:
    c = spec.load_cell(name)
    cfg = copy.deepcopy(c.config)
    cfg.update(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
    cfg["program"]["overrides"].update(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, head_dim=0)
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


def run(c: spec.Cell, seed: int, seconds: float = 3.0, hook=None) -> dict:
    import run as bench_run
    return bench_run.run_cell(c, seed, seconds, False, require_tpu=False,
                              t_start=time.perf_counter(), engine_hook=hook)
