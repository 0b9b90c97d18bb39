"""The check's control on the chip: it has to come out not correct.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, in one process: a whole run of the cell as ``run.py`` makes
it (its window at the cell's own load), the check's numbers for the
program, then the control judged by the same ``check.compare`` at the
cell's committed limits: the reference computed in float8 (e4m3,
per-channel scales) in the program's place, its first choice at each
position the check reads taken as the served token. One JSON line per
seed, with both sides' numbers (the readings the limits are set from).
Exits non-zero if the control comes out correct on any seed, or the
program does not. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run
import spec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    run.setup_program(spec.ROOT)
    run.use_checkout_cache()
    import check
    failed = []
    for seed in args.seeds:
        got = {}

        def control(gaps, finished, req_of, vocab, seed=seed, got=got):
            tokens = check.control_tokens(gaps, finished, req_of, seed,
                                          vocab)
            got["correct"], got["numbers"], got["facts"] = check.compare(
                gaps, tokens, req_of, seed, vocab, cell.cell["limits"])

        res = run.run_cell(cell, seed, args.seconds, False,
                           t_start=time.perf_counter(), after_check=control)
        if got["correct"] or not res["correct"]:
            failed.append(seed)
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "program": {k: v["value"] for k, v in res["check"].items()},
            "control_correct": got["correct"],
            "control": {k: v["value"] for k, v in got["numbers"].items()},
            "control_facts": got["facts"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)
    if failed:
        print(f"control: seeds {failed} did not separate (control correct "
              "or program not)", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
