#!/usr/bin/env python3
"""Readings that set a cell's ``logit_gap`` limit, on the chip, in one
process.  For each seed, a run of the cell at its own load (set-up, window,
the same sample of served tokens the benchmark checks), then two verdicts
through the benchmark's own comparison:

* ``program``: the numbers a benchmark run compares, and
* ``control``: the same with the reference computed in float8 e4m3 in the
  program's place: at each position of the same prompts and served tokens,
  the token that precision produces under the request's sampling rule.
  It has to come out not correct.

With ``--fault`` the program runs with that fault planted (see
``harness/faults.py``) and only its verdict is read: it has to come out
not correct too.

    python3 bench/control.py --workload qwen2-1.5b.chat --seconds 45 \
        --seeds 1,2,3 [--fault top_p]
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main(argv=None) -> int:
    from harness import faults
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", choices=faults.FAULTS)
    args = ap.parse_args(argv)
    from run import enable_compile_cache
    from harness import cell
    found = cell.find_cell(args.workload)
    enable_compile_cache()
    if args.fault:
        faults.plant(args.fault, found["config"]["vocab_size"])

    def log(m):
        print(m, file=sys.stderr, flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        run, samples, info = cell.serve(found, seed, args.seconds, False,
                                        log, time.perf_counter())
        row = dict(seed=seed, fault=args.fault, requests=len(samples),
                   peak=info["peak"])
        sides = [("program", None)] + ([] if args.fault else
                                       [("control", "fp8")])
        for side, quant in sides:
            t = time.perf_counter()
            compared = cell.check(run, samples, info, seed, found["limits"],
                                  quant)
            row[side] = dict(correct=cell.verdict(compared),
                             check_s=time.perf_counter() - t,
                             **{k: v for k, (v, _) in compared.items()})
        row["limits"] = {k: lim for k, (_, lim) in compared.items()}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
