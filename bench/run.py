#!/usr/bin/env python3
"""On-chip serving benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload qwen2-1.5b.chat --seed 7 --seconds 45 \
        --trace 0

Weights and traffic come from ``--seed``.  Set-up compiles (or reads from
the persistent cache) every program shape the cell's traffic can reach; the
window then drives ``Server`` -> ``ServingEngine`` for ``--seconds``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
middle of the window.  After the window the program's state is freed and
a sample of the served tokens is compared with a float32 reference forward
pass under each request's sampling rule; a compile inside the window also
makes the run not correct.

Needs a TPU (no CPU fallback).  The last stdout line is the result JSON;
progress and the compared numbers with their limits go to stderr.  The
persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` or else
``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


def enable_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size cap, so no lock file: set-up compiles on several threads, and
    # their writes timed out on the cap's lock and were lost
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from harness import cell
    found = cell.find_cell(args.workload)
    path = enable_compile_cache()
    print(f"[setup] compile cache: {path}", file=sys.stderr, flush=True)
    out = cell.run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), T_START, found=found)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
