#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip: serve its traffic
at each rate in turn, in one process (one set-up), and print per rate the
offered and completed load, TTFT and admission-wait percentiles, and
whether the queue grew (admission wait of the last third of the requests
against the first third).  The knee is the highest rate whose queue does not
grow; the cell's file then takes 0.8 x knee as a fixed number.

    python3 bench/sweep.py --config qwen2-1.5b --traffic code --seed 5 \
        --seconds 40 --rates 1,1.5,2,2.5,3
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
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    import numpy as np
    from run import enable_compile_cache
    from harness import cell, driver, traffic as T, warmup, weights
    from repro.serving import EngineConfig, Server, ServingEngine
    conf = cell.load_json(cell.BENCH, "configs", args.config + ".json")
    mix = cell.load_json(cell.BENCH, "traffic", args.traffic + ".json")
    enable_compile_cache()
    chips = int(np.prod(conf["engine"].get("mesh") or (1, 1)))
    devs = cell.device_info(True, chips)[:chips]
    reduced = next(c["reduced"] for c in cell.load_json(
        cell.ROOT, "BENCHMARK.json")["configs"] if c["name"] == args.config)
    cfg = cell.program_config(conf, reduced)
    spec = weights.spec_of(conf)
    placement = cell.weight_placement(conf, cfg, spec, chips,
                                      cell.peaks_for(devs[0], True))
    eng = ServingEngine(
        cfg, params=weights.make_params(spec, args.seed, placement),
        seed=args.seed & 0x7FFFFFFF, ecfg=EngineConfig(**conf["engine"]))
    server = Server(eng)
    plans = []
    for r in rates:
        mix["rate_per_s"] = r
        plans.append(T.plan(mix, spec["vocab"], eng.ecfg.max_len, args.seed,
                            args.seconds))
    shapes = warmup.program_shapes(eng, plans[-1])
    warmup.compile_all(eng, shapes, lambda m: print(m, file=sys.stderr))
    warmup.touch_small_programs(server, plans[-1])
    rows = []
    for rate, plan in zip(rates, plans):
        w = driver.run_open(server, plan, args.seconds, drain_s=0.0)
        for tr in w.tracks:                       # free the slots
            if not tr.handle.request.state.terminal:
                server.cancel(tr.handle.rid)
        due = sorted(w.tracks, key=lambda tr: tr.due)
        wait = [((tr.left_queue or w.drained_until) - tr.due)
                for tr in due]
        ttft = [((tr.first_token or w.drained_until) - tr.due)
                for tr in due]
        third = max(len(due) // 3, 1)
        toks = sum(d[2] for tr in w.tracks for d in tr.deliveries
                   if d[1] <= w.end)
        rows.append(dict(
            rate=rate, requests=len(due), window_s=w.seconds,
            out_tok_per_s=toks / w.seconds,
            ttft_p50_s=float(np.percentile(ttft, 50)),
            ttft_p95_s=float(np.percentile(ttft, 95)),
            wait_first_third_s=float(np.median(wait[:third])),
            wait_last_third_s=float(np.median(wait[-third:])),
            no_first_token=sum(tr.first_token is None for tr in due)))
        print(json.dumps(rows[-1]), flush=True)
        time.sleep(1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
