"""One run of one cell: set-up, the measured window, the reading of the
trace, the comparison with the plain reference, and the result line.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic mix in ``bench/traffic/<traffic>.json``, its limits in
``bench/limits/<cell>.json`` and each metric's reader in
``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

# configuration key -> the program's ModelConfig field
MODEL_FIELDS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "qkv_bias", "qk_norm": "qk_norm",
}
# what the reference computes; the program's config must agree
ARCH_FIXED = dict(block_pattern=("full",), act="silu", glu=True, norm="rms",
                  pos_embedding="rope", rotary_frac=1.0, attn_softcap=0.0,
                  final_softcap=0.0, attn_scale=None, embed_scale=False,
                  post_block_norm=False, kv_quant=False, num_experts=0,
                  dtype="bfloat16")
TRACE_FROM, TRACE_SPAN = 0.25, 0.5     # share of the window


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(name: str) -> dict:
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    return dict(
        cell=cell, bench=bench,
        config=load_json(BENCH, "configs", cell["config"] + ".json"),
        traffic=load_json(BENCH, "traffic", cell["traffic"] + ".json"),
        limits=load_json(BENCH, "limits", name + ".json"),
        reduced=next(c["reduced"] for c in bench["configs"]
                     if c["name"] == cell["config"]))


def metrics_for(bench: dict, cell: str, trace: bool) -> List[dict]:
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str) -> Callable:
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_config(conf: dict, reduced, strict: bool = True):
    """The program's registered config with this file's sizes; with
    ``strict``, only the keys in ``reduced`` may differ from it."""
    from repro.configs import get_config
    base = get_config(conf["arch"])
    cfg = base.replace(**{f: conf[k] for k, f in MODEL_FIELDS.items()})
    allowed = {MODEL_FIELDS[k] for k in reduced}
    changed = [f for f in MODEL_FIELDS.values()
               if getattr(cfg, f) != getattr(base, f)]
    if strict and set(changed) - allowed:
        raise SystemExit(f"{conf['arch']}: the file changes {changed} of the "
                         f"registered config, reduced lists {sorted(allowed)}")
    odd = {k: getattr(cfg, k) for k, v in ARCH_FIXED.items()
           if getattr(cfg, k) != v}
    if odd:
        raise SystemExit(f"{conf['arch']}: not the dense decoder the "
                         f"reference computes: {odd}")
    return cfg


@dataclasses.dataclass
class Run:
    """What the metric readers see."""
    spec: dict
    traffic: dict
    window: object
    setup_s: float
    peaks: dict                           # of one chip
    chips: int = 1                        # the cell's
    trace: Optional[dict] = None          # devtrace.reduce output
    traced: Optional[tuple] = None        # host (start, stop) of the trace
    occupancy: list = dataclasses.field(default_factory=list)


class Tracer:
    """Starts the profiler at the first round end past ``TRACE_FROM`` of the
    window and stops it at the first round end ``TRACE_SPAN`` later, so the
    trace holds whole rounds; reads the pager's occupancy after each round."""

    def __init__(self, seconds: float, engine, directory: str):
        self.seconds, self.engine, self.dir = seconds, engine, directory
        self.t0 = self.start = self.stop = None
        self.occupancy = []
        self._ann = None

    def __call__(self, rounds, t):
        import jax
        if self.t0 is None:
            self.t0 = rounds.rounds[0].start
        if self.engine.pager is not None:
            self.occupancy.append(
                self.engine.pager.occupancy()["occupancy_live"])
        if self.start is None and t - self.t0 >= TRACE_FROM * self.seconds:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans only: low overhead
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
            self.start = time.perf_counter()
        elif self.start is not None and self.stop is None and \
                t - self.start >= TRACE_SPAN * self.seconds:
            self.close()

    def close(self):
        import jax
        if self.start is not None and self.stop is None:
            self.stop = time.perf_counter()
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()


def device_info(require_tpu: bool, chips: int):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


def sample_for_check(window, seed: int, min_tokens: int, max_reqs: int):
    """Requests with served tokens: the one with the most, then others drawn
    from the seed until ``min_tokens`` or ``max_reqs``.  Each as (prompt,
    served tokens, sampling rule)."""
    cands = [tr for tr in window.tracks if tr.tokens]
    if not cands:
        return []
    cands.sort(key=lambda tr: (-len(tr.tokens), tr.req.idx))
    pick = [cands[0]]
    rest = cands[1:]
    order = np.random.default_rng(seed + 17).permutation(len(rest))
    for i in order:
        if len(pick) >= max_reqs or \
                sum(len(t.tokens) for t in pick) >= min_tokens:
            break
        pick.append(rest[i])
    return [(np.asarray(tr.req.prompt, np.int32),
             np.asarray(tr.tokens, np.int32),
             (tr.req.temperature or 0.0, tr.req.top_k, tr.req.top_p,
              tr.req.lane_seed or 0)) for tr in pick]


def logit_gaps(spec, seed, samples, quant=None, placement=None):
    """Per sample, by how much the reference's logits would have to move
    for each served token to be the one its sampling rule produces (or,
    with ``quant``, for the token that precision produces at the same
    position).  The weights are remade where ``placement`` puts them."""
    from . import reference, weights
    params = weights.make_params(spec, seed, placement)
    seqs = [np.concatenate([p, t[:-1]]) for p, t, _ in samples]
    starts = [len(p) - 1 for p, _, _ in samples]
    targets = [t for _, t, _ in samples]
    rules = [r for _, _, r in samples]
    if quant is not None:
        targets = reference.read_positions(spec, params, seqs, starts,
                                           targets, rules, quant,
                                           mode="choose")
    return reference.read_positions(spec, params, seqs, starts, targets,
                                    rules)


def weight_placement(conf: dict, cfg, spec: dict, chips: int,
                     peaks: dict):
    """Where the engine keeps each weight over its mesh, so that the weights
    are made there, once (None on one chip).  Refuses first, before any
    weight is made, an engine mesh that is not the cell's chips, and
    weights that alone overfill a chip."""
    from . import weights
    mesh = conf["engine"].get("mesh") or (1, 1)
    span = int(np.prod(mesh))
    if span != chips:
        raise SystemExit(f"{conf['arch']}: engine.mesh "
                         f"{conf['engine'].get('mesh')} spans {span} chips, "
                         f"the cell {chips}")
    placement = None
    if chips > 1:
        from repro.launch.mesh import make_serving_mesh
        from repro.launch.shardings import named, serving_param_specs
        m = make_serving_mesh(*mesh)
        placement = named(m, serving_param_specs(cfg, m)[0])
    if "hbm_bytes" in peaks:
        total = weights.param_bytes(spec)
        chip = weights.param_bytes(spec, placement)
        if chip > peaks["hbm_bytes"]:
            raise SystemExit(
                f"{conf['arch']}: {total} bytes of weights over {chips} "
                f"chips is {chip} a chip, more than its "
                f"{peaks['hbm_bytes']:.0f}")
    return placement


def serve(found: dict, seed: int, seconds: float, trace: bool, log,
          t_start: float, require_tpu: bool = True, strict: bool = True,
          after_window: Optional[Callable] = None):
    """Set-up and window.  Returns (Run, samples, info)."""
    import jax
    from . import driver, traffic as T, warmup, weights
    from .compile_log import CompileLog
    from repro.serving import EngineConfig, Server, ServingEngine
    cell = found["cell"]

    def stage(what):
        log(f"[setup] {time.perf_counter() - t_start:8.3f} s {what}")

    chips = cell["chips"]
    devs = device_info(require_tpu, chips)
    ours = devs[:chips]
    stage(f"devices: {devs}, the cell's: {[d.id for d in ours]}")
    clog = CompileLog()
    conf, mix = found["config"], found["traffic"]
    cfg = program_config(conf, found["reduced"], strict)
    spec = weights.spec_of(conf)
    peaks = peaks_for(devs[0], require_tpu)
    placement = weight_placement(conf, cfg, spec, chips, peaks)
    params = weights.make_params(spec, seed, placement)
    from repro.models import init_params
    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    if jax.tree.map(lambda x: (x.shape, x.dtype), want) != got:
        raise SystemExit("bench weights do not match the program's layout")
    jax.block_until_ready(params)
    stage("weights made")
    eng = ServingEngine(cfg, params=params, seed=seed & 0x7FFFFFFF,
                        ecfg=EngineConfig(**conf["engine"]))
    del params
    server = Server(eng)
    stage("engine built")
    plan = T.plan(mix, spec["vocab"], eng.ecfg.max_len, seed, seconds)
    stage("traffic planned")
    m = clog.mark()
    warmup.compile_all(eng, warmup.program_shapes(eng, plan), log)
    stage("programs compiled")
    warmup.touch_small_programs(server, plan)
    stage("small programs touched")
    log(f"[setup] {clog.since(m)}; slowest: " + ", ".join(
        f"{k}={v:.2f}" for k, v in sorted(clog.by_name.items(),
                                          key=lambda kv: -kv[1])[:5]))
    tracer = None
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        tracer = Tracer(seconds, eng, tdir)
    m = clog.mark()
    run_loop = driver.run_open if plan.loop == "open" else driver.run_closed
    try:
        window = run_loop(server, plan, seconds, on_round=tracer)
    finally:
        if tracer is not None:
            tracer.close()
    window_compiles = clog.n - m[0]
    log(f"[window] {window.seconds:.6f} s, {len(window.rounds)} rounds, "
        f"{len(window.tracks)} requests, in-window {clog.since(m)}")
    stats = [d.memory_stats() or {} for d in ours]
    peak = max((st["peak_bytes_in_use"] for st in stats
                if "peak_bytes_in_use" in st), default=None)
    log(f"[window] peak_bytes_in_use="
        f"{[st.get('peak_bytes_in_use') for st in stats]} "
        f"bytes_limit={stats[0].get('bytes_limit')}")
    if after_window is not None:
        after_window(server, window)
    for tr in window.tracks:
        tr.detach()
    mine = [tr for tr in window.tracks if tr.due >= window.start]
    failed = sum(1 for tr in mine
                 if tr.state in ("FAILED", "SHED", "CANCELLED")
                 or (plan.loop == "open" and tr.first_token is None))
    vocab_ok = all(0 <= t < spec["vocab"] for tr in window.tracks
                   for t in tr.tokens)
    run = Run(spec, mix, window, setup_s=window.start - t_start,
              peaks=peaks, chips=chips)
    if tracer is not None:
        from . import devtrace
        run.occupancy = tracer.occupancy
        if tracer.stop is not None:
            run.traced = (tracer.start, tracer.stop)
            run.trace = devtrace.reduce(devtrace.read(tdir),
                                        [d.id for d in ours])
        shutil.rmtree(tdir, ignore_errors=True)
    samples = sample_for_check(window, seed,
                               found["limits"]["check_tokens"],
                               found["limits"]["check_requests"])
    info = dict(peak=peak, failed=failed, vocab_ok=vocab_ok,
                attempted=len(mine), window_compiles=window_compiles,
                device=devs[0], n_devices=len(devs), placement=placement)
    del server, eng, tracer
    gc.collect()
    return run, samples, info


def peaks_for(dev, require_tpu: bool) -> dict:
    table = load_json(BENCH, "peaks.json")
    kind = dev.device_kind
    if kind in table:
        return table[kind]
    if require_tpu:
        raise SystemExit(f"bench/peaks.json has no entry for {kind!r}")
    return {}


def check(run: Run, samples, info, seed: int, limits: dict,
          quant: Optional[str] = None) -> dict:
    """{name: (value, limit)} of every number compared.  With ``quant``,
    the control: the reference in that precision in the program's place."""
    gap = None
    if samples:
        gap = float(max(np.max(g) for g in logit_gaps(
            run.spec, seed, samples, quant, info.get("placement"))))
    served = sum(len(t) for _, t, _ in samples)
    return {
        "logit_gap": (gap, limits["logit_gap"]["limit"]),
        "checked_tokens": (served, limits["check_tokens_min"]),
        "failed_requests": (info["failed"], 0),
        "tokens_in_vocab": (int(info["vocab_ok"]), 1),
        "window_compiles": (info["window_compiles"], 0),
    }


def verdict(compared: dict) -> bool:
    """Each number within its limit: at least ``checked_tokens`` and
    ``tokens_in_vocab``, at most every other."""
    at_least = ("checked_tokens", "tokens_in_vocab")
    return all(v is not None and (v >= lim if k in at_least else v <= lim)
               for k, (v, lim) in compared.items())


def result(found: dict, run: Run, info: dict, compared: dict,
           trace: bool) -> dict:
    cell = found["cell"]["name"]
    metrics = {}
    for m in metrics_for(found["bench"], cell, trace):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = info["device"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": info["n_devices"],
              "memory_peak_bytes": info["peak"]}
    out = {"correct": verdict(compared), "attempted": info["attempted"],
           "failed": info["failed"], "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in compared.items()}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             found: Optional[dict] = None, strict: bool = True,
             after_window: Optional[Callable] = None) -> dict:
    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    found = found or find_cell(name)
    run, samples, info = serve(found, seed, seconds, trace, log, t_start,
                               require_tpu, strict, after_window)
    t = time.perf_counter()
    compared = check(run, samples, info, seed, found["limits"])
    log(f"[check] reference over {len(samples)} requests, "
        f"{sum(len(s[1]) for s in samples)} served tokens: "
        f"{time.perf_counter() - t:.3f} s")
    out = result(found, run, info, compared, trace)
    for k, v in out["compared"].items():
        log(f"compared {k}: {v['value']} limit {v['limit']}")
    return out
