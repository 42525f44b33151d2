"""Serving-engine data-plane benchmark: slot-native vs the pre-PR (legacy)
engine — and the paged KV cache vs the dense slot layout — wall-clock
measured on the smoke config.

Metrics per (governor, batch):

* ``decode``  — steady-state decode tokens/s with a full batch of
  never-ending streams (no admissions in the window): isolates the jitted
  block decode (ctx-bucketed, scanned, donated, no per-token host sync)
  against the legacy per-step host-synced loop.
* ``admit``   — admissions/s: jitted bucketed slot prefill vs the legacy
  eager prefill + fresh per-request cache + host-side full-batch splice.
* ``serve``   — sustained serving tokens/s with continuous batching churn
  (finite outputs, streams join/leave): the end-to-end engine number.
* ``serve ... mixed_sampling`` — the same churn with heterogeneous
  per-request sampling (greedy / temperature / top-k / top-p rows sharing
  each batch through the per-slot sampling lanes): overhead vs the
  all-greedy serve number, and the CI smoke that the mixed path drains.

Paged scenarios (``--paged``):

* ``decode/serve _paged`` — the same workloads through the page-table data
  plane (gathered page chains, chain growth at block boundaries).
* ``longadmit`` — chunked admission of prompts longer than the smallest
  attention buffer (sliding-window config) vs the legacy eager-prefill
  fallback.
* ``capacity`` — concurrent streams sustained on a pool of *half* the dense
  K/V memory: the dense layout pins ``memory / max_len`` streams; paging
  holds ``max_batch`` (the acceptance lever for GreenLLM's decode batching).

Prefix-cache scenario (``--prefix-cache``):

* ``engine_prefix_cache`` — a shared-system-prompt burst served cold vs
  with the content-addressed prefix cache: prefill tokens computed, hit
  rate, and (full-size plant accounting) energy per request.  Output
  tokens are hard-asserted identical between the two runs;
  ``compare.py`` gates the saved-token fraction and the energy ratio.

Cluster scenario (``--cluster``):

* ``cluster_disagg_1p1d`` — a 2-replica disaggregated prefill/decode cluster
  (paged-KV handoff, per-phase DVFS) vs a 2x-colocated max-frequency
  baseline on the same mini-trace: tokens/s, energy ratio (incl. idle up to
  the shared makespan), handoff and preemption counts.  ``--governors ""``
  skips the per-governor engine scenarios and runs only this one (CI smoke).

Mesh scenario (``--mesh dp,tp`` or ``--mesh auto``):

* ``engine_mesh_dp{D}tp{T}`` — the same burst served on a sharded device
  mesh vs the unsharded engine: output tokens hard-asserted identical
  (the PR 10 bit-exactness invariant), and the energy-per-token ratio is
  emitted for ``compare.py`` to hold inside its strict parity band.

    PYTHONPATH=src python benchmarks/serving_engine.py [--quick] [--paged]
        [--cluster] [--mesh 2,4] [--arch qwen2-1.5b] [--batches 1,4,8]
        [--governors greenllm,defaultnv] [--json out.json]

Prints ``name,value,derived`` CSV rows like benchmarks/run.py.  ``--json``
additionally writes the rows (plus the run configuration) as a JSON
document — the format of the checked-in ``BENCH_*.json`` baselines that
make the perf trajectory diffable across PRs.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import time

import jax
import numpy as np


def _engine(cfg, params, *, batch, governor, slot_native, max_len=256,
            paged=False, num_pages=0, chunked=True):
    from repro.serving import EngineConfig, ServingEngine
    return ServingEngine(cfg, params=params, ecfg=EngineConfig(
        max_batch=batch, max_len=max_len, governor=governor,
        slot_native=slot_native, paged=paged, num_pages=num_pages,
        chunked_prefill=chunked))


def _fill(eng, batch, *, prompt_len=24, output_len=10 ** 9, rng=None):
    from repro.core import Request
    for i in range(batch):
        pl = prompt_len if rng is None else int(rng.integers(8, 100))
        eng.submit(Request(rid=i, arrival=0.0, prompt_len=pl,
                           output_len=output_len))
    eng._admit()


def bench_decode(cfg, params, *, batch, governor, slot_native, steps,
                 paged=False):
    eng = _engine(cfg, params, batch=batch, governor=governor,
                  slot_native=slot_native, paged=paged)
    _fill(eng, batch)
    # warm the (ctx, k) kernels outside the timed window
    for _ in range(2):
        eng._decode_block(16) if slot_native else eng._step_legacy()
    jax.block_until_ready(eng._tok)
    t0 = time.perf_counter()
    if slot_native:
        eng._decode_block(steps)
    else:
        for _ in range(steps):
            eng._step_legacy()
    jax.block_until_ready(eng.caches)
    return batch * steps / (time.perf_counter() - t0)


def bench_admit(cfg, params, *, governor, slot_native, n):
    eng = _engine(cfg, params, batch=8, governor=governor,
                  slot_native=slot_native)
    from repro.core import Request
    eng.submit(Request(rid=10 ** 6, arrival=0.0, prompt_len=24, output_len=4))
    eng._admit()                       # compile warmup
    eng._retire(list(eng.active.keys()))
    jax.block_until_ready(eng._tok)
    for i in range(n):
        eng.submit(Request(rid=i, arrival=0.0, prompt_len=24, output_len=4))
    t0 = time.perf_counter()
    while eng.pending:
        eng._admit()
        jax.block_until_ready(eng._tok)
        eng._retire(list(eng.active.keys()))
    return n / (time.perf_counter() - t0)


def bench_serve(cfg, params, *, batch, governor, slot_native, nreq, out_len,
                paged=False):
    """Sustained serving through the ``serving.api`` front door (the same
    driver loop production callers use)."""
    from repro.core import SamplingParams
    from repro.serving import Server
    eng = _engine(cfg, params, batch=batch, governor=governor,
                  slot_native=slot_native, paged=paged)
    srv = Server(eng)
    rng = np.random.default_rng(0)
    for _ in range(nreq):
        srv.submit(rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(8, 100))),
                   SamplingParams(max_tokens=out_len))
    t0 = time.perf_counter()
    srv.run()
    jax.block_until_ready(eng._tok)
    return nreq * out_len / (time.perf_counter() - t0)


def bench_long_admit(cfg, params, *, governor, n, chunked):
    """Admission latency for prompts longer than the smallest attention
    buffer: chunked slot-native admission vs the legacy eager-prefill
    fallback.  Requires a sliding-window config (see bench caller)."""
    from repro.core import Request
    eng = _engine(cfg, params, batch=8, governor=governor, slot_native=True,
                  chunked=chunked)
    long_len = min(eng.ecfg.max_len // 2, eng.buckets[-1] * 4)

    def admit_one(rid):
        eng.submit(Request(rid=rid, arrival=0.0, prompt_len=long_len,
                           output_len=4))
        eng._admit()
        while eng.prefilling:
            eng._advance_chunks()
        jax.block_until_ready(eng._tok)
        eng._retire(list(eng.active.keys()))

    admit_one(10 ** 6)                 # compile warmup
    t0 = time.perf_counter()
    for i in range(n):
        admit_one(i)
    return n / (time.perf_counter() - t0)


def bench_paged_capacity(cfg, params, *, governor, nreq, out_len):
    """Streams sustained concurrently on half the dense K/V memory.

    Returns (streams, dense_equivalent_streams, tokens_per_s): the paged
    engine runs ``nreq`` concurrent streams against a pool whose token
    capacity would pin only ``pool_tokens / max_len`` dense rows.
    """
    from repro.core import SamplingParams
    from repro.serving import Server
    max_len = 256
    ps = 16
    num_pages = (nreq * max_len // ps) // 2 + 1     # half dense memory
    eng = _engine(cfg, params, batch=nreq, governor=governor,
                  slot_native=True, max_len=max_len, paged=True,
                  num_pages=num_pages)
    srv = Server(eng)
    rng = np.random.default_rng(0)
    for _ in range(nreq):
        srv.submit(rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(16, 64))),
                   SamplingParams(max_tokens=out_len))
    eng._admit()
    peak = len(eng.active) + len(eng.prefilling)
    t0 = time.perf_counter()
    rep = srv.run()
    jax.block_until_ready(eng._tok)
    dt = time.perf_counter() - t0
    # usable pool size from the allocator (page 0 is reserved scratch)
    dense_eq = (eng.pager.occupancy()["pages_total"] * ps) // max_len
    return peak, dense_eq, rep.decode_tokens / dt


def bench_mixed_sampling(cfg, params, *, batch, governor, nreq, out_len):
    """Sustained serving of a heterogeneous sampling mix (greedy /
    temperature / top-k / top-p rows sharing each batch) through the
    ``serving.api`` front door — the scenario the engine-global-temperature
    design rejected outright.  Returns (tok/s, greedy-fraction-served)."""
    from repro.core import SamplingParams
    from repro.serving import Server
    eng = _engine(cfg, params, batch=batch, governor=governor,
                  slot_native=True)
    srv = Server(eng)
    rng = np.random.default_rng(0)
    mixes = [SamplingParams(max_tokens=out_len),
             SamplingParams(max_tokens=out_len, temperature=0.9, seed=1),
             SamplingParams(max_tokens=out_len, temperature=0.7, top_k=40,
                            seed=2),
             SamplingParams(max_tokens=out_len, temperature=1.1, top_p=0.9,
                            seed=3)]
    hs = []
    for i in range(nreq):
        hs.append(srv.submit(
            rng.integers(0, cfg.vocab_size, size=int(rng.integers(8, 100))),
            mixes[i % len(mixes)]))
    t0 = time.perf_counter()
    rep = srv.run()
    jax.block_until_ready(eng._tok)
    dt = time.perf_counter() - t0
    assert rep.completed == nreq, "mixed-sampling smoke must drain"
    greedy = sum(1 for i in range(nreq) if i % len(mixes) == 0)
    return nreq * out_len / dt, greedy / nreq


def bench_prefix_cache(cfg, params, *, governor, nreq, out_len, arch):
    """Shared-system-prompt burst, cold cache vs ``prefix_cache=True``.

    Every request carries the same 96-token system prompt plus a short
    random tail — the chat/RAG traffic shape the prefix cache targets.  The
    warm run must produce bit-identical tokens (hard-asserted here: the CI
    smoke rides this scenario) while computing fewer prefill tokens and
    billing less prefill energy.  Accounting uses the *full-size* plant
    config for ``arch`` (virtual clock, deterministic): at paper scale the
    skipped tokens carry real joules, whereas the smoke model's prefill is
    weight-read-bound and nearly flat in L.

    Returns (warm tok/s, prefill_tokens_saved_frac, hit_rate,
    energy_per_request warm/cold ratio).
    """
    import dataclasses

    from repro.configs import get_config
    from repro.core import SamplingParams
    from repro.models import init_params
    from repro.serving import EngineConfig, Server, ServingEngine
    plant_cfg = get_config(arch)
    # f32 model compute: a hit routes the stream through chunked prefill
    # (reading matched context from the cache) while the cold run one-shots
    # the whole prompt — two summation orders that agree bitwise in f32 but
    # differ by an ulp in bf16, which the token-identity assert below would
    # trip over (same reason the paging equivalence tests pin f32).  Energy
    # accounting uses the plant config and is unaffected.
    if cfg.dtype != "float32":
        cfg = dataclasses.replace(cfg, dtype="float32")
        params = init_params(jax.random.PRNGKey(0), cfg)

    def run(pc):
        eng = ServingEngine(cfg, params=params, plant_cfg=plant_cfg,
                            ecfg=EngineConfig(
                                max_batch=8, max_len=256, governor=governor,
                                slot_native=True, paged=True,
                                cache_dtype="float32", prefix_cache=pc))
        srv = Server(eng)
        rng = np.random.default_rng(0)
        sys_prompt = rng.integers(0, cfg.vocab_size, size=96)
        for _ in range(nreq):
            tail = rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(8, 32)))
            srv.submit(np.concatenate([sys_prompt, tail]),
                       SamplingParams(max_tokens=out_len))
        t0 = time.perf_counter()
        rep = srv.run()
        jax.block_until_ready(eng._tok)
        return eng, rep, time.perf_counter() - t0

    run(True)                                  # compile warmup
    cold, crep, _ = run(False)
    warm, wrep, dt = run(True)
    assert [q.tokens for q in warm.requests] == \
        [q.tokens for q in cold.requests], \
        "prefix-cache hit must be token-identical to the cold run"
    assert crep.completed == wrep.completed == nreq
    saved = 1.0 - warm.prefill_tokens / cold.prefill_tokens
    hit_rate = warm.prefix_cache.stats()["hit_rate"]
    eratio = wrep.total_energy_j / crep.total_energy_j
    return nreq * out_len / dt, saved, hit_rate, eratio


def bench_metrics_overhead(cfg, params, *, batch, governor, nreq, out_len):
    """Serve the same burst with no observability sinks and with the full
    PR-7 surface installed (MetricsRegistry + Tracer through ``Server``).

    Hard-asserts the structural zero-overhead invariant first — identical
    host-drain counts, virtual clock and token totals between the two runs
    (observability must ride existing sync points, never add one) — then
    measures wall-clock overhead as median-of-3 per mode and asserts it
    stays under 2%.  Returns (plain tok/s, instrumented tok/s, registry).
    """
    from repro.core import MetricsRegistry, SamplingParams, Tracer
    from repro.serving import Server

    def run(with_sinks):
        eng = _engine(cfg, params, batch=batch, governor=governor,
                      slot_native=True)
        reg = MetricsRegistry(snapshot_min_dt=0.0) if with_sinks else None
        tr = Tracer() if with_sinks else None
        srv = Server(eng, metrics=reg, tracer=tr)
        rng = np.random.default_rng(0)
        for _ in range(nreq):
            srv.submit(rng.integers(0, cfg.vocab_size,
                                    size=int(rng.integers(8, 100))),
                       SamplingParams(max_tokens=out_len))
        gc.collect()        # don't bill earlier runs' garbage to this one
        t0 = time.perf_counter()
        rep = srv.run()
        jax.block_until_ready(eng._tok)
        return time.perf_counter() - t0, eng, reg, rep

    run(False)                                 # compile warmup
    _, e0, _, r0 = run(False)
    _, e1, reg, r1 = run(True)
    assert e1._host_drains == e0._host_drains, \
        f"observability added host syncs: {e1._host_drains} vs " \
        f"{e0._host_drains}"
    assert abs(e1.vtime - e0.vtime) < 1e-9, "virtual clocks diverged"
    assert (r1.decode_tokens, r1.completed) == \
        (r0.decode_tokens, r0.completed), "served work diverged"
    # median of paired ratios: min-of-3 per mode let one lucky-fast bare
    # run inflate the ratio several percent on shared machines; pairing
    # adjacent bare/instrumented runs cancels slow load drift before the
    # ratio is taken.  A round poisoned end-to-end by external load defeats
    # any within-round statistic, so a failing round is re-measured once —
    # a real regression fails both rounds
    def measure():
        plains, insts = [], []
        for _ in range(5):
            plains.append(run(False)[0])
            insts.append(run(True)[0])
        return (statistics.median(i / p
                                  for p, i in zip(plains, insts)) - 1.0,
                statistics.median(plains), statistics.median(insts))

    overhead, t_plain, t_inst = measure()
    if overhead >= 0.02:
        overhead, t_plain, t_inst = measure()
    assert overhead < 0.02, \
        f"metrics/tracing overhead {overhead * 100:.2f}% exceeds 2% " \
        f"in two measurement rounds"
    total = nreq * out_len
    return total / t_plain, total / t_inst, reg


def bench_cluster(cfg, params, *, nreq, out_len, max_len=192):
    """Disaggregated 1 prefill + 1 decode cluster (GreenLLM per-phase DVFS)
    vs an equal-replica-count colocated max-frequency baseline on the same
    mini-trace: completed counts must match, and the energy ratio (incl.
    idle up to the shared makespan) is the headline number.

    Returns (tok/s of the disaggregated run, energy ratio disagg/colocated,
    handoffs, preemptions).
    """
    from repro.core import SamplingParams
    from repro.serving import EngineConfig, Server, ServingCluster

    def run(**kw):
        cl = ServingCluster(cfg, params=params, ecfg=EngineConfig(
            max_batch=8, max_len=max_len, governor=kw.pop("governor")), **kw)
        srv = Server(cl)
        rng = np.random.default_rng(0)
        for i in range(nreq):
            plen = int(rng.integers(24, max_len // 2))
            srv.submit(rng.integers(0, cfg.vocab_size, size=plen),
                       SamplingParams(max_tokens=out_len),
                       arrival=0.05 * i)
        t0 = time.perf_counter()
        rep = srv.run()
        return rep, time.perf_counter() - t0

    base, _ = run(governor="defaultnv", n_prefill=0, n_decode=0,
                  n_colocated=2)
    rep, dt = run(governor="greenllm", n_prefill=1, n_decode=1)
    assert rep.completed == base.completed == nreq
    tokens = rep.prefill_tokens + rep.decode_tokens
    return (tokens / dt, rep.total_energy_j / base.total_energy_j,
            rep.migrated, rep.preempted)


def bench_mesh(cfg, params, *, governor, nreq, out_len, mesh):
    """Same burst served unsharded and on a ``(dp, tp)`` device mesh.

    PR 10's equivalence bar makes this a parity gate, not a horse race:
    params are storage-sharded and gathered at kernel entry, slot rows and
    the paged pool shard along ``data`` — pure data movement, so tokens are
    hard-asserted identical and energy per token must sit inside
    ``compare.py``'s strict band (it is 1.0 exactly when the invariant
    holds).  Returns (mesh tok/s, energy-per-token ratio mesh/unsharded).
    """
    from repro.core import SamplingParams
    from repro.serving import EngineConfig, Server, ServingEngine

    def run(m):
        eng = ServingEngine(cfg, params=params, ecfg=EngineConfig(
            max_batch=8, max_len=256, governor=governor, slot_native=True,
            paged=True, mesh=m))
        srv = Server(eng)
        rng = np.random.default_rng(0)
        for _ in range(nreq):
            srv.submit(rng.integers(0, cfg.vocab_size,
                                    size=int(rng.integers(8, 100))),
                       SamplingParams(max_tokens=out_len))
        t0 = time.perf_counter()
        rep = srv.run()
        jax.block_until_ready(eng._tok)
        return eng, rep, time.perf_counter() - t0

    run(mesh)                                  # compile warmup
    beng, brep, _ = run(None)
    meng, mrep, dt = run(mesh)
    assert [q.tokens for q in meng.requests] == \
        [q.tokens for q in beng.requests], \
        "mesh serving must be token-identical to the unsharded engine"
    assert mrep.completed == brep.completed == nreq

    def ept(rep):
        return rep.total_energy_j / (rep.prefill_tokens + rep.decode_tokens)

    return nreq * out_len / dt, ept(mrep) / ept(brep)


def _parse_mesh(spec: str):
    """'dp,tp' -> tuple; 'auto' picks the widest shape the visible devices
    support (both axes when 8 are forced, data-only on 2).  One device has
    no mesh to measure: 'auto' refuses it instead of timing a (1,1) mesh."""
    if spec == "auto":
        d = len(jax.devices())
        if d < 2:
            raise SystemExit(
                "--mesh auto needs at least 2 devices, found 1 (on CPU set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return (2, 4) if d >= 8 else (2, 1)
    dp, tp = (int(x) for x in spec.split(","))
    return dp, tp


def bench_serving_engine(quick: bool = False, arch: str = "qwen2-1.5b",
                         batches=(1, 4, 8), governors=("greenllm", "defaultnv"),
                         paged: bool = False, cluster: bool = False,
                         prefix_cache: bool = False, mesh: str = "",
                         extras: dict = None):
    from repro.configs import get_config
    from repro.models import init_params

    cfg = get_config(arch).smoke()
    params = init_params(jax.random.PRNGKey(0), cfg)
    steps = 48 if quick else 128
    nreq = 12 if quick else 24
    n_admit = 8 if quick else 16

    def warm2(fn, *a, **kw):
        # identical schedule -> identical (cfg, ctx, k) jit keys: the first
        # pass compiles into the shared cache, the second is the measurement
        fn(*a, **kw)
        return fn(*a, **kw)

    rows = []
    for gov in governors:
        dense_decode = {}
        for b in batches:
            legacy = bench_decode(cfg, params, batch=b, governor=gov,
                                  slot_native=False, steps=steps)
            slot = warm2(bench_decode, cfg, params, batch=b, governor=gov,
                         slot_native=True, steps=steps)
            dense_decode[b] = slot
            rows.append((f"engine_decode_b{b}_{gov}_legacy", 1e6 / legacy,
                         f"{legacy:.0f}tok/s"))
            rows.append((f"engine_decode_b{b}_{gov}_slot", 1e6 / slot,
                         f"{slot:.0f}tok/s;speedup={slot / legacy:.1f}x"))
        legacy = bench_admit(cfg, params, governor=gov, slot_native=False,
                             n=n_admit)
        slot = bench_admit(cfg, params, governor=gov, slot_native=True,
                           n=n_admit)
        rows.append((f"engine_admit_{gov}_legacy", 1e6 / legacy,
                     f"{legacy:.1f}adm/s"))
        rows.append((f"engine_admit_{gov}_slot", 1e6 / slot,
                     f"{slot:.1f}adm/s;speedup={slot / legacy:.1f}x"))
        b = max(batches)
        legacy = bench_serve(cfg, params, batch=b, governor=gov,
                             slot_native=False, nreq=nreq, out_len=32)
        slot = warm2(bench_serve, cfg, params, batch=b, governor=gov,
                     slot_native=True, nreq=nreq, out_len=32)
        rows.append((f"engine_serve_b{b}_{gov}_legacy", 1e6 / legacy,
                     f"{legacy:.0f}tok/s"))
        rows.append((f"engine_serve_b{b}_{gov}_slot", 1e6 / slot,
                     f"{slot:.0f}tok/s;speedup={slot / legacy:.1f}x"))
        mixed, gfrac = warm2(bench_mixed_sampling, cfg, params, batch=b,
                             governor=gov, nreq=nreq, out_len=32)
        rows.append((f"engine_serve_b{b}_{gov}_mixed_sampling", 1e6 / mixed,
                     f"{mixed:.0f}tok/s;vs_greedy={mixed / slot:.2f}x;"
                     f"greedy_frac={gfrac:.2f}"))
        if paged:
            rows.extend(_paged_rows(cfg, params, gov=gov, b=b, steps=steps,
                                    nreq=nreq, n_admit=n_admit, warm2=warm2,
                                    dense_decode=dense_decode[b]))
        if prefix_cache:
            tps, saved, hit, eratio = bench_prefix_cache(
                cfg, params, governor=gov, nreq=nreq,
                out_len=12 if quick else 24, arch=arch)
            rows.append((f"engine_prefix_cache_{gov}",
                         1e6 / max(tps, 1e-9),
                         f"{tps:.0f}tok/s;"
                         f"prefill_tokens_saved_frac={saved:.3f};"
                         f"hit_rate={hit:.2f};"
                         f"energy_per_req_vs_cold={eratio:.3f}x"))
    if governors:
        # observability overhead: no-sink vs instrumented serve (host-drain
        # and token equality hard-asserted; wall overhead must stay <2%)
        b = max(batches)
        plain, inst, reg = bench_metrics_overhead(
            cfg, params, batch=b, governor=governors[0], nreq=nreq,
            out_len=32)
        rows.append((f"engine_serve_b{b}_{governors[0]}_metrics",
                     1e6 / inst,
                     f"{inst:.0f}tok/s;overhead="
                     f"{(plain / inst - 1) * 100:.2f}%"))
        if extras is not None:
            extras["metrics_snapshot"] = reg.flat()
    if cluster:
        # 2-replica disaggregated mini-trace vs 2x-colocated max-freq
        tps, eratio, handoffs, preempted = bench_cluster(
            cfg, params, nreq=6 if quick else 12, out_len=12 if quick else 24)
        rows.append(("cluster_disagg_1p1d", 1e6 / max(tps, 1e-9),
                     f"{tps:.0f}tok/s;energy_vs_colocated="
                     f"{eratio:.2f}x;handoffs={handoffs};"
                     f"preempted={preempted}"))
    if mesh:
        # mesh-sharded data plane vs the unsharded engine on the same burst:
        # tokens hard-asserted identical, energy-per-token ratio gated by
        # compare.py's strict band (bit-exact serving makes it 1.0)
        m = _parse_mesh(mesh)
        gov = governors[0] if governors else "defaultnv"
        tps, eratio = bench_mesh(cfg, params, governor=gov,
                                 nreq=6 if quick else 12,
                                 out_len=12 if quick else 24, mesh=m)
        rows.append((f"engine_mesh_dp{m[0]}tp{m[1]}_{gov}",
                     1e6 / max(tps, 1e-9),
                     f"{tps:.0f}tok/s;"
                     f"energy_per_tok_vs_unsharded={eratio:.4f}x"))
    return rows


def _paged_rows(cfg, params, *, gov, b, steps, nreq, n_admit, warm2,
                dense_decode):
    """Paged-vs-dense and long-prompt-admission scenarios.  ``dense_decode``
    is the already-measured slot-native decode tok/s for this (gov, b)."""
    from repro.configs import get_config
    rows = []
    dense = dense_decode
    pg = warm2(bench_decode, cfg, params, batch=b, governor=gov,
               slot_native=True, steps=steps, paged=True)
    rows.append((f"engine_decode_b{b}_{gov}_paged", 1e6 / pg,
                 f"{pg:.0f}tok/s;vs_dense={pg / dense:.2f}x"))
    pg = warm2(bench_serve, cfg, params, batch=b, governor=gov,
               slot_native=True, nreq=nreq, out_len=32, paged=True)
    rows.append((f"engine_serve_b{b}_{gov}_paged", 1e6 / pg,
                 f"{pg:.0f}tok/s"))
    streams, dense_eq, tps = bench_paged_capacity(cfg, params, governor=gov,
                                                  nreq=b, out_len=16)
    rows.append((f"engine_capacity_{gov}_paged_halfmem", 1e6 / max(tps, 1e-9),
                 f"streams={streams};dense_equiv={dense_eq};{tps:.0f}tok/s"))
    # long-prompt chunked admission needs a sliding-window config
    wcfg = get_config("gemma2-9b").smoke()
    from repro.models import init_params as _ip
    wparams = _ip(jax.random.PRNGKey(0), wcfg)
    legacy = bench_long_admit(wcfg, wparams, governor=gov, n=n_admit,
                              chunked=False)
    chunked = bench_long_admit(wcfg, wparams, governor=gov, n=n_admit,
                               chunked=True)
    rows.append((f"engine_longadmit_{gov}_legacy", 1e6 / legacy,
                 f"{legacy:.1f}adm/s"))
    rows.append((f"engine_longadmit_{gov}_chunked", 1e6 / chunked,
                 f"{chunked:.1f}adm/s;speedup={chunked / legacy:.1f}x"))
    return rows


def bench_serving_engine_quick():
    """Registry entry for benchmarks.run (CI-sized)."""
    return bench_serving_engine(quick=True, batches=(1, 8),
                                governors=("defaultnv",), paged=True,
                                prefix_cache=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="add paged-vs-dense, capacity and long-prompt-"
                         "admission scenarios")
    ap.add_argument("--cluster", action="store_true",
                    help="add the 2-replica disaggregated prefill/decode "
                         "mini-trace vs the 2x-colocated max-freq baseline")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="add the shared-system-prompt burst: prefix cache "
                         "vs cold cache (prefill tokens computed, hit rate, "
                         "energy/request; token identity hard-asserted)")
    ap.add_argument("--mesh", default="", metavar="DP,TP",
                    help="add the mesh-sharded serving scenario on a "
                         "'dp,tp' device mesh ('auto' sizes to the visible "
                         "devices; force CPU devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8): tokens "
                         "hard-asserted identical to the unsharded engine, "
                         "energy-per-token parity gated by compare.py")
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--batches", default="1,4,8")
    ap.add_argument("--governors", default="greenllm,defaultnv")
    ap.add_argument("--json", default="", metavar="OUT",
                    help="also write rows + run config as a JSON document "
                         "(the BENCH_*.json baseline format)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    batches = tuple(int(x) for x in args.batches.split(","))
    # --governors "" runs only the standalone scenarios (e.g. --cluster)
    governors = tuple(g for g in args.governors.split(",") if g)
    extras = {}
    rows = bench_serving_engine(
        quick=args.quick, arch=args.arch, batches=batches,
        governors=governors, paged=args.paged, cluster=args.cluster,
        prefix_cache=args.prefix_cache, mesh=args.mesh, extras=extras)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}", flush=True)
    if args.json:
        doc = {
            "benchmark": "serving_engine",
            "config": {"quick": args.quick, "arch": args.arch,
                       "batches": list(batches),
                       "governors": list(governors),
                       "paged": args.paged, "cluster": args.cluster,
                       "prefix_cache": args.prefix_cache,
                       "mesh": args.mesh},
            "backend": jax.default_backend(),
            "rows": [{"name": n, "us_per_call": round(us, 1),
                      "derived": d} for n, us, d in rows],
            # final registry state of the instrumented serve run: makes the
            # baseline diffable on served work, not just wall time
            **({"metrics_snapshot": extras["metrics_snapshot"]}
               if "metrics_snapshot" in extras else {}),
        }
        with open(args.json, "w") as fh:
            json.dump(doc, fh, indent=2)
            fh.write("\n")
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
