"""Production observability tests: the metrics registry and tracer units,
the telemetry sliding-window edge cases they depend on, and the wiring
through the serving planes.

* Registry: counter/gauge/histogram semantics, Prometheus text exposition
  (validated by the repo's own ``parse_prometheus``), the snapshot timeline
  (``record_snapshot`` / ``query`` / ``series``) and its JSONL round-trip.
* Tracer: span/instant/decision recording, ring-buffer drop accounting,
  Chrome-trace structure, JSONL round-trip, and ``decision_at`` (the audit
  primitive: what decision explains the frequency at instant t?).
* Telemetry windows: eviction exactly at the horizon boundary, out-of-order
  timestamps against the high-water clock, and the NaN empty-window
  sentinels (an empty window is "no data", never "zero latency").
* Engine wiring: lifecycle spans, DVFS reason codes, SLO counters — and the
  zero-overhead regression: a run with sinks installed must be *identical*
  (host drains, virtual clock, energy, tokens) to a run without, because
  publication rides existing host-sync points.
* Server retention: ``retain_reports`` bounds handle/backend bookkeeping
  growth under a request storm (the long-lived-server leak fix).
"""
import json
import math

import jax
import numpy as np
import pytest

from repro.core import (MetricsRegistry, OccupancyMeter, Request,
                        SamplingParams, SlidingWindow, TBTMeter, TPSMeter,
                        Tracer, parse_prometheus, read_timeline_jsonl,
                        read_trace_jsonl)
from repro.core.decode_controller import (DecodeControllerConfig,
                                          DualLoopController)
from repro.core.hardware import A100_SXM4_40G
from repro.core.models import TPSFreqTable
from repro.models import init_params
from repro.models.config import ModelConfig
from repro.serving import EngineConfig, Server, ServingCluster, ServingEngine

KEY = jax.random.PRNGKey(0)
MAXLEN = 96

# every reason code a DVFS decision may carry (stable API — see README)
DECODE_REASONS = {"tbt_pressure", "tbt_pressure_sat", "tbt_slack",
                  "tbt_slack_sat", "tbt_hold", "tps_band_init",
                  "tps_band_shift", "occ_pressure", "occ_decay",
                  "band_reclip", "band_adapt_up", "band_adapt_down"}
PREFILL_REASONS = {"empty_queue", "infeasible_fmax", "optimal",
                   "job_slo_floor", "stability_floor"}


def _cfg(**kw) -> ModelConfig:
    base = dict(name="tobs", arch_type="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128,
                vocab_size=128, dtype="float32", max_seq=512)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, init_params(KEY, cfg)


def _engine(cfg, params, **kw):
    ekw = dict(max_batch=4, max_len=MAXLEN, paged=True,
               governor="greenllm")
    ekw.update({k: v for k, v in kw.items()
                if k not in ("metrics", "tracer", "name")})
    return ServingEngine(cfg, params=params, ecfg=EngineConfig(**ekw),
                         **{k: kw[k] for k in ("metrics", "tracer", "name")
                            if k in kw})


def _burst(srv, vocab, n=6, out=10, arrival_gap=0.01):
    rng = np.random.default_rng(0)
    for i in range(n):
        srv.submit(rng.integers(0, vocab, size=int(rng.integers(12, 40))),
                   SamplingParams(max_tokens=out), arrival=arrival_gap * i)
    return srv.run()


# -- metrics registry -----------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help", ("who",))
    c.labels(who="a").inc()
    c.labels(who="a").inc(2.5)
    c.labels(who="b").inc(1)
    g = reg.gauge("g", "", ("who",))
    g.labels(who="a").set(4.0)
    g.labels(who="a").inc(-1.0)
    flat = reg.flat()
    assert flat['c_total{who="a"}'] == 3.5
    assert flat['c_total{who="b"}'] == 1.0
    assert flat['g{who="a"}'] == 3.0
    # counters are monotone
    with pytest.raises(ValueError):
        c.labels(who="a").inc(-1)
    # a family name reused with a different type is a bug, not a new family
    with pytest.raises(ValueError):
        reg.gauge("c_total", "", ("who",))


def test_histogram_cumulative_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "", (), buckets=(0.1, 0.5, 1.0))
    for v in (0.05, 0.1, 0.3, 2.0):
        h.labels().observe(v)
    h.labels().observe(0.4, n=3)     # batch-weighted (shared TBT sample)
    flat = reg.flat()
    assert flat['lat_seconds_bucket{le="0.1"}'] == 2          # 0.05, 0.1
    assert flat['lat_seconds_bucket{le="0.5"}'] == 6          # + 0.3, 0.4x3
    assert flat['lat_seconds_bucket{le="+Inf"}'] == 7
    assert flat["lat_seconds_count"] == 7
    assert abs(flat["lat_seconds_sum"] - (0.05 + 0.1 + 0.3 + 2.0 + 1.2)) \
        < 1e-9


def test_prometheus_render_parses():
    reg = MetricsRegistry()
    reg.counter("a_total", "with \"quotes\" and {braces}",
                ("x",)).labels(x='v"1').inc(2)
    reg.gauge("b", "").labels().set(-1.5)
    reg.histogram("h_s", "", (), buckets=(1.0,)).labels().observe(0.5)
    text = reg.render_prometheus()
    parsed = parse_prometheus(text)
    assert parsed == reg.flat()
    # malformed exposition is rejected, not silently dropped
    with pytest.raises(ValueError):
        parse_prometheus("no_value_here{")


def test_snapshot_timeline_query(tmp_path):
    reg = MetricsRegistry(snapshot_min_dt=0.1)
    g = reg.gauge("v", "").labels()
    g.set(1.0)
    assert reg.record_snapshot(0.0)
    g.set(2.0)
    assert not reg.record_snapshot(0.05)      # throttled by min_dt
    assert reg.record_snapshot(0.2)
    g.set(3.0)
    assert reg.record_snapshot(0.2)           # same t replaces
    assert not reg.record_snapshot(0.1)       # clocks never run backwards
    assert len(reg.timeline) == 2
    assert reg.query(-1.0) is None
    assert reg.query(0.0)["v"] == 1.0
    assert reg.query(0.1)["v"] == 1.0         # last at-or-before
    assert reg.query(5.0)["v"] == 3.0
    assert reg.series("v") == [(0.0, 1.0), (0.2, 3.0)]
    out = tmp_path / "tl.jsonl"
    assert reg.write_timeline_jsonl(str(out)) == 2
    assert read_timeline_jsonl(str(out)) == reg.timeline


# -- tracer ---------------------------------------------------------------------------


def test_tracer_spans_decisions_and_ring(tmp_path):
    tr = Tracer(capacity=4)
    for i in range(6):
        tr.span("prefill", i, 0.1 * i, 0.1 * i + 0.05, replica="p0",
                tokens=32)
    assert len(list(tr.spans())) == 4          # ring kept the newest
    assert tr.dropped_spans == 2
    assert {s.rid for s in tr.spans()} == {2, 3, 4, 5}

    tr = Tracer()
    tr.span("queue", 1, 0.0, 0.2, replica="p0")
    tr.instant("finish", 1, 0.5, replica="d0", tokens=10)
    tr.decision(0.1, "d0", "decode", 990.0, "tbt_slack", p95_tbt=0.03)
    tr.decision(0.3, "d0", "decode", 1005.0, "tbt_pressure", p95_tbt=0.2)
    tr.decision(0.3, "p0", "prefill", 700.0, "optimal", n_jobs=2)
    assert [s.name for s in tr.spans(rid=1)] == ["queue", "finish"]
    assert len(list(tr.decisions(replica="d0"))) == 2
    # the audit primitive: last decision at-or-before t for a replica
    assert tr.decision_at(0.2, "d0").freq_mhz == 990.0
    assert tr.decision_at(0.3, "d0").reason == "tbt_pressure"
    assert tr.decision_at(0.05, "p0", phase="prefill") is None

    # bind() adapts controllers that don't know their replica name
    cb = tr.bind("d1")
    cb(0.7, "decode", 1200.0, "tbt_hold", margin=0.8)
    assert tr.decision_at(0.7, "d1").inputs["margin"] == 0.8

    out = tmp_path / "trace.jsonl"
    n = tr.write_jsonl(str(out))
    assert n == len(list(tr.spans())) + len(list(tr.decisions())) == 6
    back = read_trace_jsonl(str(out))
    assert [s.name for s in back.spans()] == [s.name for s in tr.spans()]
    assert [d.reason for d in back.decisions()] == \
        [d.reason for d in tr.decisions()]


def test_chrome_trace_structure(tmp_path):
    tr = Tracer()
    tr.span("prefill", 3, 0.0, 0.5, replica="prefill0")
    tr.instant("finish", 3, 0.6, replica="decode0")
    tr.decision(0.25, "decode0", "decode", 900.0, "tbt_slack")
    doc = tr.to_chrome_trace()
    evs = doc["traceEvents"]
    # one process per replica, announced by metadata events
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert names == {"prefill0", "decode0"}
    x = next(e for e in evs if e["ph"] == "X")
    assert x["ts"] == 0.0 and x["dur"] == 0.5 * 1e6   # microseconds
    assert x["tid"] == 4                              # rid + 1
    assert any(e["ph"] == "i" and e["name"] == "dvfs:tbt_slack"
               for e in evs)
    out = tmp_path / "c.json"
    tr.write_chrome_trace(str(out))
    assert json.load(open(out))["traceEvents"]


# -- telemetry window edges (satellite: NaN sentinels + eviction) ---------------------


def test_sliding_window_boundary_eviction():
    w = SlidingWindow(horizon=1.0)
    w.push(0.0, 1.0)
    w.push(1.0, 2.0)
    # a sample exactly at (now - horizon) is retained: eviction is strict <
    assert list(w.values(1.0)) == [1.0, 2.0]
    w.push(1.0 + 1e-9, 3.0)
    assert list(w.values(1.0 + 1e-9)) == [2.0, 3.0]


def test_sliding_window_out_of_order():
    w = SlidingWindow(horizon=1.0)
    w.push(5.0, 1.0)          # high-water at 5.0
    w.push(0.5, 99.0)         # stale sample, already outside the window
    w.push(4.5, 2.0)          # out of order but inside the window
    assert sorted(w.values(5.0).tolist()) == [1.0, 2.0]
    # the high-water clock rules: a query at an *earlier* now cannot
    # resurrect evicted samples or evict live ones
    assert sorted(w.values(4.2).tolist()) == [1.0, 2.0]
    assert w.count(5.0) == 2


def test_empty_window_sentinels():
    occ, tbt, tps = OccupancyMeter(1.0), TBTMeter(1.0), TPSMeter(1.0)
    assert math.isnan(occ.mean(0.0)) and math.isnan(occ.peak(0.0))
    assert math.isnan(tbt.p95(0.0)) and math.isnan(tbt.p99(0.0))
    assert tps.tps(0.0) == 0.0          # a rate of zero is a real zero
    # peak after *full* eviction is NaN too — not the stale maximum
    occ.record(0.0, 0.9)
    assert occ.peak(0.0) == 0.9
    assert math.isnan(occ.peak(10.0))
    tbt.record_tbt(0.0, 0.05)
    assert math.isnan(tbt.p95(10.0))


def test_window_property_high_water():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.tuples(st.floats(0, 100), st.floats(0, 1)),
                    min_size=1, max_size=40))
    def prop(samples):
        w = SlidingWindow(horizon=10.0)
        for t, v in samples:
            w.push(t, v)
        hw = max(t for t, _ in samples)
        kept = w.values(hw)
        expect = [v for t, v in samples if t >= hw - 10.0]
        assert sorted(kept) == sorted(expect)

    prop()


# -- DVFS decision log (controller unit) ----------------------------------------------


def _table(hw):
    tps = [200, 1000, 3000]
    freqs = hw.ladder()[::4]
    p95 = 0.08 * (np.asarray(tps)[:, None] / 3000.0) \
        * (hw.f_max / freqs[None, :])
    ept = np.tile(np.linspace(0.3, 1.0, len(freqs)), (3, 1))
    return TPSFreqTable.from_profile(tps, freqs, p95, ept, 0.1, hw.f_step)


def test_dual_loop_controller_reason_codes():
    hw = A100_SXM4_40G
    ctl = DualLoopController(hw, _table(hw), DecodeControllerConfig())
    tr = Tracer()
    ctl.on_decision = tr.bind("d0")
    t = 0.0
    for _ in range(60):                       # ~1.2 s of slow tokens
        ctl.record_tokens(t, 4, 0.2)          # p95 TBT 200ms >> 100ms SLO
        ctl.maybe_tick(t)
        t += 0.02
    ds = list(tr.decisions(replica="d0"))
    assert ds, "a saturating TBT must generate decisions"
    assert {d.reason for d in ds} <= DECODE_REASONS
    assert any(d.reason.startswith("tbt_pressure") for d in ds)
    # every decision's frequency is the controller state at that instant,
    # and its inputs carry the p95 that justified it
    fine = [d for d in ds if d.reason.startswith("tbt_")]
    assert all(d.inputs["p95_tbt"] > 0.1 for d in fine)
    assert tr.decision_at(t, "d0").freq_mhz == ctl.freq


# -- engine wiring --------------------------------------------------------------------


def test_engine_lifecycle_and_metrics(model):
    cfg, params = model
    reg, tr = MetricsRegistry(), Tracer()
    eng = _engine(cfg, params, name="e0", metrics=reg, tracer=tr)
    rep = _burst(Server(eng), cfg.vocab_size)
    assert rep.completed == 6
    flat = reg.flat()
    assert flat['greenllm_requests_total{replica="e0",event="submitted"}'] \
        == 6
    assert flat['greenllm_requests_total{replica="e0",event="completed"}'] \
        == 6
    assert flat['greenllm_tbt_seconds_count{replica="e0"}'] > 0
    assert flat['greenllm_ttft_seconds_count{replica="e0"}'] == 6
    assert flat['greenllm_frequency_mhz{replica="e0"}'] > 0
    # energy counters track the engine's own accounting exactly
    assert abs(flat['greenllm_energy_joules_total'
                    '{replica="e0",phase="decode"}']
               - eng.decode_energy_j) < 1e-6
    spans = {s.name for s in tr.spans()}
    assert {"submit", "queue", "prefill", "decode_block",
            "finish"} <= spans
    assert {d.reason for d in tr.decisions()} <= DECODE_REASONS
    # the timeline is monotone and queryable anywhere inside the run
    times = [t for t, _ in reg.timeline]
    assert times == sorted(times) and len(times) >= 2
    assert reg.query(rep.duration_s / 2) is not None


def test_engine_zero_overhead_regression(model):
    """Observability must ride existing sync points: a run with sinks is
    step-for-step identical to a run without (same host drains, same
    virtual clock, same energy, same tokens)."""
    cfg, params = model

    def run(with_sinks):
        kw = dict(metrics=MetricsRegistry(), tracer=Tracer()) \
            if with_sinks else {}
        eng = _engine(cfg, params, name="z", **kw)
        rep = _burst(Server(eng), cfg.vocab_size)
        return eng, rep

    e0, r0 = run(False)
    e1, r1 = run(True)
    assert e1._host_drains == e0._host_drains
    assert e1.vtime == e0.vtime
    assert e1.energy_j == e0.energy_j
    assert (r1.decode_tokens, r1.prefill_tokens, r1.completed) == \
        (r0.decode_tokens, r0.prefill_tokens, r0.completed)
    # no sink installed -> no metric state anywhere
    assert e0._m is None and e0.metrics is None and e0.tracer is None


@pytest.fixture(scope="module")
def smoke_programs():
    """Compiled HLO text of the paged decode block (sampled) and of the
    slot prefill, for the qwen2-1.5b ``.smoke()`` config."""
    from repro.configs import get_config
    from repro.models import NOSHARD, init_cache
    from repro.serving import engine as E
    cfg = get_config("qwen2-1.5b").smoke()
    B, max_len, pages, ps = 4, 128, 33, 16
    S = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: init_params(KEY, cfg))
    caches = jax.eval_shape(
        lambda: init_cache(cfg, B, max_len, paged_pool=(pages, ps)))
    rows, one = S((B,), np.int32), S((), np.int32)
    lanes = (S((B, 2), np.uint32), S((B,), np.float32), rows,
             S((B,), np.float32))
    decode = E._paged_decode_block_kernel.lower(
        cfg, NOSHARD, 4, True, params, rows, caches,
        S((B, max_len // ps), np.int32), rows, S((B,), np.bool_), *lanes)
    prefill = E._prefill_kernel.lower(
        cfg, NOSHARD, True, params, S((1, 32), np.int32), one, caches, one,
        S((1, 32 // ps), np.int32), rows, rows, *lanes)
    return {k: v.compile().as_text() for k, v in
            (("decode", decode), ("prefill", prefill))}


def _op_names(text):
    import re
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("program,scopes", [
    ("decode", ("decode_block/", "kv_cache", "attention", "sampler")),
    ("prefill", ("prefill/", "attention", "sampler"))])
def test_named_scopes_reach_the_hlo_op_names(smoke_programs, program,
                                             scopes):
    """The device side of the trace: each program's ops carry the engine's
    ``jax.named_scope`` path in their ``op_name`` metadata, under the
    program's own scope."""
    names = _op_names(smoke_programs[program])
    outer = scopes[0]
    for scope in scopes:
        assert any(scope in n for n in names), scope
    # the sampler and attention sit inside the program's scope (reducer
    # bodies carry a path relative to their reduce, without the program)
    inner = [n for n in names if n.startswith("jit(")
             and ("/sampler/" in n or "/attention/" in n)]
    assert inner and all(outer in n for n in inner)
    if program == "decode":
        kv = [n for n in names if "/kv_cache/" in n]
        assert kv and not any("/attention/" in n for n in kv)


def test_host_span_records_nothing_without_a_profile():
    from repro.core.tracing import host_span
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with host_span("engine.step", pending=1, active=2) as span:
        span.set_metadata(finished=0)
    assert isinstance(span, jax.profiler.TraceAnnotation)


def test_engine_evict(model):
    cfg, params = model
    eng = _engine(cfg, params)
    srv = Server(eng)
    h = srv.submit(np.arange(16) % cfg.vocab_size,
                   SamplingParams(max_tokens=4))
    live = srv.submit(np.arange(20) % cfg.vocab_size,
                      SamplingParams(max_tokens=64))
    h.result()
    assert not eng.evict(live.rid)            # live requests stay
    assert eng.evict(h.rid)
    assert all(r.rid != h.rid for r in eng.requests)
    assert h.rid not in eng._tbt
    srv.run()


def test_server_retention_storm(model):
    """retain_reports bounds every per-request structure on a long-lived
    server: handles, backend request rows, TBT records."""
    cfg, params = model
    eng = _engine(cfg, params)
    srv = Server(eng, retain_reports=4)
    rng = np.random.default_rng(1)
    for i in range(24):
        srv.submit(rng.integers(0, cfg.vocab_size, size=16),
                   SamplingParams(max_tokens=3), arrival=0.001 * i)
    rep = srv.run()
    assert rep.completed <= 4                 # only retained rows are scored
    assert len(srv._handles) <= 4 + 4         # retained + max in flight
    assert len(eng.requests) <= 4 + 4
    assert len(eng._tbt) <= 4 + 4
    assert len(srv._terminal_order) <= 4


# -- cluster + simulator wiring -------------------------------------------------------


def test_cluster_observability(model):
    cfg, params = model
    reg, tr = MetricsRegistry(), Tracer()
    cl = ServingCluster(cfg, params=params, n_prefill=1, n_decode=1,
                        ecfg=EngineConfig(max_batch=4, max_len=MAXLEN,
                                          governor="greenllm"),
                        metrics=reg, tracer=tr)
    rep = _burst(Server(cl), cfg.vocab_size)
    assert rep.completed == 6
    flat = reg.flat()
    for r in ("prefill0", "decode0"):
        assert flat[f'greenllm_frequency_mhz{{replica="{r}"}}'] > 0
    # handoffs surface as spans and counters on both ends
    assert flat['greenllm_requests_total'
                '{replica="prefill0",event="exported"}'] == 6
    assert flat['greenllm_requests_total'
                '{replica="decode0",event="imported"}'] == 6
    assert any(s.name == "handoff" for s in tr.spans())
    # per-phase decisions with per-phase reason codes
    pre = {d.reason for d in tr.decisions(replica="prefill0",
                                          phase="prefill")}
    dec = {d.reason for d in tr.decisions(replica="decode0",
                                          phase="decode")}
    assert pre <= PREFILL_REASONS
    assert dec and dec <= DECODE_REASONS
    # kill the decode replica post-run: fault span + counter appear
    cl.kill_replica("decode0")
    assert any(s.name == "replica_kill" and s.replica == "decode0"
               for s in tr.spans())
    assert reg.flat()['greenllm_faults_total'
                      '{replica="decode0",kind="kill"}'] == 1


def test_simulator_observability():
    from repro.data import get_trace
    from repro.sim import ReplayConfig, build_simulator
    from repro.sim.replay import make_plant_fn  # noqa: F401 (sanity import)
    reg, tr = MetricsRegistry(), Tracer()
    rc = ReplayConfig(governor="greenllm")
    sim = build_simulator(_cfg(), A100_SXM4_40G, rc)
    sim.install_observability(reg, tr)
    for r in get_trace("chat_5qps", duration=6.0)[:10]:
        sim.submit(r)
    while sim.step():
        pass
    rep = sim.report()
    assert rep.completed > 0
    flat = reg.flat()
    assert flat['greenllm_requests_total{replica="node",event="submitted"}'] \
        == 10
    assert any(k.startswith("greenllm_frequency_mhz") for k in flat)
    assert sum(v for k, v in flat.items()
               if k.startswith("greenllm_energy_joules_total")) > 0
    assert {s.name for s in tr.spans()} >= {"submit", "queue", "prefill",
                                            "finish"}
    reasons = {d.reason for d in tr.decisions()}
    assert reasons <= (DECODE_REASONS | PREFILL_REASONS)
    assert any(d.phase == "prefill" for d in tr.decisions())
    # simulator evict obeys the same terminal-only contract
    done = next(r.rid for r in sim.requests if r.state.terminal)
    assert sim.evict(done)
    assert all(r.rid != done for r in sim.requests)
