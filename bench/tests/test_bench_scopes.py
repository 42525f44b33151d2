"""The engine's host spans on the profiler's clock, and their attribution:
a burst served on the CPU under ``jax.profiler.trace`` read back with
``devtrace.read``, and ``scopes.reduce`` on a built trace and on a slice of
a trace recorded on one TPU v5e (``bench/testdata``)."""
import gzip
import json
import os
import re
import sys
from collections import Counter

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import devtrace, scopes  # noqa: E402

DATA = os.path.join(BENCH, "testdata", "v5e_chat_scoped.json.gz")
ATTRS = {
    "engine.step": {"pending", "active"},
    "engine.admit": {"admitted"},
    "engine.prefill": {"rid", "tokens", "bucket"},
    "engine.chunk": {"rid", "start", "tokens"},
    "engine.grow_pages": {"k", "pages_used"},
    "engine.dispatch": {"ctx", "kb", "rows", "sampled"},
    "engine.drain": {"steps"},
    "engine.bookkeep": {"steps", "rows", "finished"},
}
ONCE = ("engine.admit", "engine.grow_pages", "engine.drain",
        "engine.bookkeep")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A paged engine with the prefix cache serves a burst, then the burst's
    first prompt again (a cache hit, so it prefills in chunks), all under
    the profiler."""
    import jax
    from repro.configs import get_config
    from repro.core import SamplingParams
    from repro.models import init_params
    from repro.serving import EngineConfig, Server, ServingEngine
    cfg = get_config("qwen2-1.5b").smoke()
    eng = ServingEngine(cfg, params=init_params(jax.random.PRNGKey(0), cfg),
                        ecfg=EngineConfig(max_batch=4, max_len=128,
                                          paged=True, prefix_cache=True,
                                          decode_block=8))
    srv = Server(eng)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n)
               for n in (40, 20, 33, 12, 27)]
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(d, profiler_options=opts):
        for i, p in enumerate(prompts):
            srv.submit(p, SamplingParams(max_tokens=6 + 3 * i,
                                         temperature=0.8 * (i % 2), top_k=20))
        srv.run()
        srv.submit(prompts[0], SamplingParams(max_tokens=5))
        srv.run()
    spans = [e for e in devtrace.read(d)["host"]
             if e[2].startswith("engine.")]
    return eng, spans


def test_every_engine_span_is_traced_with_its_attrs(served):
    _, spans = served
    assert {e[2] for e in spans} == set(ATTRS)
    for a, b, name, stats in spans:
        assert set(stats) == ATTRS[name], name
        assert b >= a


def test_one_bookkeep_span_per_host_drain(served):
    eng, spans = served
    n = Counter(e[2] for e in spans)
    assert n["engine.bookkeep"] == n["engine.drain"] == eng._host_drains > 0


def test_spans_per_round_stay_within_the_bound(served):
    """No span inside a per-token or per-row loop: a round holds each fixed
    span at most once, one prefill per slot admission, one span per chunk
    and per kernel dispatch, and its dispatches run the steps it drains."""
    _, spans = served
    rounds = [e for e in spans if e[2] == "engine.step"]
    assert len(rounds) > 3
    for a, b, _, _ in rounds:
        inner = [e for e in spans if a <= e[0] and e[1] <= b]
        n = Counter(e[2] for e in inner)
        assert n["engine.step"] == 1
        assert all(n[s] <= 1 for s in ONCE)
        admitted = sum(e[3]["admitted"] for e in inner
                       if e[2] == "engine.admit")
        assert n["engine.prefill"] <= admitted
        assert len(inner) <= 5 + admitted + n["engine.chunk"] + \
            n["engine.dispatch"]
        if n["engine.drain"]:
            steps = next(e[3]["steps"] for e in inner
                         if e[2] == "engine.drain")
            kbs = [e[3]["kb"] for e in inner if e[2] == "engine.dispatch"]
            assert sum(kbs) == steps and len(kbs) <= steps
    # the repeated prompt hit the prefix cache and prefilled in chunks
    assert any(e[2] == "engine.chunk" for e in spans)


def _xspace(tmp_path, scoped=True):
    """A trace file built from a text proto: two decode programs and a
    prefill on one TPU in a 100 ns window, nested engine spans on the host;
    op paths in the op metadata, as the chip writes them (a string, or a
    reference to an interned one)."""
    from jax.profiler import ProfileData
    dec = "jit(_paged_decode_block_kernel)/decode_block/while/body/"
    ops = [  # start, end, name, program, op_name path
        (10, 20, "%fusion.1 = f32[8] fusion(x)", 7, dec + "sampler/sort:"),
        (20, 30, "%fusion.2 = f32[8] fusion(x)", 7, dec + "attention/dot:"),
        (10, 30, "%while.3 = (f32[8]) while(x)", 7, dec + "while:"),
        (35, 40, "%fusion.4 = f32[8] fusion(x)", 9,
         "jit(_prefill_kernel)/prefill/attention/dot:"),
        (55, 65, "%fusion.5 = f32[8] fusion(x)", 7, dec + "kv_cache/gather:"),
        (65, 80, "%fusion.1 = f32[8] fusion(x)", 7, dec + "sampler/sort:"),
        (80, 90, "%copy.7 = f32[8] copy(x)", 7, "")]
    if not scoped:
        ops = [o[:4] + (o[4].replace("decode_block/", "")
                        .replace("sampler/", "").replace("attention/", "")
                        .replace("kv_cache/", ""),) for o in ops]
    mods = [(10, 35, "jit__paged_decode_block_kernel(7)"),
            (35, 40, "jit__prefill_kernel(9)"),
            (55, 90, "jit__paged_decode_block_kernel(7)")]
    spans = [(0, 100, devtrace.WINDOW_SPAN), (0, 50, "engine.step"),
             (5, 15, "engine.admit"), (40, 50, "engine.bookkeep"),
             (50, 100, "engine.step"), (90, 98, "engine.drain"),
             (60, 70, "other")]
    meta, ids = [], {}

    def mid(name):
        return ids.setdefault(name, len(ids) + 1)

    for _, _, n, pid, path in ops:
        if (n, pid) in ids:
            continue
        i = ids[(n, pid)] = len(ids) + 1
        stat = (f'stats {{ metadata_id: 2 ref_value: 3 }}' if "sampler" in path
                else f'stats {{ metadata_id: 2 str_value: "{path}" }}')
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" stats {{ metadata_id: 1 uint64_value: '
                    f'{pid} }} {stat if path else ""} }} }}')
    for n in [m[2] for m in mods]:
        i = mid(n)
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}')

    def events(evs, key):
        return " ".join(f"events {{ metadata_id: {key(e)} offset_ps: "
                        f"{e[0] * 1000} duration_ps: {(e[1] - e[0]) * 1000} }}"
                        for e in evs)

    sampler = dec + "sampler/sort:" if scoped else dec + "sort:"
    device = (
        'planes { id: 1 name: "/device:TPU:0" '
        'lines { id: 1 name: "XLA Modules" timestamp_ns: 0 '
        + events(mods, lambda e: ids[e[2]]) + ' } '
        'lines { id: 2 name: "XLA Ops" timestamp_ns: 0 '
        + events(ops, lambda e: ids[(e[2], e[3])]) + ' } '
        + " ".join(meta) +
        ' stat_metadata { key: 1 value { id: 1 name: "program_id" } }'
        ' stat_metadata { key: 2 value { id: 2 name: "tf_op" } }'
        f' stat_metadata {{ key: 3 value {{ id: 3 name: "{sampler}" }} }} }}')
    hmeta = {n: i + 1 for i, n in enumerate(sorted({e[2] for e in spans}))}
    host = ('planes { id: 2 name: "/host:CPU" lines { id: 1 name: "python3" '
            'timestamp_ns: 0 ' + events(spans, lambda e: hmeta[e[2]]) + ' } '
            + " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }}' for n, i in hmeta.items()) + ' }')
    f = tmp_path / ("scoped" if scoped else "bare") / "t.xplane.pb"
    f.parent.mkdir()
    f.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        device + " " + host))
    return str(f)


def test_op_paths_read_from_the_op_metadata(tmp_path):
    paths = scopes.op_paths(_xspace(tmp_path))
    dec = "jit(_paged_decode_block_kernel)/decode_block/while/body/"
    assert paths[(7, "%fusion.1 = f32[8] fusion(x)")] == dec + "sampler/sort:"
    assert paths[(7, "%fusion.5 = f32[8] fusion(x)")] == \
        dec + "kv_cache/gather:"
    assert (7, "%copy.7 = f32[8] copy(x)") not in paths
    assert scopes.program_id("jit__prefill_kernel(9)") == 9
    assert scopes.in_scope(dec + "sampler/sort:", "sampler")
    assert not scopes.in_scope(dec + "sampler_x/sort:", "sampler")


def test_attribution_on_a_built_trace(tmp_path):
    f = _xspace(tmp_path)
    sc = scopes.reduce(devtrace.read(f), scopes.op_paths(f))
    ns = pytest.approx
    assert sc["decode_s"] == ns(60e-9)
    assert sc["scope_s"] == {k: ns(v * 1e-9) for k, v in
                             dict(decode_block=45, sampler=25, attention=10,
                                  kv_cache=10).items()}
    # idle: 0-10, 30-35, 40-55, 90-100; innermost span over each stretch
    assert sc["idle_s"] == ns(40e-9)
    assert sc["span_idle_s"] == {k: ns(v * 1e-9) for k, v in dict(
        [("engine.step", 17), ("engine.admit", 5), ("engine.bookkeep", 10),
         ("engine.drain", 8)]).items()}
    assert sc["step_idle_s"] == ns(40e-9) and sc["steps"] == 2
    m = scopes.metrics(sc)
    assert m["sampler_share"] == ns(100 * 25 / 60)
    assert m["decode_attention_share"] == ns(100 * 20 / 60)
    assert m["step_host_idle_ms"] == ns(20e-6)


def test_unscoped_program_reads_none(tmp_path):
    f = _xspace(tmp_path, scoped=False)
    tr = devtrace.read(f)
    m = scopes.metrics(scopes.reduce(tr, scopes.op_paths(f)))
    assert m["sampler_share"] is None and m["decode_attention_share"] is None
    assert m["step_host_idle_ms"] is not None
    assert scopes.metrics(None) == dict.fromkeys(m)
    no_window = dict(tr, host=[e for e in tr["host"]
                               if e[2] != devtrace.WINDOW_SPAN])
    assert scopes.reduce(no_window, {}) is None


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA) as f:
        raw = json.load(f)
    a0, a1 = raw["window"]
    tr = {"device": {int(d): {ln: [(a, b, n, {}) for a, b, n in evs]
                              for ln, evs in lines.items()}
                     for d, lines in raw["device"].items()},
          "host": [tuple(e) for e in raw["host"]]
          + [(a0, a1, devtrace.WINDOW_SPAN, {})]}
    paths = {(p, n): v for p, n, v in raw["paths"]}
    return raw, tr, paths, scopes.reduce(tr, paths)


def test_scope_seconds_match_a_sum_over_the_slice(recorded):
    raw, _, paths, sc = recorded
    a0, a1 = raw["window"]
    mods = [(max(a, a0), min(b, a1), int(n.split("(")[1].rstrip(")")))
            for a, b, n in raw["device"]["0"]["XLA Modules"]
            if n.startswith("jit__paged_decode_block_kernel(")]
    want, decode = Counter(), sum(b - a for a, b, _ in mods) / 1e9
    for a, b, n in raw["device"]["0"]["XLA Ops"]:
        a, b = max(a, a0), min(b, a1)
        pid = next((p for x, y, p in mods if x <= a and b <= y), None)
        if pid is None or re.search(r"\s(while|conditional|call)\(", n):
            continue
        parts = paths.get((pid, n), "").split(":")[0].split("/")
        for s in scopes.SCOPES:
            want[s] += (b - a) / 1e9 * (s in parts)
    assert sc["decode_s"] == pytest.approx(decode)
    assert sc["scoped_ops"] > 500
    for s in ("decode_block", "sampler", "attention", "kv_cache"):
        assert sc["scope_s"][s] == pytest.approx(want[s], rel=1e-9), s
        assert 0 < sc["scope_s"][s] < decode
    m = scopes.metrics(sc)
    assert m["sampler_share"] + m["decode_attention_share"] <= 100


def test_idle_of_the_slice_goes_to_its_engine_spans(recorded):
    _, tr, _, sc = recorded
    red = devtrace.reduce(tr)
    idle = red["window_s"] - red["busy_s"]
    assert sc["idle_s"] == pytest.approx(idle)
    attributed = sum(sc["span_idle_s"].values())
    assert 0 < attributed <= sc["idle_s"] + 1e-12
    assert sc["step_idle_s"] <= sc["idle_s"] + 1e-12
    # the host's bookkeeping after a drain and the admission's eager work
    # before its prefill hold the device's idle time here
    top = sorted(sc["span_idle_s"], key=sc["span_idle_s"].get)[-2:]
    assert set(top) == {"engine.bookkeep", "engine.prefill"}


def test_slice_without_scopes_reads_none(recorded):
    _, tr, paths, _ = recorded
    bare = {k: "/".join(p for p in v.split("/") if p not in scopes.SCOPES)
            for k, v in paths.items()}
    m = scopes.metrics(scopes.reduce(tr, bare))
    assert m["sampler_share"] is None and m["decode_attention_share"] is None
    assert scopes.metrics(scopes.reduce(tr, {}))["sampler_share"] is None
