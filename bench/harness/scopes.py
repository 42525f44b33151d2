"""Attribute a profiler trace to the program's own names: device time of the
decode block program per ``jax.named_scope``, and device idle time per
engine host span (``repro.core.tracing.host_span``).

Reads the planes ``devtrace.read`` returns, within the same window (the host
span ``bench.window``), and the ops' ``op_name`` paths, which the trace keeps
in each device op's event metadata (stat ``tf_op``), where ``devtrace.read``
(``jax.profiler.ProfileData``) cannot see them: ``op_paths`` reads them from
the ``.xplane.pb`` itself.

* An op belongs to the decode block program when it lies inside one of that
  program's intervals on the ``XLA Modules`` line, and to a scope when its
  ``/``-separated ``op_name`` path holds the scope's name.  Control flow
  (``while``, ``conditional``, ``call``) is left out: its events span the
  ops inside it.  A fusion carries the path of its root op.
* Idle time is the window less the union of op intervals, per device.  An
  idle stretch belongs to the innermost ``engine.*`` span that covers it.

With no op of the decode program carrying a scope (a trace of a program
compiled without them, or an executable read from a compile cache filled by
such a program: the cache key leaves metadata out) the scope shares read
None; they are never computed over unscoped ops.

    python bench/harness/scopes.py <trace dir or .xplane.pb>
"""
from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

try:
    from . import devtrace
except ImportError:                 # run as a script
    import devtrace

DECODE_PROGRAMS = ("_paged_decode_block_kernel", "_decode_block_kernel")
SCOPES = ("decode_block", "prefill", "prefill_chunk", "kv_cache",
          "attention", "sampler")
SPAN_PREFIX = "engine."
STEP_SPAN = "engine.step"


# -- op_name paths from the .xplane.pb (protobuf wire format, XSpace schema:
# XSpace.planes 1; XPlane name 2, event_metadata 4, stat_metadata 5;
# map entry key 1, value 2; XEventMetadata name 2, stats 5; XStatMetadata
# name 2; XStat metadata_id 1, uint64 3, int64 4, str 5, ref 7)

def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field, skipped fixed-width ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield num, v
        elif wire == 2:
            m, i = _varint(b, i)
            yield num, b[i:i + m]
            i += m
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in a trace message")


def _map_value(entry):
    return next((v for k, v in _fields(entry) if k == 2), b"")


def op_paths(path: str) -> Dict[Tuple[int, str], str]:
    """(program id, op event name) -> the op's ``op_name`` path, for every
    device op in the trace whose metadata carries one."""
    with open(devtrace.find_xplane(path), "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for k, v in _fields(plane):
            if k == 2:
                name = bytes(v).decode()
            elif k == 4:
                events.append(_map_value(v))
            elif k == 5:
                sid = next((x for j, x in _fields(v) if j == 1), 0)
                stat_names[sid] = next((bytes(x).decode() for j, x in
                                        _fields(_map_value(v)) if j == 2), "")
        if not devtrace.DEVICE_PLANE.match(name):
            continue
        for md in events:
            op, stats = "", {}
            for k, v in _fields(md):
                if k == 2:
                    op = bytes(v).decode()
                elif k == 5:
                    sid, val = 0, None
                    for j, x in _fields(v):
                        if j == 1:
                            sid = x
                        elif j in (3, 4):
                            val = x
                        elif j == 5:
                            val = bytes(x).decode()
                        elif j == 7:
                            val = stat_names.get(x)
                    stats[stat_names.get(sid)] = val
            if stats.get("tf_op") and stats.get("program_id") is not None:
                out[(stats["program_id"], op)] = stats["tf_op"]
    return out


def program_id(module_event: str) -> Optional[int]:
    """'jit__prefill_kernel(123)' -> 123."""
    tail = module_event.rpartition("(")[2].rstrip(")")
    return int(tail) if tail.isdigit() else None


def in_scope(path: str, scope: str) -> bool:
    """Is ``scope`` a component of ``path`` ('a/b/c:op_type')?"""
    return scope in path.split(":")[0].split("/")


def _innermost(spans) -> List[tuple]:
    """Cut the host spans' cover into pieces, each with the shortest span
    over it: [(start, end, name)], sorted and disjoint."""
    edges = sorted({x for s in spans for x in s[:2]})
    out = []
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        cover = [i for i, s in enumerate(spans) if s[0] <= mid <= s[1]]
        if cover:
            i = min(cover, key=lambda j: spans[j][1] - spans[j][0])
            out.append((a, b, spans[i][2]))
    return out


def _program_at(a: float, b: float, progs) -> Optional[int]:
    """The program id of the interval of sorted, disjoint ``progs``
    ((start, end, id)) that holds [a, b], or None."""
    i = bisect.bisect_right(progs, (a, float("inf"))) - 1
    if i >= 0 and progs[i][0] <= a and b <= progs[i][1]:
        return progs[i][2]
    return None


def reduce(tr: dict, paths: Dict[Tuple[int, str], str],
           devices: Optional[Iterable[int]] = None) -> Optional[dict]:
    """Per-scope device seconds in the decode block program, the program's
    device seconds, device idle seconds per innermost engine span and
    inside ``engine.step`` spans, and the count of those spans, each a mean
    over the planes of ``devices`` (or all).  ``tr`` is ``devtrace.read``'s,
    ``paths`` ``op_paths``' of the same trace.  None without device events
    or without the window span."""
    win = [e for e in tr["host"] if e[2] == devtrace.WINDOW_SPAN]
    dev = devtrace.planes(tr, devices)
    if not win or not dev:
        return None
    w0, w1 = win[0][0], win[0][1]
    spans = sorted((e for e in tr["host"] if e[2].startswith(SPAN_PREFIX)
                    and e[1] > w0 and e[0] < w1), key=lambda e: e[0])
    pieces = _innermost([(max(a, w0), min(b, w1), n) for a, b, n, _ in spans])
    starts = [p[0] for p in pieces]
    steps = devtrace._union([(max(a, w0), min(b, w1))
                             for a, b, n, _ in spans if n == STEP_SPAN])
    n_dev = len(dev)
    decode_s, scoped_ops = 0.0, 0
    scope_s: Dict[str, float] = defaultdict(float)
    idle_s, step_idle_s = 0.0, 0.0
    span_idle: Dict[str, float] = defaultdict(float)
    for lines in dev.values():
        progs = sorted((max(a, w0), min(b, w1), program_id(n))
                       for a, b, n, _ in lines.get(devtrace.MODULES_LINE, [])
                       if devtrace.module_name(n) in DECODE_PROGRAMS
                       and min(b, w1) > max(a, w0))
        decode_s += sum(b - a for a, b, _ in progs) / n_dev
        busy = []
        for a, b, name, _ in lines.get(devtrace.OPS_LINE, []):
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            busy.append((a, b))
            pid = _program_at(a, b, progs)
            if pid is None or devtrace.op_label(name) is None:
                continue
            path = paths.get((pid, name), "")
            hit = [s for s in SCOPES if in_scope(path, s)]
            scoped_ops += bool(hit)
            for s in hit:
                scope_s[s] += (b - a) / n_dev
        u = devtrace._union(busy)
        edges = [w0] + [x for ab in u for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            idle_s += (b - a) / n_dev
            step_idle_s += _overlap(a, b, steps) / n_dev
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            for p0, p1, name in pieces[i:]:
                if p0 >= b:
                    break
                o = min(b, p1) - max(a, p0)
                if o > 0:
                    span_idle[name] += o / n_dev
    return {
        "window_s": (w1 - w0) / 1e9,
        "decode_s": decode_s / 1e9,
        "scoped_ops": scoped_ops,
        "scope_s": {s: v / 1e9 for s, v in scope_s.items()},
        "idle_s": idle_s / 1e9,
        "span_idle_s": {n: v / 1e9 for n, v in sorted(
            span_idle.items(), key=lambda kv: -kv[1])},
        "step_idle_s": step_idle_s / 1e9,
        "steps": sum(1 for e in spans if e[2] == STEP_SPAN),
    }


def _overlap(a: float, b: float, spans) -> float:
    i = max(bisect.bisect_right(spans, (a, float("inf"))) - 1, 0)
    out = 0.0
    for s0, s1 in spans[i:]:
        if s0 >= b:
            break
        out += max(0.0, min(b, s1) - max(a, s0))
    return out


def share(sc: Optional[dict], *scopes: str) -> Optional[float]:
    """Percent of the decode block program's device time spent in ops under
    any of ``scopes``; None when no op of the program carries a scope."""
    if not sc or not sc["scoped_ops"] or not sc["decode_s"]:
        return None
    return 100.0 * sum(sc["scope_s"].get(s, 0.0) for s in scopes) / \
        sc["decode_s"]


def metrics(sc: Optional[dict]) -> dict:
    """The three per-layer numbers this attribution gives."""
    idle = None
    if sc and sc["steps"]:
        idle = sc["step_idle_s"] / sc["steps"] * 1e3
    return {"sampler_share": share(sc, "sampler"),
            "decode_attention_share": share(sc, "kv_cache", "attention"),
            "step_host_idle_ms": idle}


if __name__ == "__main__":
    sc = reduce(devtrace.read(sys.argv[1]), op_paths(sys.argv[1]))
    print(json.dumps({"scopes": sc, "metrics": metrics(sc)}, indent=1))
