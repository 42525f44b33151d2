"""Reduce a profiler trace (``.xplane.pb``) to the device numbers the
per-layer metrics read: busy time (union of op intervals) and idle gaps,
device time per jitted program, and the operations that took most time.

The window is the host span ``bench.window`` that the harness opens when
tracing starts and closes before it stops; device events are clipped to it.
Each idle gap is named by the shortest host event that covers its middle.
Only the planes of the cell's own chips are read, so the chips of a host
that the cell leaves unused do not count as idle ones.

    python bench/harness/devtrace.py <trace dir or .xplane.pb>   # dump
"""
from __future__ import annotations

import glob
import os
import re
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


CONTAINERS = ("while", "conditional", "call")


def op_label(name: str) -> Optional[str]:
    """'%fusion.203 = pred[4861952]{0:T(1024)} fusion(...)' ->
    'fusion.203 fusion pred[4861952]'; None for control flow, whose events
    span the ops inside them."""
    m = re.match(r"%?(\S+) = ", name)
    if not m:
        return name[:80]
    op, rest = m.group(1), name[m.end():]
    if rest.startswith("("):                      # tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = "tuple", rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    kind = re.match(r"[\w-]*", rest).group(0)
    if kind in CONTAINERS:
        return None
    return f"{op} {kind} {re.sub(r'{[^}]*}', '', shape)}"[:80]


def module_name(name: str) -> str:
    """'jit__prefill_kernel(123)' -> '_prefill_kernel'."""
    name = name.split("(")[0].strip()
    return name[4:] if name.startswith("jit_") else name


def _events(line) -> List[Tuple[float, float, str, dict]]:
    out = []
    for ev in line.events:
        out.append((float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                    ev.name, dict(ev.stats)))
    return out


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def read(path: str) -> dict:
    """Planes as plain lists: {"device": {i: {line: events}}, "host":
    [events]} with events (start_ns, end_ns, name, stats)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(path))
    dev: Dict[int, Dict[str, list]] = {}
    host: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev[int(m.group(1))] = {ln.name: _events(ln) for ln in plane.lines}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(e for e in _events(ln) if e[1] > e[0])
    return {"device": dev, "host": host}


def planes(tr: dict, devices: Optional[Iterable[int]] = None) -> dict:
    """The device planes of ``devices`` (``/device:TPU:<id>``), or all."""
    if devices is None:
        return tr["device"]
    keep = set(devices)
    return {i: p for i, p in tr["device"].items() if i in keep}


def reduce(tr: dict, devices: Optional[Iterable[int]] = None
           ) -> Optional[dict]:
    """Busy seconds (mean over the planes of ``devices``, or all), window
    seconds, device seconds per program, top device ops and idle gaps;
    None without device events or without the window span."""
    spans = [e for e in tr["host"] if e[2] == WINDOW_SPAN]
    dev = planes(tr, devices)
    if not spans or not dev:
        return None
    w0, w1 = spans[0][0], spans[0][1]
    busy, modules, ops = [], defaultdict(float), defaultdict(float)
    gaps = []
    for lines in dev.values():
        iv = []
        for a, b, name, st in lines.get(OPS_LINE, []):
            a, b = max(a, w0), min(b, w1)
            if b > a:
                iv.append((a, b))
                label = op_label(name)
                if label is not None:
                    ops[label] += (b - a) / len(dev)
        u = _union(iv)
        busy.append(sum(b - a for a, b in u))
        edges = [w0] + [x for ab in u for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        for a, b, name, _ in lines.get(MODULES_LINE, []):
            a, b = max(a, w0), min(b, w1)
            if b > a:
                modules[module_name(name)] += (b - a) / 1e9 / len(dev)
    if not any(busy):
        return None
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for e in tr["host"] if e[2] != WINDOW_SPAN]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "module_s": dict(modules),
        "device_ops": [[n, s / 1e9] for n, s in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_host_label(host, a, b), (b - a) / 1e9]
                      for a, b in gaps[:TOP]],
    }


def _host_label(host, a: float, b: float) -> str:
    mid = (a + b) / 2
    cover = [e for e in host if e[0] <= mid <= e[1]]
    if not cover:
        return "host: no span"
    return min(cover, key=lambda e: e[1] - e[0])[2][:80]


def dump(path: str, n: int = 8) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(path))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for ln in lines:
            evs = list(ln.events)
            print(f"  LINE {ln.name!r}: {len(evs)} events")
            for ev in evs[:n]:
                print(f"    {ev.name[:100]!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={dict(ev.stats)}"[:400])


if __name__ == "__main__":
    dump(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 8)
