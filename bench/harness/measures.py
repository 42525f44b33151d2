"""Quantities over a run's records that several metric readers share.

``run`` is ``cell.Run``: the window's tracks and rounds (host clock), the
model spec, the reduced trace and the host times it spans.
"""
from __future__ import annotations

from typing import Iterable, List, Optional

import numpy as np


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    v = list(values)
    return float(np.percentile(v, q)) if v else None


def in_window(run, tr) -> list:
    w = run.window
    return [d for d in tr.deliveries if w.start <= d[1] <= w.end]


def output_tokens(run) -> int:
    return sum(d[2] for tr in run.window.tracks for d in in_window(run, tr))


def tpot_ms(run) -> List[float]:
    """Per request with two or more deliveries in the window: the time from
    its first to its last delivery over the tokens after the first."""
    out = []
    for tr in run.window.tracks:
        ds = in_window(run, tr)
        if len(ds) >= 2:
            after = sum(d[2] for d in ds[1:])
            out.append((ds[-1][1] - ds[0][1]) / after * 1e3)
    return out


def traced_rounds(run) -> list:
    """Indices of the rounds inside the traced window."""
    if run.traced is None:
        return []
    a, b = run.traced
    return [i for i, r in enumerate(run.window.rounds)
            if r.start >= a and r.end <= b]


def traced_work(run):
    """(prompt lengths prefilled, decode keys per decoded token, decode
    steps) in the traced rounds.  A decoded token with index j >= 1 of a
    request with prompt length L was computed attending to L + j keys."""
    idx = set(traced_rounds(run))
    prompts, keys = [], []
    for tr in run.window.tracks:
        L = len(tr.req.prompt)
        for r, _, n, had in tr.deliveries:
            if r not in idx:
                continue
            if had == 0:
                prompts.append(L)
            keys.extend(L + j for j in range(max(had, 1), had + n))
    steps = sum(run.window.rounds[i].steps for i in idx)
    return prompts, keys, steps


def module_seconds(run, *names) -> Optional[float]:
    if run.trace is None:
        return None
    s = sum(run.trace["module_s"].get(n, 0.0) for n in names)
    return s if s > 0 else None
