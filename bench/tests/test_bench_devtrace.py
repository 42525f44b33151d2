"""The trace reducer on a slice of a trace recorded on one TPU v5e
(``bench/testdata``): busy and idle time against an independent count,
per-program device time, and the op table."""
import gzip
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import devtrace  # noqa: E402

DATA = os.path.join(BENCH, "testdata", "v5e_chat_slice.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA) as f:
        raw = json.load(f)
    a0, a1 = raw["window"]
    tr = {"device": {int(d): {ln: [(a, b, n, {}) for a, b, n in evs]
                              for ln, evs in lines.items()}
                     for d, lines in raw["device"].items()},
          "host": [(a, b, n, {}) for a, b, n in raw["host"]]
          + [(a0, a1, devtrace.WINDOW_SPAN, {})]}
    return raw, tr, devtrace.reduce(tr)


def test_busy_time_matches_a_count_on_a_grid(recorded):
    raw, _, red = recorded
    a0, a1 = raw["window"]
    step = 10.0                                   # ns
    grid = np.zeros(int((a1 - a0) / step), bool)
    for a, b, _ in raw["device"]["0"]["XLA Ops"]:
        i, j = max(int((a - a0) / step), 0), min(int((b - a0) / step),
                                                 len(grid))
        grid[i:j] = True
    assert red["window_s"] == pytest.approx(0.077)
    assert red["busy_s"] == pytest.approx(grid.sum() * step / 1e9, rel=1e-3)
    assert 0 < red["busy_s"] < red["window_s"]


def test_idle_gaps_are_the_holes_in_the_busy_time(recorded):
    _, _, red = recorded
    gaps = [s for _, s in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) == 10
    assert sum(gaps) <= red["window_s"] - red["busy_s"] + 1e-9
    # the largest hole is the host waiting on the device's sync flag
    assert red["idle_gaps"][0][0] == "ReadSyncFlag"
    assert gaps[0] == pytest.approx(0.003361145, rel=1e-6)


def test_program_time_from_the_module_line(recorded):
    raw, _, red = recorded
    a0, a1 = raw["window"]
    mods = {}
    for a, b, n in raw["device"]["0"]["XLA Modules"]:
        k = devtrace.module_name(n)
        mods[k] = mods.get(k, 0) + (min(b, a1) - max(a, a0)) / 1e9
    assert red["module_s"] == pytest.approx(mods)
    # one whole prefill of a 4096-bucket prompt, and the head of a decode
    # block the window cuts
    assert red["module_s"]["_prefill_kernel"] == pytest.approx(0.044824522)
    assert 0 < red["module_s"]["_paged_decode_block_kernel"] < 0.02


def test_op_table_leaves_out_control_flow(recorded):
    _, _, red = recorded
    assert len(red["device_ops"]) == devtrace.TOP
    for label, secs in red["device_ops"]:
        assert "while" not in label.split()[0] and "tuple" not in label
        assert secs > 0
    assert devtrace.op_label(
        "%fusion.203 = pred[4861952]{0:T(1024)} fusion(pred[32,151936]") == \
        "fusion.203 fusion pred[4861952]"
    assert devtrace.op_label("%while.4 = (s32[], f32[2]{0}) while(%t)") is None
    assert devtrace.module_name("jit__prefill_kernel(123)") == \
        "_prefill_kernel"


def test_no_window_span_reads_nothing(recorded):
    _, tr, _ = recorded
    bare = dict(tr, host=[e for e in tr["host"]
                          if e[2] != devtrace.WINDOW_SPAN])
    assert devtrace.reduce(bare) is None
