"""The comparison that decides ``correct``, driven through a whole run of
the harness on the CPU at a small size (the look for a chip skipped):
a sound run passes; a run whose tokens are altered where they are
produced, or whose sampler drops part of a request's sampling rule, fails;
and the float8 control in the program's place fails, reading far above the
sound program."""
import copy
import os
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import cell, faults, weights  # noqa: E402

CELL = "qwen2-1.5b.chat"
SMALL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=512)
# the logit_gap limit at this size: sound runs read 0-0.030 on seeds 1-7
# and 2**31 + 23, 2**33 + 5; the float8 control 0.166-0.614 on seeds 1-6
SMALL_LIMIT = 0.08


def small(traffic):
    """The chat cell at a small size, serving ``traffic`` (the open-loop
    code mix drives the other loop of the window)."""
    found = cell.find_cell(CELL)
    found["config"] = dict(found["config"], **SMALL, engine=dict(
        paged=True, max_batch=4, max_len=128, num_pages=0))
    mix = copy.deepcopy(cell.load_json(BENCH, "traffic", traffic + ".json"))
    mix["prompt"].update(min=8, max=60)
    mix["output"].update(min=4, max=30)
    if mix["loop"] == "closed":
        mix.update(clients=4, pool=32)
    else:
        mix["rate_per_s"] = 4.0
    found["traffic"] = mix
    found["limits"] = dict(found["limits"],
                           logit_gap=dict(limit=SMALL_LIMIT))
    return found


def run(traffic, seed, **kw):
    return cell.run_cell(CELL, seed, 2.0, False, time.perf_counter(),
                         require_tpu=False, found=small(traffic),
                         strict=False, **kw)


@pytest.mark.parametrize("traffic", ["chat", "code"])
def test_sound_run_is_correct(traffic):
    out = run(traffic, 2**31 + 5)
    assert out["correct"], out["compared"]
    assert out["compared"]["checked_tokens"]["value"] >= 64
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("program", ["_paged_decode_block_kernel",
                                     "_prefill_kernel"])
def test_token_altered_where_produced_is_not_correct(program):
    fault = {"_paged_decode_block_kernel": "decode_token",
             "_prefill_kernel": "prefill_token"}[program]
    undo = faults.plant(fault, SMALL["vocab_size"])
    try:
        out = run("code", 11)
    finally:
        undo()
    assert not out["correct"]
    gap = out["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", ["top_p", "top_k", "temperature"])
def test_sampler_fault_is_not_correct(fault):
    undo = faults.plant(fault, SMALL["vocab_size"])
    try:
        out = run("chat", 2**31 + 23)
    finally:
        undo()
    assert not out["correct"]
    gap = out["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_float8_control_reads_far_above_the_program():
    kept = {}
    sound, control = [], []
    found = small("chat")
    for seed in (1, 2, 3):
        out = run("chat", seed, after_window=lambda server, window:
                  kept.update(w=window))
        assert out["correct"], out["compared"]
        samples = cell.sample_for_check(kept["w"], seed, 512, 8)
        run_ = cell.Run(weights.spec_of(found["config"]), found["traffic"],
                        kept["w"], 0.0, {})
        info = dict(failed=0, vocab_ok=True, window_compiles=0)
        compared = cell.check(run_, samples, info, seed, found["limits"],
                              quant="fp8")
        assert not cell.verdict(compared), compared
        sound.append(out["compared"]["logit_gap"]["value"])
        control.append(compared["logit_gap"][0])
    assert min(control) >= 3 * max(sound), (sound, control)


def test_compile_inside_the_window_is_not_correct(monkeypatch):
    import jax
    from harness import warmup
    monkeypatch.setattr(warmup, "compile_all", lambda *a, **k: 0)
    jax.clear_caches()
    out = run("chat", 2**31 + 29)
    assert out["compared"]["window_compiles"]["value"] > 0
    assert not out["correct"]
