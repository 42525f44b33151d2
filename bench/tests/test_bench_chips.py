"""A cell that spans several chips, on four virtual CPU devices: weights made
in place over the chips are bit for bit the one-chip weights, and so is the
reference computed from them; trace readings come from the cell's own chips,
and roofline and MFU shares are over the chips' summed peaks; a tiny
configuration serves correctly through the harness on a (1, 4) engine mesh;
and a configuration that cannot fit its chips is refused before any weight
is made.

The cases that need four devices run in a child process (this file, run as
a script), because the device count is fixed when JAX starts.
"""
import copy
import functools
import json
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import cell, devtrace, weights  # noqa: E402

CELL = "qwen2-1.5b.chat"
SEED = 2**33 + 7
SMALL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=512)
# (qkv_bias, qk_norm, tied, layers, head_dim): with 3 layers and head_dim
# 18 no axis of the q/k norm scales divides by four, so they are replicated
VARIANTS = {"qwen2": (True, False, True, 2, 16),
            "qwen3": (False, True, False, 3, 18),
            "plain": (False, False, False, 2, 16)}


def small_conf(variant, mesh=None):
    bias, qk_norm, tied, layers, hd = VARIANTS[variant]
    conf = dict(cell.find_cell(CELL)["config"], **SMALL)
    conf.update(qkv_bias=bias, qk_norm=qk_norm, tie_word_embeddings=tied,
                num_hidden_layers=layers, head_dim=hd,
                engine=dict(conf["engine"], mesh=mesh))
    return conf


def small_spec(variant):
    return weights.spec_of(small_conf(variant))


def small_found(chips, mesh):
    """The chat cell at a small size, as ``test_bench_check`` runs it, on
    ``chips`` chips with that engine mesh."""
    found = cell.find_cell(CELL)
    found["cell"] = dict(found["cell"], chips=chips)
    engine = dict(paged=True, max_batch=4, max_len=128, num_pages=0)
    if mesh is not None:
        engine["mesh"] = mesh
    found["config"] = dict(found["config"], **SMALL, engine=engine)
    mix = copy.deepcopy(found["traffic"])
    mix["prompt"].update(min=8, max=60)
    mix["output"].update(min=4, max=30)
    mix.update(clients=4, pool=32)
    found["traffic"] = mix
    # test_bench_check's limit at this size
    found["limits"] = dict(found["limits"], logit_gap=dict(limit=0.08))
    return found


# -- the child: cases that need four devices ---------------------------------

def _weights_case(variant):
    import jax
    conf = small_conf(variant, [1, 4])
    spec = weights.spec_of(conf)
    cfg = cell.program_config(conf, [], strict=False)
    placement = cell.weight_placement(conf, cfg, spec, 4, {})
    one = weights.make_params(spec, SEED)
    four = weights.make_params(spec, SEED, placement)
    flat1 = dict(jax.tree_util.tree_leaves_with_path(one))
    where = dict(jax.tree_util.tree_leaves_with_path(placement))
    # the engine puts its weights where they already are: no second copy
    moved = jax.device_put(four, placement)
    out = {"leaves": {}, "kept": all(
        a is b for a, b in zip(jax.tree.leaves(four), jax.tree.leaves(moved)))}
    for path, x in jax.tree_util.tree_leaves_with_path(four):
        y = flat1[path]
        out["leaves"][jax.tree_util.keystr(path)] = dict(
            shape=list(x.shape), same=np.asarray(x).tobytes()
            == np.asarray(y).tobytes(),
            devices=len(x.devices()), one_device=len(y.devices()),
            engines=x.sharding == where[path],
            split=any(x.sharding.spec),
            shard_bytes=x.addressable_shards[0].data.nbytes)
    total = weights.param_bytes(spec)
    chip = weights.param_bytes(spec, placement)
    out.update(total=total, chip=chip, refused=[])
    for hbm in (total - 1, chip, chip - 1):
        try:
            cell.weight_placement(conf, cfg, spec, 4, {"hbm_bytes": hbm})
        except SystemExit as e:
            out["refused"].append(str(e))
    from harness import reference
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, spec["vocab"], n).astype(np.int32)
            for n in (40, 23)]
    starts = [30, 10]
    targets = [seqs[0][31:40], seqs[1][11:23]]
    rules = [(0.0, 0, 1.0, 0), (0.8, 50, 0.95, 9)]
    a = reference.read_positions(spec, one, seqs, starts, targets, rules)
    b = reference.read_positions(spec, four, seqs, starts, targets, rules)
    out["reference_same"] = all(np.array_equal(x, y) for x, y in zip(a, b))
    out["reference_gap"] = max(float(np.max(x)) for x in a)
    return out


def _serve_case():
    """A traced run: the profiler's reading goes through the cell's chips
    too (on the CPU it finds no device plane and reads nothing)."""
    return cell.run_cell(CELL, SEED, 2.0, True, time.perf_counter(),
                         require_tpu=False, found=small_found(4, [1, 4]),
                         strict=False)


def _child(argv):
    case = argv[0]
    out = _serve_case() if case == "serve" else _weights_case(case)
    print(json.dumps(out), flush=True)


@functools.lru_cache(maxsize=None)
def _run_child(case):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, os.path.abspath(__file__), case],
                          capture_output=True, text=True, timeout=600,
                          env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1]), proc.stderr


# -- weights and the reference over four chips -------------------------------

@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_weights_over_four_chips_equal_the_one_chip_weights(variant):
    out, _ = _run_child(variant)
    leaves = out["leaves"]
    assert len(leaves) == len(weights.leaf_shapes(small_spec(variant)))
    for name, leaf in leaves.items():
        assert leaf["same"], name
        assert leaf["devices"] == 4 and leaf["one_device"] == 1, name
        assert leaf["engines"], name
    assert out["kept"]
    assert sum(leaf["split"] for leaf in leaves.values()) > len(leaves) / 2
    if variant == "qwen3":
        norms = [k for k in leaves if "q_norm" in k or "k_norm" in k]
        assert norms and not any(leaves[k]["split"] for k in norms)
    assert out["reference_same"]
    assert out["reference_gap"] > 0


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_weights_over_four_chips_are_counted_a_chip_at_a_time(variant):
    out, _ = _run_child(variant)
    assert out["chip"] == sum(leaf["shard_bytes"]
                              for leaf in out["leaves"].values())
    assert out["total"] / 4 <= out["chip"] < out["total"]
    # weights too large for one chip fit four; one byte less a chip does not
    assert out["refused"] == [
        f"qwen2-1.5b: {out['total']} bytes of weights over 4 chips is "
        f"{out['chip']} a chip, more than its {out['chip'] - 1}"]


# -- readings from the cell's own chips --------------------------------------

def _plane(shift=0.0):
    """One chip's lines: a decode program of two ops and a prefill, in a
    window of 1000 ns."""
    ops = [(100, 300, "%fusion.1 = f32[8] fusion(x)"),
           (300, 500, "%fusion.2 = f32[8] fusion(x)"),
           (600, 700, "%fusion.3 = f32[8] fusion(x)")]
    mods = [(100, 500, "jit__paged_decode_block_kernel(7)"),
            (600, 700, "jit__prefill_kernel(9)")]
    ev = lambda evs: [(a + shift, b + shift, n, {}) for a, b, n in evs]
    return {devtrace.OPS_LINE: ev(ops), devtrace.MODULES_LINE: ev(mods)}


def _trace(planes):
    host = [(0.0, 1000.0, devtrace.WINDOW_SPAN, {}),
            (500.0, 600.0, "engine.bookkeep", {})]
    return {"device": planes, "host": host}


def _run(trace, chips):
    """A run whose traced window holds one round: two requests prefilled
    (prompts of 100 and 60 tokens) and ten decode steps of each."""
    spec = small_spec("qwen2")
    req = lambda n: types.SimpleNamespace(prompt=[0] * n)
    tracks = [types.SimpleNamespace(req=req(n), deliveries=[(0, 1.0, 11, 0)])
              for n in (100, 60)]
    window = types.SimpleNamespace(
        start=0.0, end=2.0, seconds=2.0, tracks=tracks,
        rounds=[types.SimpleNamespace(start=0.5, end=1.0, steps=10,
                                      decode_tokens=20, first_tokens=2)])
    peaks = cell.load_json(BENCH, "peaks.json")["TPU v5 lite"]
    return cell.Run(spec, {}, window, 0.5, peaks, chips=chips,
                    trace=devtrace.reduce(trace, list(range(chips))),
                    traced=(0.0, 1.5))


def _read(run, *names):
    return {n: cell.reader(n)(run) for n in names}


SHARES = ("step_mfu", "decode_roofline")
PER_CHIP = ("device_idle_share", "decode_step_ms")


def test_one_chip_cell_reads_its_own_plane_on_a_four_chip_host():
    alone = _trace({0: _plane()})
    host = _trace({0: _plane(), 1: {}, 2: {}, 3: {}})
    assert devtrace.reduce(host, [0]) == devtrace.reduce(alone)
    assert _read(_run(host, 1), *SHARES, *PER_CHIP) == \
        _read(_run(alone, 1), *SHARES, *PER_CHIP)
    # read over every plane, the unused chips would count as idle ones
    assert devtrace.reduce(host)["busy_s"] == pytest.approx(
        devtrace.reduce(alone)["busy_s"] / 4)


def test_four_chip_shares_are_over_the_summed_peaks():
    one = _read(_run(_trace({0: _plane()}), 1), *SHARES, *PER_CHIP)
    four = _read(_run(_trace({i: _plane(i) for i in range(4)}), 4),
                 *SHARES, *PER_CHIP)
    for name in SHARES:
        assert one[name] > 0
        assert four[name] == pytest.approx(one[name] / 4, rel=1e-12), name
    for name in PER_CHIP:
        assert four[name] == pytest.approx(one[name], rel=1e-12), name


def test_scopes_read_the_cell_planes_only():
    from harness import scopes
    alone = _trace({0: _plane()})
    host = _trace({0: _plane(), 1: {}, 2: {}, 3: {}})
    assert scopes.reduce(host, {}, [0]) == scopes.reduce(alone, {})
    assert scopes.reduce(host, {})["idle_s"] > \
        scopes.reduce(alone, {})["idle_s"]


# -- the harness on a (1, 4) engine mesh --------------------------------------

def test_harness_serves_on_a_four_chip_engine_mesh():
    out, err = _run_child("serve")
    assert out["correct"], out["compared"]
    assert out["compared"]["window_compiles"]["value"] == 0
    assert out["compared"]["checked_tokens"]["value"] >= 64
    assert out["device"]["count"] == 4
    assert "the cell's: [0, 1, 2, 3]" in err
    assert "batch_rows_mean" in out["metrics"]


# -- what is refused before any weight is made --------------------------------

@pytest.fixture
def no_weights(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("weights made before the refusal")
    monkeypatch.setattr(weights, "make_params", refuse)


def test_engine_mesh_that_is_not_the_cell_chips_is_refused(no_weights):
    found = small_found(1, [1, 2])
    with pytest.raises(SystemExit, match=r"spans 2 chips, the cell 1"):
        cell.serve(found, 1, 1.0, False, lambda m: None, time.perf_counter(),
                   require_tpu=False, strict=False)


def test_weights_that_overfill_the_chips_are_refused(no_weights,
                                                     monkeypatch):
    found = small_found(1, None)
    spec = weights.spec_of(found["config"])
    hbm = weights.param_bytes(spec) - 1
    monkeypatch.setattr(cell, "peaks_for", lambda *a: {"hbm_bytes": hbm})
    with pytest.raises(SystemExit, match=rf"{hbm + 1} bytes of weights over "
                       rf"1 chips is {hbm + 1} a chip, more than its {hbm}"):
        cell.serve(found, 1, 1.0, False, lambda m: None, time.perf_counter(),
                   require_tpu=False, strict=False)


if __name__ == "__main__":
    _child(sys.argv[1:])
