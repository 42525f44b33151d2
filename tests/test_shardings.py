"""Sharding-rule unit tests + a subprocess mini-mesh lowering test.

The subprocess is needed because XLA locks the host device count at first
jax init; the main pytest process must keep seeing 1 CPU device.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_sanitize_spec_drops_nondivisible():
    from repro.launch.shardings import sanitize_spec
    mesh = _FakeMesh({"data": 16, "model": 16})
    assert sanitize_spec(P("model", None), (151936, 64), mesh) == P("model", None)
    assert sanitize_spec(P("model", None), (50280, 64), mesh) == P(None, None)
    assert sanitize_spec(P(("data", "model"), None), (512, 8), mesh) \
        == P(("data", "model"), None)
    assert sanitize_spec(P(("data", "model"), None), (128, 8), mesh) == P(None, None)
    assert sanitize_spec(P(None, "model"), (4, 12), mesh) == P(None, None)


def test_batch_axes_for():
    from repro.launch.shardings import batch_axes_for
    mesh2 = _FakeMesh({"data": 16, "model": 16})
    mesh3 = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert batch_axes_for(mesh2, 256) == ("data",)
    assert batch_axes_for(mesh3, 256) == ("pod", "data")
    assert batch_axes_for(mesh3, 16) == ("data",)
    assert batch_axes_for(mesh3, 1) == ()


@pytest.mark.slow
def test_mini_mesh_lowering_subprocess():
    """Lower train + decode for a reduced arch on an 8-device host mesh."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, dataclasses
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_config
        from repro.launch import shardings as SH
        from repro.launch.specs import InputShape, build_step
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(model=4, data=2)
        from repro.launch.specs import build_train
        failures = []
        # FSDP strategy + int8 KV variants also lower
        try:
            cfg = get_config("gemma2-9b").smoke()
            built = build_train(cfg, InputShape("t", "train", 64, 8), mesh,
                                strategy="fsdp")
            jf = jax.jit(built["fn"], in_shardings=built["in_shardings"],
                         out_shardings=built["out_shardings"],
                         donate_argnums=built["donate_argnums"])
            with mesh:
                jf.lower(*built["args"]).compile()
        except Exception as e:
            failures.append(("gemma2-fsdp", "train", repr(e)[:200]))
        for arch in ("qwen2-1.5b", "mixtral-8x7b", "mamba2-370m"):
            cfg = get_config(arch).smoke()
            if arch == "qwen2-1.5b":
                cfg = cfg.replace(kv_quant=True)
            for shape in (InputShape("t", "train", 64, 8),
                          InputShape("d", "decode", 128, 8)):
                try:
                    built = build_step(cfg, shape, mesh)
                    jf = jax.jit(built["fn"], in_shardings=built["in_shardings"],
                                 out_shardings=built["out_shardings"],
                                 donate_argnums=built["donate_argnums"])
                    with mesh:
                        jf.lower(*built["args"]).compile()
                except Exception as e:
                    failures.append((arch, shape.kind, repr(e)[:200]))
        assert not failures, failures
        print("MINI-MESH-OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=600)
    assert r.returncode == 0 and "MINI-MESH-OK" in r.stdout, r.stderr[-2000:]


# -- serving spec properties (PR 10) ------------------------------------------
#
# Random ModelConfigs x mesh shapes, three invariants:
#   1. serving_param_specs partitions only divisible axes (never a dim an
#      axis set doesn't divide);
#   2. placing params with those specs and gathering back is the identity,
#      bit for bit (storage sharding is pure data movement);
#   3. on a divisible 'model' axis, each MoE expert's weights land on exactly
#      one model shard (the expert axis is the only sharded axis of an
#      expert leaf).
#
# The suite runs twice: hypothesis-driven when the optional dep is present
# (requirements-dev.txt convention), and a fixed-seed sweep that always runs.

def _case(seed: int):
    """Deterministic (cfg, fake-mesh) pair from a seed."""
    import numpy as np
    from repro.models.config import ModelConfig
    rng = np.random.RandomState(seed)
    heads = int(rng.choice([2, 3, 4]))
    kv = heads if heads == 3 else int(rng.choice([1, 2, heads]))
    moe = bool(rng.randint(2))
    kw = dict(name=f"p{seed}", arch_type="moe" if moe else "dense",
              num_layers=2, d_model=int(rng.choice([32, 48, 64])),
              num_heads=heads, num_kv_heads=kv, head_dim=16,
              d_ff=int(rng.choice([96, 128])),
              vocab_size=int(rng.choice([100, 128, 160])),
              dtype="float32", max_seq=256)
    if moe:
        kw.update(num_experts=int(rng.choice([2, 3, 4])),
                  experts_per_token=2)
    cfg = ModelConfig(**kw)
    dp = int(rng.choice([1, 2, 3, 4, 8]))
    tp = int(rng.choice([1, 2, 3, 4]))
    return cfg, _FakeMesh({"data": dp, "model": tp})


def _axis_size(mesh, entry):
    axes = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _check_spec_case(seed: int):
    import jax
    from repro.launch.shardings import serving_param_specs
    from repro.models.moe import is_expert_leaf
    cfg, mesh = _case(seed)
    specs, shapes = serving_param_specs(cfg, mesh)
    tp = mesh.shape["model"]
    expert_ok = cfg.is_moe and tp > 1 and cfg.num_experts % tp == 0

    def check(path, spec, shape):
        dims = shape.shape
        # (1) only divisible axes are ever assigned
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            size = _axis_size(mesh, entry)
            assert dims[i] % size == 0, (seed, path, spec, dims)
        # (3) expert leaves: expert axis on 'model', nothing else sharded —
        # whole experts per shard, each expert on exactly one shard
        if is_expert_leaf(cfg, path, dims):
            entries = list(spec) + [None] * (len(dims) - len(spec))
            if expert_ok:
                assert entries[1] == "model", (seed, path, spec)
                assert all(e is None for i, e in enumerate(entries)
                           if i != 1), (seed, path, spec)

    jax.tree_util.tree_map_with_path(
        check, specs, shapes,
        is_leaf=lambda x: isinstance(x, P))


def test_serving_spec_properties_seeded():
    """Fixed-seed sweep of the spec properties (always runs)."""
    for seed in range(24):
        _check_spec_case(seed)


def test_serving_spec_properties_hypothesis():
    hypothesis = pytest.importorskip(
        "hypothesis", reason="optional test dep (see requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.integers(min_value=0, max_value=50_000))
    def run(seed):
        _check_spec_case(seed)

    run()


@pytest.mark.slow
def test_serving_param_roundtrip_subprocess():
    """(2) device_put with serving specs + gather back == identity, bitwise,
    for dense and MoE params and a paged serving cache tree — on a real
    8-device (2,4) mesh (subprocess: the forced device count must be set
    before jax init)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_serving_mesh
        from repro.launch.shardings import (named, serving_param_specs,
                                            shard_serving_caches)
        from repro.models import init_params, init_cache
        from repro.models.config import ModelConfig
        mesh = make_serving_mesh(2, 4)
        dense = ModelConfig(name="rt-d", arch_type="dense", num_layers=2,
                            d_model=64, num_heads=4, num_kv_heads=4,
                            head_dim=16, d_ff=128, vocab_size=128,
                            dtype="float32", max_seq=256)
        moe = ModelConfig(name="rt-m", arch_type="moe", num_layers=2,
                          d_model=64, num_heads=4, num_kv_heads=2,
                          head_dim=16, d_ff=128, vocab_size=128,
                          num_experts=4, experts_per_token=2,
                          dtype="float32", max_seq=256)
        for cfg in (dense, moe):
            params = init_params(jax.random.PRNGKey(0), cfg)
            host = jax.tree.map(np.asarray, params)
            specs, _ = serving_param_specs(cfg, mesh)
            placed = jax.device_put(params, named(mesh, specs))
            back = jax.tree.map(np.asarray, jax.device_get(placed))
            eq = jax.tree.map(np.array_equal, host, back)
            assert all(jax.tree.leaves(eq)), cfg.name
            caches = init_cache(cfg, 8, 128, dtype=jnp.float32,
                                paged_pool=(32, 16))
            chost = jax.tree.map(np.asarray, caches)
            cback = jax.tree.map(
                np.asarray,
                jax.device_get(shard_serving_caches(caches, mesh)))
            ceq = jax.tree.map(np.array_equal, chost, cback)
            assert all(jax.tree.leaves(ceq)), cfg.name
        print("ROUNDTRIP-OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=600)
    assert r.returncode == 0 and "ROUNDTRIP-OK" in r.stdout, r.stderr[-2000:]
