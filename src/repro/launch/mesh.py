"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benchmarks see the single real CPU device.
"""
from __future__ import annotations

import jax


def _mk_mesh(shape, axes, devices):
    """``jax.make_mesh`` with every axis in Auto (GSPMD) partitioning."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (v5e pod) or 2x16x16 = 512 chips (2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)}; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return _mk_mesh(shape, axes, devices)


def make_debug_mesh(model: int = 1, data: int = 1):
    """Small mesh over the locally available devices (tests)."""
    n = model * data
    return _mk_mesh((data, model), ("data", "model"), jax.devices()[:n])


def make_serving_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh for the sharded serving data plane.

    A cluster "replica" becomes a slice of this mesh: per-slot state, the
    page table, and the paged KV pool shard along ``data``; parameters are
    storage-sharded over the flattened axes (``launch.shardings.
    serving_param_specs``) and gathered to replicated at kernel entry, which
    keeps every mesh shape bit-identical to single-device serving (the PR 10
    invariant).  CI forces an 8-device CPU topology via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    n = data * model
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"serving mesh ({data},{model}) needs {n} devices, have "
            f"{len(devices)}; on CPU run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} (set before importing jax)")
    return _mk_mesh((data, model), ("data", "model"), devices[:n])
