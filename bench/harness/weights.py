"""Random weights from the seed, made on the device in one jitted call, in
the type they are served in (bfloat16; norm scales float32), laid out as
the program's parameter tree for a dense decoder (one stage of stacked
layers).  ``run.py`` checks the layout against the program before serving.

The same call remakes the same values, which is how the reference gets its
weights: from the seed, not from the program.  Over several chips each leaf
is made in place, where the engine keeps it; partitionable threefry, pinned
here, makes the values bit for bit those of a one-chip make.
"""
from __future__ import annotations

import functools
import math
import zlib

MATRIX_DTYPE = "bfloat16"


def spec_of(model: dict) -> dict:
    """The sizes the harness needs, from a configuration file."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    return dict(
        d=d, ff=model["intermediate_size"], layers=model["num_hidden_layers"],
        heads=h, kv_heads=model["num_key_value_heads"],
        head_dim=model.get("head_dim") or d // h,
        vocab=model["vocab_size"], tied=bool(model["tie_word_embeddings"]),
        qkv_bias=bool(model["qkv_bias"]), qk_norm=bool(model["qk_norm"]),
        rope_theta=float(model["rope_theta"]),
        eps=float(model["rms_norm_eps"]))


def leaf_shapes(s: dict) -> dict:
    """{path: (shape, dtype, kind)}; kind is 'matrix' (std 1/sqrt(fan_in)),
    'bias' or 'scale' (1 + noise)."""
    n, d, ff, hd = s["layers"], s["d"], s["ff"], s["head_dim"]
    qd, kvd = s["heads"] * hd, s["kv_heads"] * hd
    m, f32 = MATRIX_DTYPE, "float32"
    out = {"embed/embedding": ((s["vocab"], d), m, "embed"),
           "final_norm/scale": ((d,), f32, "scale"),
           "norm/scale": ((n, d), f32, "scale"),
           "attn/q": ((n, d, qd), m, "matrix"),
           "attn/k": ((n, d, kvd), m, "matrix"),
           "attn/v": ((n, d, kvd), m, "matrix"),
           "attn/o": ((n, qd, d), m, "matrix"),
           "mlp_norm/scale": ((n, d), f32, "scale"),
           "mlp/gate": ((n, d, ff), m, "matrix"),
           "mlp/up": ((n, d, ff), m, "matrix"),
           "mlp/down": ((n, ff, d), m, "matrix")}
    if not s["tied"]:
        out["embed/lm_head"] = ((d, s["vocab"]), m, "matrix")
    if s["qkv_bias"]:
        out.update({"attn/q_b": ((n, qd), m, "bias"),
                    "attn/k_b": ((n, kvd), m, "bias"),
                    "attn/v_b": ((n, kvd), m, "bias")})
    if s["qk_norm"]:
        out.update({"attn/q_norm": ((n, hd), f32, "scale"),
                    "attn/k_norm": ((n, hd), f32, "scale")})
    return out


def _leaf(key, shape, dtype, kind):
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    if kind == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, dt)
    if kind == "bias":
        return (0.2 * jax.random.normal(key, shape, dt)).astype(dt)
    fan_in = shape[-1] if kind == "embed" else shape[-2]
    std = jnp.asarray(1.0 / math.sqrt(fan_in), dt)
    return jax.random.normal(key, shape, dt) * std


def _nest(flat: dict) -> dict:
    """Flat {path: leaf} -> the program's tree."""
    params = {"embed": {}, "final_norm": {}, "stages": []}
    block: dict = {}
    for path, x in flat.items():
        top, name = path.split("/")
        if top in ("embed", "final_norm"):
            params[top][name] = x
        else:
            block.setdefault(top, {})[name] = x
    params["stages"].append({"blocks": (block,)})
    return params


@functools.lru_cache(maxsize=None)
def _maker(items: tuple, placement):
    import jax

    def make(lo, hi):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(0), lo), hi)
        return _nest({
            path: _leaf(jax.random.fold_in(
                key, zlib.crc32(path.encode()) & 0x7FFFFFFF), shape, dtype,
                kind)
            for path, (shape, dtype, kind) in items})
    if placement is None:
        return jax.jit(make)
    return jax.jit(make, out_shardings=jax.tree.unflatten(*placement))


def make_params(s: dict, seed: int, placement=None):
    """The weights of ``seed``: on the default device, or each leaf where
    ``placement``, a tree of shardings in the program's layout, puts it."""
    import jax
    import numpy as np
    items = tuple(sorted(leaf_shapes(s).items()))
    if placement is not None:
        leaves, tree = jax.tree.flatten(placement)
        placement = (tree, tuple(leaves))
    with jax.threefry_partitionable(True):
        return _maker(items, placement)(
            np.uint32(seed & 0xFFFFFFFF),
            np.uint32((seed >> 32) & 0xFFFFFFFF))


def param_bytes(s: dict, placement=None) -> int:
    """Bytes of every leaf as served; with ``placement``, those that each
    chip holds."""
    import jax
    import numpy as np
    leaves = leaf_shapes(s)
    order = jax.tree.leaves(_nest({p: p for p in leaves}))
    shards = dict(zip(order, jax.tree.leaves(placement))) \
        if placement is not None else {}
    return sum(int(np.prod(shards[p].shard_shape(shape) if p in shards
                           else shape)) * (2 if dt == MATRIX_DTYPE else 4)
               for p, (shape, dt, _) in leaves.items())
