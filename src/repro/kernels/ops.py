"""Jit'd public wrappers around the Pallas kernels.

Off a TPU the kernels run in interpret mode (the body executes as jnp ops);
on a TPU they lower to Mosaic (the default is decided by the platform).  Layout conventions match the model code: (B, S, H, hd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention as _flash
from .decode_attention import decode_attention as _decode


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "scale", "interpret"))
def flash_attention_bshd(q, k, v, *, causal=True, window=0, softcap=0.0,
                         scale=None, interpret=None):
    """q (B,Sq,Hq,hd); k,v (B,Sk,KH,hd) -> (B,Sq,Hq,hd)."""
    interpret = _default_interpret() if interpret is None else interpret
    out = _flash(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                 causal=causal, window=window, softcap=softcap, scale=scale,
                 interpret=interpret)
    return out.swapaxes(1, 2)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def decode_attention_bshd(q, k, v, k_pos, q_pos, *, window=0, scale=None,
                          interpret=None):
    """q (B,1,Hq,hd); k,v (B,Sk,KH,hd); k_pos (B,Sk); q_pos (B,) ->
    (B,1,Hq,hd)."""
    interpret = _default_interpret() if interpret is None else interpret
    out = _decode(q[:, 0], k.swapaxes(1, 2), v.swapaxes(1, 2), k_pos, q_pos,
                  window=window, scale=scale, interpret=interpret)
    return out[:, None]
