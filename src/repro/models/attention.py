"""GQA attention (XLA reference path) used for training, prefill and decode.

The Pallas kernels in ``repro.kernels`` implement the same math with explicit
VMEM tiling for the TPU target; this module is the shardable XLA path used by
the multi-pod dry-run and the CPU smoke tests.  Long sequences are processed
in query chunks (flash-style streaming over the key dimension is left to the
kernel; chunking bounds the materialized score block).

Positions are *per batch row*: ``q_pos``/``k_pos`` are (B, S) and every mask
(causal, sliding window, ring-buffer validity via negative ``k_pos``) is
evaluated row-wise.  The slot-native serving engine relies on this: a batch
mixes streams at different decode positions, and each row must attend to its
own context — never reduce positions over the batch dimension here.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def _mask(q_pos, k_pos, window: int):
    """(B,Sq),(B,Sk) -> bool (B,Sq,Sk). Causal + optional sliding window.

    Slots with negative k_pos (unfilled ring-buffer entries) are masked out.
    """
    m = k_pos[:, None, :] <= q_pos[:, :, None]
    m &= k_pos[:, None, :] >= 0
    if window:
        m &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
    return m


def _attend_block(q, k, v, q_pos, k_pos, *, window, softcap, scale, skip_blocks=False):
    """q (B,Sq,H,hd), k/v (B,Sk,H,hd) — H already GQA-expanded."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    m = _mask(q_pos, k_pos, window)[:, None]
    s = jnp.where(m, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # rows with every key masked (can happen for padded ring slots) -> zeros
    any_valid = jnp.any(m, axis=-1, keepdims=True)
    p = jnp.where(any_valid, p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def attention(
    q: jax.Array,            # (B, Sq, Hq, hd)
    k: jax.Array,            # (B, Sk, KH, hd)
    v: jax.Array,            # (B, Sk, KH, hd)
    q_pos: jax.Array,        # (B, Sq) int32 absolute positions
    k_pos: jax.Array,        # (B, Sk) int32 absolute positions (-1 = invalid)
    *,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    q_chunk: int = 1024,
    unroll: bool = False,
) -> jax.Array:
    with jax.named_scope("attention"):
        B, Sq, Hq, hd = q.shape
        KH = k.shape[2]
        scale = scale if scale is not None else hd ** -0.5
        if Hq != KH:
            rep = Hq // KH
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)

        if Sq <= q_chunk or Sq % q_chunk != 0:
            return _attend_block(q, k, v, q_pos, k_pos,
                                 window=window, softcap=softcap, scale=scale)

        nc = Sq // q_chunk
        qc = q.reshape(B, nc, q_chunk, Hq, hd).swapaxes(0, 1)        # (nc,B,qc,H,hd)
        pc = q_pos.reshape(B, nc, q_chunk).swapaxes(0, 1)            # (nc,B,qc)

        @jax.checkpoint
        def body(_, xs):
            qi, pi = xs
            o = _attend_block(qi, k, v, pi, k_pos,
                              window=window, softcap=softcap, scale=scale)
            return _, o

        _, out = jax.lax.scan(body, None, (qc, pc), unroll=unroll)
        return out.swapaxes(0, 1).reshape(B, Sq, Hq, hd)
