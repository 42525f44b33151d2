"""Flash-decode kernel (Pallas TPU): one query token against a (ring-buffer)
KV cache — GreenLLM's decode-phase memory hot spot (the KV reads that make
decode memory-bound and push its energy knee below prefill's).

Design:
* grid (B, KH, n_k_blocks): per kv head, the G = Hq/KH query heads that
  share it are processed together as a (G, hd) tile; online-softmax
  accumulators persist in VMEM scratch across k blocks.
* ring-buffer support: key slot positions arrive as a precomputed int32
  array (B, Sk) (slot -> absolute position, -1 for unfilled); masking is
  `0 <= k_pos <= q_pos` plus an optional sliding window — identical
  semantics to models.kvcache.
* TPU tiling: ``q_pos`` rides in as a scalar-prefetch operand (a (1,) VMEM
  block of a (B,) array is not tileable), and ``k_pos`` is viewed as
  (B, 1, Sk) so its (1, block_k) block spans the full middle dim.
* fp32 accumulation; bf16 cache reads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(qpos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale: float, window: int,
            block_k: int, num_k_blocks: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    kpos = kpos_ref[0]                               # (1, bk)
    qpos = qpos_ref[b]                               # scalar int32 (SMEM)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = jnp.logical_and(kpos >= 0, kpos <= qpos)
    if window:
        valid = jnp.logical_and(valid, kpos > qpos - window)
    s = jnp.where(valid, s, NEG_INF)                 # (G, bk)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0, ...] = (acc_ref[...] / safe).astype(o_ref.dtype)


def decode_attention(q, k, v, k_pos, q_pos, *, window: int = 0,
                     scale: float = None, block_k: int = 256,
                     interpret: bool = False):
    """q (B,Hq,hd); k,v (B,KH,Sk,hd); k_pos (B,Sk) int32 slot positions
    (-1 = unfilled); q_pos (B,) int32. Returns (B,Hq,hd)."""
    B, Hq, hd = q.shape
    KH, Sk = k.shape[1], k.shape[2]
    assert Hq % KH == 0
    G = Hq // KH
    scale = hd ** -0.5 if scale is None else scale
    block_k = min(block_k, Sk)
    assert Sk % block_k == 0
    nk = Sk // block_k
    qg = q.reshape(B, KH, G, hd)

    kernel = functools.partial(_kernel, scale=scale, window=window,
                               block_k=block_k, num_k_blocks=nk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                       # q_pos
        grid=(B, KH, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j, qp: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, j, qp: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, j, qp: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, j, qp: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, j, qp: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, hd), q.dtype),
        interpret=interpret,
    )(q_pos, qg, k, v, k_pos.reshape(B, 1, Sk))
    return out.reshape(B, Hq, hd)
