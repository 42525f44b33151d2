"""Paged flash-decode kernel (Pallas TPU): one query token per row against the
shared paged K/V pool, read through the row's page table.

This is the TPU form of the serving engine's paged decode attention; the
XLA path (``models.kvcache.paged_cache_kv_arrays`` + ``attention``) gathers
every row's chain out to the context bucket as a dense (B, n_pages*ps, KH,
hd) array and repeats it to the query heads.  The kernel reads each row's
live pages straight from the pool and nothing else.

Design:
* grid (B,): one grid step per row.  Inside it a loop walks the row's live
  blocks of ``block_pages`` pages — those holding positions
  ``max(0, pos - window + 1) .. pos`` — with a trip count read from the
  row's length, so pages between the row's length and the bucket cost no
  DMA and no compute.  Rows the caller marks inactive walk at most one page.
* pool layout: the serving layout (P, ps, KH, hd), read as it lies — the
  caller may pass several layers' pools stacked as (L*P, ps, KH, hd) with
  the table offset by ``layer * P``.  One page is contiguous, and the
  kernel's VMEM page buffer has the same shape and tiling as the pool, so
  each page is one straight async copy (pool in HBM, ``pl.ANY``); a KV
  head is read out of the buffer as ``[:, h, :]``.  No transpose, no
  kernel-native copy of the pool.  Each block's pages are copied while the
  previous block computes (two buffers).
* page indirection: the page table (flattened) and each row's position and
  page range ride in as scalar-prefetch operands.
* GQA: the G = Hq/KH query heads of one KV head form one (G_pad, hd) tile,
  G padded to the sublane tile, so each page is read once and no K/V is
  repeated.
* numerics: pool reads are upcast to float32 in VMEM; scores, softcap,
  online softmax and the value sum are float32, the two contractions at
  full float32 precision; masking is by position
  (``kpos <= pos`` and, with a window, ``kpos > pos - window``); a row with
  no valid key gives 0, as ``models.attention`` does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# pages per block: at most 32 x 16 tokens = 512 positions per loop step,
# fewer where the two K and V page buffers would pass BUFFER_BYTES of VMEM
BLOCK_PAGES = 32
BUFFER_BYTES = 4 << 20


def _dot(a, b, dims):
    """float32 contraction at full float32 precision (Mosaic's default
    rounds float32 operands to bfloat16)."""
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(lo_ref, hi_ref, pos_ref, pt_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, *, page_size: int, block_pages: int,
            n_pages: int, kv_heads: int, head_dim: int, scale: float,
            window: int, softcap: float):
    # lax primitives throughout: jnp's jitted helpers (where, //, %) would
    # each lower as a nested function in every program that holds the kernel
    lax = jax.lax
    b = pl.program_id(0)
    lo, hi, pos = lo_ref[b], hi_ref[b], pos_ref[b]
    n_blocks = lax.div(hi - lo + block_pages - 1, block_pages)
    G, T, hd = q_ref.shape[2], block_pages * page_size, head_dim

    def page_copies(i, slot, fn):
        """Each page of block ``i`` (none past the row's last page, none at
        all past its last block) as one async copy per tensor."""
        first = lo + i * block_pages

        def page(j, _):
            src = pt_ref[b * n_pages + first + j]
            dst = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            fn(pltpu.make_async_copy(k_hbm.at[src], kbuf.at[slot, dst],
                                     sems.at[0, slot]))
            fn(pltpu.make_async_copy(v_hbm.at[src], vbuf.at[slot, dst],
                                     sems.at[1, slot]))

        lax.fori_loop(0, lax.min(hi - first, jnp.int32(block_pages)), page,
                      None)

    page_copies(0, 0, lambda c: c.start())
    neg = lax.full((G, T), NEG_INF, jnp.float32)
    zero_s = lax.full((G, T), 0.0, jnp.float32)
    zero_v = lax.full((T, hd), 0.0, jnp.float32)

    def body(i, carry):
        slot = lax.rem(i, 2)
        page_copies(i + 1, 1 - slot, lambda c: c.start())
        page_copies(i, slot, lambda c: c.wait())
        base = (lo + i * block_pages) * page_size
        kpos = base + lax.broadcasted_iota(jnp.int32, (G, T), 1)
        valid = kpos <= pos
        if window:
            valid = valid & (kpos > pos - window)
        # pages past the row's last one hold whatever the buffer held
        v_ok = base + lax.broadcasted_iota(jnp.int32, (T, hd), 0) <= pos
        out = []
        for h, (m, l, acc) in enumerate(carry):
            k = kbuf[slot, :, h, :].astype(jnp.float32)          # (T, hd)
            v = lax.select(v_ok, vbuf[slot, :, h, :].astype(jnp.float32),
                           zero_v)
            q = q_ref[0, h].astype(jnp.float32)                   # (G, hd)
            s = _dot(q, k, (((1,), (1,)), ((), ()))) * scale
            if softcap:
                s = lax.tanh(s / softcap) * softcap
            s = lax.select(valid, s, neg)                         # (G, T)
            m_new = lax.max(m, s.max(axis=1, keepdims=True))
            p = lax.select(valid, lax.exp(s - m_new), zero_s)
            corr = lax.exp(m - m_new)
            out.append((m_new, l * corr + p.sum(axis=1, keepdims=True),
                        acc * corr + _dot(p, v, (((1,), (0,)), ((), ())))))
        return tuple(out)

    init = tuple((lax.full((G, 1), NEG_INF, jnp.float32),
                  lax.full((G, 1), 0.0, jnp.float32),
                  lax.full((G, hd), 0.0, jnp.float32))
                 for _ in range(kv_heads))
    carry = lax.fori_loop(0, n_blocks, body, init)
    for h, (_, l, acc) in enumerate(carry):
        # a row with no valid key has l == 0 and acc == 0: output 0
        safe = lax.select(l > 0.0, l, lax.full(l.shape, 1.0, jnp.float32))
        o_ref[0, h] = (acc / safe).astype(o_ref.dtype)


# jitted so that the serving programs that hold the kernel (one per context
# bucket and block length) share one trace of it per shape
@functools.partial(jax.jit, static_argnames=("window", "softcap", "scale",
                                             "block_pages", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, page_table, q_pos, active=None,
                           *, window: int = 0, softcap: float = 0.0,
                           scale: float = None, block_pages: int = BLOCK_PAGES,
                           interpret: bool = False):
    """q (B, Hq, hd); k_pool/v_pool (P, ps, KH, hd) in the serving layout;
    page_table (B, n_pages) int32 physical page ids, sliced by the caller to
    the context bucket; q_pos (B,) int32, the position just written (keys
    ``0..q_pos`` are attended); active (B,) bool or None — inactive rows walk
    at most one page and their output is not meaningful.  Returns
    (B, Hq, hd) in q's dtype."""
    B, Hq, hd = q.shape
    ps, KH = k_pool.shape[1:3]
    n = page_table.shape[1]
    assert Hq % KH == 0 and k_pool.shape[3] == hd
    G = Hq // KH
    sub = 8 * 4 // jnp.dtype(q.dtype).itemsize       # sublane tile of q
    G_pad = -(-G // sub) * sub
    page_bytes = ps * KH * hd * jnp.dtype(k_pool.dtype).itemsize
    bp = max(1, min(block_pages, n, BUFFER_BYTES // (4 * page_bytes)))
    scale = hd ** -0.5 if scale is None else scale

    pos = jnp.asarray(q_pos, jnp.int32)
    hi = jnp.minimum(pos // ps + 1, n)
    lo = (jnp.maximum(pos - window + 1, 0) // ps if window
          else jnp.zeros_like(pos))
    if active is not None:
        hi = jnp.where(active, hi, jnp.minimum(hi, lo + 1))
    lo = jnp.minimum(lo, hi)

    qg = q.reshape(B, KH, G, hd)
    if G_pad != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, G_pad - G), (0, 0)))

    kernel = functools.partial(
        _kernel, page_size=ps, block_pages=bp, n_pages=n, kv_heads=KH,
        head_dim=hd, scale=scale, window=window, softcap=softcap)
    row = lambda b, *_: (b, 0, 0, 0)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,               # lo, hi, pos, page table
            grid=(B,),
            in_specs=[pl.BlockSpec((1, KH, G_pad, hd), row),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, KH, G_pad, hd), row),
            scratch_shapes=[pltpu.VMEM((2, bp * ps, KH, hd), k_pool.dtype),
                            pltpu.VMEM((2, bp * ps, KH, hd), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KH, G_pad, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(lo, hi, pos, page_table.reshape(-1), qg, k_pool, v_pool)
    return out[:, :, :G].reshape(B, Hq, hd)


def paged_decode_attention_ref(q, k_pool, v_pool, page_table, q_pos, *,
                               window: int = 0, softcap: float = 0.0,
                               scale: float = None):
    """Pure-jnp oracle: gather the page chains dense, then mask + softmax with
    the same semantics (linear positions, scratch-page tails masked)."""
    B, Hq, hd = q.shape
    ps, KH = k_pool.shape[1], k_pool.shape[2]
    n = page_table.shape[1]
    scale = hd ** -0.5 if scale is None else scale
    k = k_pool[page_table].reshape(B, n * ps, KH, hd).astype(jnp.float32)
    v = v_pool[page_table].reshape(B, n * ps, KH, hd).astype(jnp.float32)
    if Hq != KH:
        rep = Hq // KH
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    kpos = jnp.arange(n * ps, dtype=jnp.int32)
    valid = kpos[None, :] <= q_pos[:, None]
    if window:
        valid &= kpos[None, :] > (q_pos[:, None] - window)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), k,
                   precision="highest") * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(valid, axis=1)[:, None, None], p, 0.0)
    return jnp.einsum("bhs,bshd->bhd", p, v,
                      precision="highest").astype(q.dtype)
