"""Decode-time state: full and ring-buffer KV caches, SSM and RG-LRU states.

Caches are plain pytrees so they flow through jit / scan / shard_map.  All
buffers have static shapes; the current stream position is passed separately
as a traced scalar.  Ring buffers store entries at ``slot = position % W`` and
reconstruct absolute positions arithmetically for masking + RoPE.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .config import ModelConfig, FULL_ATTN, LOCAL_ATTN, SSM, RGLRU


def attn_buffer_len(cfg: ModelConfig, kind: str, max_len: int, long_context: bool) -> int:
    if kind == LOCAL_ATTN and cfg.window:
        return min(cfg.window, max_len)
    if long_context and kind == FULL_ATTN and not cfg.is_subquadratic:
        # beyond-paper: windowed long-context decode for full-attention archs
        return min(cfg.long_context_window, max_len)
    return max_len


def init_attn_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                    long_context: bool = False, dtype=jnp.bfloat16) -> Dict:
    S = attn_buffer_len(cfg, kind, max_len, long_context)
    shape = (batch, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.kv_quant:
        sshape = (batch, S, cfg.num_kv_heads, 1)
        return {"k": jnp.zeros(shape, jnp.int8), "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(sshape, jnp.float32),
                "v_s": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16) -> Dict:
    nh, hd, st = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    conv_ch = cfg.ssm_inner + 2 * cfg.ssm_state
    return {
        "state": jnp.zeros((batch, nh, hd, st), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_ch), dtype),
    }


def init_rglru_cache(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16) -> Dict:
    w = cfg.lru_width or cfg.d_model
    return {
        "h": jnp.zeros((batch, w), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, w), dtype),
    }


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     long_context: bool = False, dtype=jnp.bfloat16) -> Dict:
    if kind in (FULL_ATTN, LOCAL_ATTN):
        return init_attn_cache(cfg, kind, batch, max_len, long_context, dtype)
    if kind == SSM:
        return init_ssm_cache(cfg, batch, dtype)
    if kind == RGLRU:
        return init_rglru_cache(cfg, batch, dtype)
    raise ValueError(kind)


def ring_slot_positions(buf_len: int, pos):
    """Absolute position stored in each slot of a ring buffer of length
    ``buf_len`` when the *next* token to be written has position ``pos``
    (i.e. entries written so far are positions 0..pos-1, the last ``buf_len``
    of them resident).  Unfilled slots get negative values (masked).

    ``pos`` may be a scalar (one shared stream position, returns (buf_len,))
    or a (B,) vector of per-slot stream positions (returns (B, buf_len)).
    """
    j = jnp.arange(buf_len, dtype=jnp.int32)
    last = jnp.asarray(pos, jnp.int32)[..., None] - 1   # (..., 1)
    p = last - ((last - j) % buf_len)
    p = jnp.where(p < 0, -1, p).astype(jnp.int32)
    return p if p.ndim > 1 else p.reshape(buf_len)


def quantize_kv(x):
    """(..., hd) -> int8 values + f32 scale on the trailing dim."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_kv(q, scale, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def cache_write_decode(cache: Dict, k_new, v_new, pos):
    """Write one token (B,1,KH,hd) at position ``pos``.

    ``pos`` is either a traced scalar (all rows share one stream position —
    the lockstep path) or a (B,) int32 vector of per-slot positions (the
    slot-native serving path: each row writes at its own ring slot).
    """
    buf_len = cache["k"].shape[1]
    pos = jnp.asarray(pos)
    if "k_s" in cache:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        qcache = {"k": cache["k"], "v": cache["v"]}
        scache = {"k": cache["k_s"], "v": cache["v_s"]}
        out = cache_write_decode(qcache, kq, vq, pos)
        sc = cache_write_decode(scache, ks, vs, pos)
        return {"k": out["k"], "v": out["v"], "k_s": sc["k"], "v_s": sc["v"]}
    if pos.ndim == 1:
        B = k_new.shape[0]
        slots = jnp.mod(pos, buf_len)
        k = cache["k"].at[jnp.arange(B), slots].set(
            k_new[:, 0].astype(cache["k"].dtype))
        v = cache["v"].at[jnp.arange(B), slots].set(
            v_new[:, 0].astype(cache["v"].dtype))
        return {"k": k, "v": v}
    slot = jnp.mod(pos, buf_len)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                     (0, slot, 0, 0))
    return {"k": k, "v": v}


def cache_kv_arrays(cache: Dict, dtype=jnp.bfloat16):
    """Return dequantized (k, v) ready for attention."""
    if "k_s" in cache:
        return (dequantize_kv(cache["k"], cache["k_s"], dtype),
                dequantize_kv(cache["v"], cache["v_s"], dtype))
    return cache["k"].astype(dtype), cache["v"].astype(dtype)


def cache_write_prefill(cache: Dict, k_seq, v_seq):
    """Write a prefill sequence (B,S,KH,hd) into a fresh buffer.

    If S > buf_len (windowed cache shorter than the prompt), only the last
    buf_len entries are retained, placed at their ring slots.
    """
    if "k_s" in cache:
        kq, ks = quantize_kv(k_seq)
        vq, vs = quantize_kv(v_seq)
        out = cache_write_prefill({"k": cache["k"], "v": cache["v"]}, kq, vq)
        scales = cache_write_prefill({"k": cache["k_s"], "v": cache["v_s"]}, ks, vs)
        return {"k": out["k"], "v": out["v"],
                "k_s": scales["k"], "v_s": scales["v"]}
    B, S = k_seq.shape[:2]
    buf_len = cache["k"].shape[1]
    if S <= buf_len:
        k = jax.lax.dynamic_update_slice(cache["k"], k_seq.astype(cache["k"].dtype),
                                         (0, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_seq.astype(cache["v"].dtype),
                                         (0, 0, 0, 0))
        return {"k": k, "v": v}
    tail_pos = jnp.arange(S - buf_len, S)
    slots = jnp.mod(tail_pos, buf_len)
    k = cache["k"].at[:, slots].set(k_seq[:, S - buf_len:].astype(cache["k"].dtype))
    v = cache["v"].at[:, slots].set(v_seq[:, S - buf_len:].astype(cache["v"].dtype))
    return {"k": k, "v": v}


def cache_write_prefill_slot(cache: Dict, k_seq, v_seq, slot):
    """Write a (bucket-padded) prefill sequence into ONE row of a batch cache.

    ``cache`` leaves are batch-shaped (B, buf_len, KH, hd); ``k_seq``/``v_seq``
    are (1, S_pad, KH, hd); ``slot`` is a traced row index.  Requires
    S_pad <= buf_len (the serving engine guards buckets against the smallest
    attention buffer and falls back to the reference path otherwise).  Pad
    positions >= the true prompt length hold garbage K/V: they are masked by
    the ring-position arithmetic until the decode loop overwrites each one at
    exactly its position, so they are never read.
    """
    if "k_s" in cache:
        kq, ks = quantize_kv(k_seq)
        vq, vs = quantize_kv(v_seq)
        out = cache_write_prefill_slot({"k": cache["k"], "v": cache["v"]},
                                       kq, vq, slot)
        sc = cache_write_prefill_slot({"k": cache["k_s"], "v": cache["v_s"]},
                                      ks, vs, slot)
        return {"k": out["k"], "v": out["v"], "k_s": sc["k"], "v_s": sc["v"]}
    S = k_seq.shape[1]
    buf_len = cache["k"].shape[1]
    assert S <= buf_len, (
        f"slot prefill bucket {S} exceeds cache buffer {buf_len}")
    k = jax.lax.dynamic_update_slice(cache["k"], k_seq.astype(cache["k"].dtype),
                                     (slot, 0, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_seq.astype(cache["v"].dtype),
                                     (slot, 0, 0, 0))
    return {"k": k, "v": v}


def cache_write_chunk_slot(cache: Dict, k_seq, v_seq, slot, start, length):
    """Write one prompt *chunk* (1, S_pad, KH, hd) into row ``slot`` of a
    batch cache at ring slots for absolute positions ``start..start+length-1``.

    Unlike ``cache_write_prefill_slot`` (which always writes from slot 0 and
    relies on position masking to hide pad garbage), chunk writes land at ring
    slots that may wrap onto *valid earlier context*, so pad positions
    ``>= length`` must not be written at all: their scatter indices are pushed
    out of range and dropped (``mode="drop"``).
    """
    if "k_s" in cache:
        kq, ks = quantize_kv(k_seq)
        vq, vs = quantize_kv(v_seq)
        out = cache_write_chunk_slot({"k": cache["k"], "v": cache["v"]},
                                     kq, vq, slot, start, length)
        sc = cache_write_chunk_slot({"k": cache["k_s"], "v": cache["v_s"]},
                                    ks, vs, slot, start, length)
        return {"k": out["k"], "v": out["v"], "k_s": sc["k"], "v_s": sc["v"]}
    S = k_seq.shape[1]
    buf_len = cache["k"].shape[1]
    i = jnp.arange(S, dtype=jnp.int32)
    slots = jnp.mod(jnp.asarray(start, jnp.int32) + i, buf_len)
    slots = jnp.where(i < jnp.asarray(length, jnp.int32), slots, buf_len)
    k = cache["k"].at[slot, slots].set(k_seq[0].astype(cache["k"].dtype),
                                       mode="drop")
    v = cache["v"].at[slot, slots].set(v_seq[0].astype(cache["v"].dtype),
                                       mode="drop")
    return {"k": k, "v": v}


def cache_row_kv_arrays(cache: Dict, slot, dtype=jnp.bfloat16):
    """Dequantized (k, v) of ONE batch row ``slot`` (traced), shape
    (1, buf_len, KH, hd) — the past-context read of the chunked prefill."""
    def row(x):
        return jax.lax.dynamic_slice_in_dim(x, jnp.asarray(slot, jnp.int32),
                                            1, axis=0)
    sub = {kk: row(vv) for kk, vv in cache.items()}
    return cache_kv_arrays(sub, dtype)


# -- paged pool layout (serving engine, EngineConfig.paged=True) ---------------
#
# Full-length attention buffers are replaced by a pool of fixed-size pages
# shared by every stream: leaves are (num_pages, page_size, KH, hd) with NO
# batch dimension (keys "kp"/"vp" so tree ops and the engine's dense-cache
# ctx slicing never confuse the two layouts).  Streams address the pool
# through an int32 page table (B, n_pages) maintained by
# ``serving.pager.PageAllocator``; logical position p of a stream lives at
# pool[page_table[b, p // ps], p % ps].  Pages are linear (no ring wrap):
# chains grow with the context, so absolute position == logical index.


# Capacity axis of a *stacked* cache leaf ((n_rep,) + leaf shape, see
# transformer.init_cache): batch rows for dense/ring/recurrent leaves, the
# page axis for paged pool leaves.  The serving mesh shards exactly this
# axis along 'data' (launch.shardings.serving_cache_specs) — both are
# capacity, neither participates in a cross-row reduction, so sharding it
# is placement only and the bits cannot move.
STACKED_CAPACITY_AXIS = 1


def is_paged(cache: Dict) -> bool:
    return "kp" in cache


def init_paged_attn_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                          dtype=jnp.bfloat16) -> Dict:
    shape = (num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    if cfg.kv_quant:
        sshape = (num_pages, page_size, cfg.num_kv_heads, 1)
        return {"kp": jnp.zeros(shape, jnp.int8),
                "vp": jnp.zeros(shape, jnp.int8),
                "kp_s": jnp.zeros(sshape, jnp.float32),
                "vp_s": jnp.zeros(sshape, jnp.float32)}
    return {"kp": jnp.zeros(shape, dtype), "vp": jnp.zeros(shape, dtype)}


def paged_key_positions(n_tokens: int, next_pos):
    """Positions (B, n_tokens) of a paged context when the *next* token to be
    written has position ``next_pos`` ((B,) vector or scalar).  Pages are
    linear, so slot j holds position j when j < next_pos and is invalid (-1,
    masked) otherwise — unallocated table entries point at the scratch page
    and are masked here by position alone."""
    j = jnp.arange(n_tokens, dtype=jnp.int32)
    p = jnp.asarray(next_pos, jnp.int32)
    valid = j[None, :] < jnp.atleast_1d(p)[:, None]
    return jnp.where(valid, j[None, :], -1)


def _paged_scatter(pool, values, flat_idx):
    """pool (P, ps, ...) scattered at token-flat indices (N,) with OOB drop."""
    P, ps = pool.shape[:2]
    flat = pool.reshape((P * ps,) + pool.shape[2:])
    flat = flat.at[flat_idx].set(values.astype(pool.dtype), mode="drop")
    return flat.reshape(pool.shape)


def paged_cache_write_decode(cache: Dict, k_new, v_new, pos, page_table):
    """Write one token (B,1,KH,hd) per stream at position ``pos`` (B,) via the
    page table (B, n_pages).  Rows whose table points at the scratch page
    (freed slots held in the batch) scribble harmlessly on scratch."""
    if "kp_s" in cache:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        out = paged_cache_write_decode({"kp": cache["kp"], "vp": cache["vp"]},
                                       kq, vq, pos, page_table)
        sc = paged_cache_write_decode({"kp": cache["kp_s"], "vp": cache["vp_s"]},
                                      ks, vs, pos, page_table)
        return {"kp": out["kp"], "vp": out["vp"],
                "kp_s": sc["kp"], "vp_s": sc["vp"]}
    ps = cache["kp"].shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    phys = jnp.take_along_axis(page_table, (pos // ps)[:, None], axis=1)[:, 0]
    flat_idx = phys * ps + pos % ps
    return {"kp": _paged_scatter(cache["kp"], k_new[:, 0], flat_idx),
            "vp": _paged_scatter(cache["vp"], v_new[:, 0], flat_idx)}


def paged_cache_write_chunk(cache: Dict, k_seq, v_seq, page_table_row, start,
                            length):
    """Write one prompt chunk (1, S_pad, KH, hd) at positions
    ``start..start+length-1`` of the stream whose table row (n_pages,) is
    given; pads >= length are dropped (same contract as
    ``cache_write_chunk_slot``)."""
    if "kp_s" in cache:
        kq, ks = quantize_kv(k_seq)
        vq, vs = quantize_kv(v_seq)
        out = paged_cache_write_chunk({"kp": cache["kp"], "vp": cache["vp"]},
                                      kq, vq, page_table_row, start, length)
        sc = paged_cache_write_chunk(
            {"kp": cache["kp_s"], "vp": cache["vp_s"]},
            ks, vs, page_table_row, start, length)
        return {"kp": out["kp"], "vp": out["vp"],
                "kp_s": sc["kp"], "vp_s": sc["vp"]}
    P, ps = cache["kp"].shape[:2]
    S = k_seq.shape[1]
    i = jnp.arange(S, dtype=jnp.int32)
    posi = jnp.asarray(start, jnp.int32) + i
    phys = page_table_row[posi // ps]
    flat_idx = phys * ps + posi % ps
    flat_idx = jnp.where(i < jnp.asarray(length, jnp.int32), flat_idx, P * ps)
    return {"kp": _paged_scatter(cache["kp"], k_seq[0], flat_idx),
            "vp": _paged_scatter(cache["vp"], v_seq[0], flat_idx)}


def paged_cache_kv_arrays(cache: Dict, page_table, dtype=jnp.bfloat16):
    """Gather the pages of ``page_table`` (B, n_pages) into dense, dequantized
    (k, v) of shape (B, n_pages*ps, KH, hd), position == index (linear pages).

    The gather width is set by the *caller-sliced* table (ctx bucketing: the
    engine passes only the pages covering the current context bucket), which
    is what bounds compile count and per-step read volume.
    """
    B, n = page_table.shape
    ps = cache["kp"].shape[1]

    def gather(pool):
        g = pool[page_table]                       # (B, n, ps, KH, hd)
        return g.reshape(B, n * ps, *pool.shape[2:])

    if "kp_s" in cache:
        return (dequantize_kv(gather(cache["kp"]), gather(cache["kp_s"]), dtype),
                dequantize_kv(gather(cache["vp"]), gather(cache["vp_s"]), dtype))
    return gather(cache["kp"]).astype(dtype), gather(cache["vp"]).astype(dtype)


def paged_chain_extract(cache: Dict, chain):
    """Gather one stream's page chain out of stacked paged pools.

    ``cache`` leaves are (n_rep, num_pages, page_size, ...); ``chain`` is the
    stream's physical page ids (host list / array).  Returns a parallel dict
    of (n_rep, len(chain), page_size, ...) arrays — the stream's live K/V and
    nothing else, which is what makes replica-to-replica migration cost
    O(context) instead of O(max_len) (no full-length buffer ever moves).
    """
    idx = jnp.asarray(chain, jnp.int32)
    return {k: v[:, idx] for k, v in cache.items()}


def paged_chain_insert(cache: Dict, pages: Dict, chain):
    """Scatter extracted chain pages (``paged_chain_extract`` output) into the
    physical pages ``chain`` of another (or the same) pool.  The destination
    chain must have the same length and page size; dtypes are cast to the
    destination pool's (migration between equal-dtype pools is bit-exact)."""
    idx = jnp.asarray(chain, jnp.int32)
    return {k: cache[k].at[:, idx].set(pages[k].astype(cache[k].dtype))
            for k in cache}


def paged_page_copy(cache: Dict, src, dst):
    """Copy physical page ``src`` onto ``dst`` in every leaf of a stacked
    paged pool dict (leaves (n_rep, num_pages, page_size, ...)).  The
    copy-on-write step of prefix sharing: the allocator swaps a private page
    into a chain, and this moves the shared page's K/V bits onto it so the
    stream's subsequent in-place writes can't perturb other readers."""
    s = jnp.asarray(src, jnp.int32)
    d = jnp.asarray(dst, jnp.int32)
    return {k: v.at[:, d].set(v[:, s]) for k, v in cache.items()}


def cache_row_extract(cache: Dict, slot: int):
    """Copy one batch row out of a stacked dense cache dict (bounded ring
    buffers and recurrent SSM/RG-LRU states): leaves (n_rep, B, ...) ->
    (n_rep, 1, ...).  Ring content is position-aligned (slot = pos % W), so a
    row transplanted into another engine at the same stream position reads
    identically."""
    return {k: v[:, slot:slot + 1] for k, v in cache.items()}


def cache_row_insert(cache: Dict, row: Dict, slot):
    """Splice an extracted row (``cache_row_extract`` output) into batch row
    ``slot`` (int or traced) of another stacked dense cache dict."""
    return {k: jax.lax.dynamic_update_slice_in_dim(
        cache[k], row[k].astype(cache[k].dtype), slot, axis=1)
        for k in cache}


def state_row_slot(batch_cache, slot):
    """Slice row ``slot`` (traced) out of a batch-shaped recurrent state
    pytree -> leading-dim-1 pytree (chunked prefill resumes from it)."""
    return jax.tree.map(
        lambda x: jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(slot, jnp.int32), 1, axis=0), batch_cache)


def state_write_slot(batch_cache, one_cache, slot):
    """Splice a single-row recurrent state (SSM / RG-LRU pytree, leading dim 1)
    into the batch-shaped state pytree at row ``slot`` (traced)."""
    return jax.tree.map(
        lambda full, one: jax.lax.dynamic_update_slice(
            full, one.astype(full.dtype), (slot,) + (0,) * (one.ndim - 1)),
        batch_cache, one_cache)


def cache_key_positions(cache: Dict, pos, batch: int):
    """Positions (B, buf_len) of cached keys when decoding token ``pos``.

    Handles both the full cache (buf_len >= pos: slot == position) and ring
    buffers uniformly — for a full buffer the ring arithmetic reduces to the
    identity on filled slots.  ``pos`` may be a scalar (shared position) or a
    (B,) vector (slot-native serving: per-row key validity/masking).
    """
    buf_len = cache["k"].shape[1]
    p = ring_slot_positions(buf_len, jnp.asarray(pos) + 1)  # pos already written
    if p.ndim == 2:
        return p
    return jnp.broadcast_to(p[None, :], (batch, buf_len))
