"""Composable decoder model covering every assigned architecture family.

Layers are grouped into *stages*: the config's block pattern (e.g. gemma2's
(local, full) or recurrentgemma's (rglru, rglru, local)) is stacked over its
repeat count and executed with ``jax.lax.scan`` — bounded HLO size for the
80-combination multi-pod dry-run — plus an unrolled tail when depth % pattern
!= 0.  Three entry points: ``forward_train`` (full causal sequence),
``prefill`` (sequence -> last logits + caches), ``decode_step`` (one token
against the caches).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .config import ModelConfig, FULL_ATTN, LOCAL_ATTN, SSM, RGLRU
from . import layers as L
from . import kvcache as KV
from .attention import attention
from .moe import init_moe, apply_moe
from .ssm import init_ssm, ssm_forward, ssm_decode_step
from .rglru import init_rglru, rglru_forward, rglru_decode_step
from repro.kernels.paged_decode_attention import paged_decode_attention


# -- sharding context ------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Activation-sharding helper. ``None`` mesh -> no-op (CPU smoke tests)."""
    mesh: Any = None
    batch_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    # decode-time KV cache is sequence-sharded over the model axis (set when
    # kv_heads doesn't divide the model axis): attention then keeps q
    # replicated over heads and lets GSPMD do flash-decode-style partial
    # softmax reductions instead of all-gathering the cache.
    kv_seq_sharded: bool = False

    def spec(self, *dims) -> P:
        ax = []
        for d in dims:
            if d == "b":
                ax.append(self.batch_axes if self.batch_axes else None)
            elif d == "m":
                # model_axis=None => FSDP-style: activations are not
                # tensor-parallel; 'm' constraints dissolve
                ax.append(self.model_axis)
            else:
                ax.append(None)
        return P(*ax)

    def _axis_size(self, entry) -> int:
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n

    def cs(self, x, *dims):
        if self.mesh is None:
            return x
        spec = self.spec(*dims)
        # drop axes that don't divide the corresponding dim (e.g. 12 heads
        # on a 16-way model axis, vocab 50280 on 16 shards)
        entries = []
        for i, e in enumerate(spec):
            if e is None or i >= x.ndim or (
                    x.shape[i] % self._axis_size(e) != 0) or x.shape[i] == 0:
                entries.append(None)
            else:
                entries.append(e)
        if all(e is None for e in entries):
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*entries)))


NOSHARD = ShardCtx()


# -- stage decomposition ------------------------------------------------------------

def stages_of(cfg: ModelConfig) -> List[Tuple[Tuple[str, ...], int]]:
    out = []
    if cfg.n_pattern_repeats:
        out.append((cfg.block_pattern, cfg.n_pattern_repeats))
    if cfg.tail_kinds:
        out.append((cfg.tail_kinds, 1))
    return out


# -- init ---------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 6)
    p = {
        "q": L.dense_init(ks[0], (cfg.d_model, cfg.q_dim), dtype=dtype),
        "k": L.dense_init(ks[1], (cfg.d_model, cfg.kv_dim), dtype=dtype),
        "v": L.dense_init(ks[2], (cfg.d_model, cfg.kv_dim), dtype=dtype),
        "o": L.dense_init(ks[3], (cfg.q_dim, cfg.d_model), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["q_b"] = jnp.zeros((cfg.q_dim,), dtype)
        p["k_b"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["v_b"] = jnp.zeros((cfg.kv_dim,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
    return p


def _init_block(key, cfg: ModelConfig, kind: str, dtype):
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {"norm": L.init_norm(cfg)}
    if kind in (FULL_ATTN, LOCAL_ATTN):
        p["attn"] = _init_attn(ks[0], cfg, dtype)
    elif kind == SSM:
        p["ssm"] = init_ssm(ks[0], cfg, dtype)
    elif kind == RGLRU:
        p["rglru"] = init_rglru(ks[0], cfg, dtype)
    if cfg.post_block_norm:
        p["post_norm"] = L.init_norm(cfg)
    if cfg.d_ff > 0 and kind != SSM:
        p["mlp_norm"] = L.init_norm(cfg)
        if cfg.is_moe:
            p["moe"] = init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg, dtype)
        if cfg.post_block_norm:
            p["post_mlp_norm"] = L.init_norm(cfg)
    return p


def init_params(key, cfg: ModelConfig) -> Dict:
    dtype = jnp.dtype(cfg.dtype)
    k_embed, k_final, *stage_keys = jax.random.split(key, 2 + len(stages_of(cfg)))
    params: Dict[str, Any] = {
        "embed": L.init_embed(k_embed, cfg, dtype),
        "final_norm": L.init_norm(cfg),
        "stages": [],
    }
    for (kinds, n_rep), sk in zip(stages_of(cfg), stage_keys):
        groups = []
        for r, rk in enumerate(jax.random.split(sk, n_rep)):
            bkeys = jax.random.split(rk, len(kinds))
            groups.append({"blocks": tuple(_init_block(bk, cfg, kind, dtype)
                                           for bk, kind in zip(bkeys, kinds))})
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *groups) \
            if n_rep > 1 else jax.tree.map(lambda x: x[None], groups[0])
        params["stages"].append(stacked)
    return params


def param_specs(cfg: ModelConfig, shd: ShardCtx) -> Dict:
    """PartitionSpecs for the param pytree (tensor-parallel over model axis)."""
    m = shd.model_axis
    msize = shd.mesh.shape[m] if shd.mesh is not None else 1

    def attn_spec():
        kv = m if cfg.num_kv_heads * cfg.head_dim % max(msize, 1) == 0 \
            and cfg.num_kv_heads % msize == 0 else None
        s = {"q": P(None, None, m), "k": P(None, None, kv), "v": P(None, None, kv),
             "o": P(None, m, None)}
        if cfg.qkv_bias:
            s.update({"q_b": P(None, m), "k_b": P(None, kv), "v_b": P(None, kv)})
        if cfg.qk_norm:
            s.update({"q_norm": P(None, None), "k_norm": P(None, None)})
        return s

    def mlp_spec():
        s = {"down": P(None, m, None)}
        if cfg.glu:
            s.update({"gate": P(None, None, m), "up": P(None, None, m)})
        else:
            s.update({"up": P(None, None, m), "up_b": P(None, m),
                      "down_b": P(None, None)})
        return s

    def moe_spec():
        e = m if cfg.num_experts % max(msize, 1) == 0 else None
        ffm = None if e == m else m
        s = {"router": P(None, None, None),
             "down": P(None, e, ffm, None)}
        if cfg.glu:
            s.update({"gate": P(None, e, None, ffm), "up": P(None, e, None, ffm)})
        else:
            s.update({"up": P(None, e, None, ffm)})
        return s

    def norm_spec(p):
        return jax.tree.map(lambda _: P(None, None), p)

    def ssm_spec():
        return {"in_proj": P(None, None, m), "conv_w": P(None, None, None),
                "conv_b": P(None, None), "A_log": P(None, None), "D": P(None, None),
                "dt_bias": P(None, None), "norm": P(None, m),
                "out_proj": P(None, m, None)}

    def rglru_spec():
        return {"in_x": P(None, None, m), "in_gate": P(None, None, m),
                "conv_w": P(None, None, m), "conv_b": P(None, m),
                "lam": P(None, m), "rg_w": P(None, m), "ig_w": P(None, m),
                "out": P(None, m, None)}

    def block_spec(kind, bp):
        s: Dict[str, Any] = {"norm": norm_spec(bp["norm"])}
        if kind in (FULL_ATTN, LOCAL_ATTN):
            s["attn"] = attn_spec()
        elif kind == SSM:
            s["ssm"] = ssm_spec()
        elif kind == RGLRU:
            s["rglru"] = rglru_spec()
        if "post_norm" in bp:
            s["post_norm"] = norm_spec(bp["post_norm"])
        if "mlp_norm" in bp:
            s["mlp_norm"] = norm_spec(bp["mlp_norm"])
            if cfg.is_moe:
                s["moe"] = moe_spec()
            else:
                s["mlp"] = mlp_spec()
            if "post_mlp_norm" in bp:
                s["post_mlp_norm"] = norm_spec(bp["post_mlp_norm"])
        return s

    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    embed_s = {"embedding": P(m, None)}
    if "lm_head" in shapes["embed"]:
        embed_s["lm_head"] = P(None, m)
    if "prefix_proj" in shapes["embed"]:
        embed_s["prefix_proj"] = P(None, None)
    specs = {"embed": embed_s, "final_norm": norm_spec(shapes["final_norm"]),
             "stages": []}
    for (kinds, n_rep), sp in zip(stages_of(cfg), shapes["stages"]):
        specs["stages"].append(
            {"blocks": tuple(block_spec(k, b) for k, b in zip(kinds, sp["blocks"]))})
    return specs


# -- block application ----------------------------------------------------------------

def paged_decode_kernel_ok(cfg: ModelConfig, shd: ShardCtx) -> bool:
    """Whether a paged decode step may attend through the Pallas kernel
    (``kernels/paged_decode_attention``) where the program is lowered for a
    TPU.  The kernel reads a single device's bf16/f32 pool whose KV heads
    are lane-aligned; int8 pools (dequantisation) and serving meshes (GSPMD
    placement of the pool's pages) keep the gather path.  The platform half
    of the choice is made inside the program (``jax.lax.platform_dependent``
    in ``_apply_attn``), so a program lowered for the CPU gathers."""
    return shd.mesh is None and not cfg.kv_quant and cfg.head_dim % 128 == 0


def _paged_gather_attention(cfg: ModelConfig, shd: ShardCtx, window: int, q,
                            cache, page_table, pos_v, active):
    """Paged decode attention in XLA: gather the ctx-bucketed page chains as
    a dense (B, n_pages*ps) context and attend with position masks."""
    del active            # inactive rows attend too; the caller drops them
    B = q.shape[0]
    with jax.named_scope("kv_cache"):
        k_att, v_att = KV.paged_cache_kv_arrays(cache, page_table, q.dtype)
        # serving mesh: the pool's page axis is data-sharded while the
        # gathered per-row context is batch-sharded — constrain the
        # gather output so GSPMD routes pages once instead of
        # replicating the pool into every shard's gather (guidance only;
        # rows are independent, so placement cannot change the bits)
        k_att = shd.cs(k_att, "b", None, None, None)
        v_att = shd.cs(v_att, "b", None, None, None)
    k_pos = jnp.broadcast_to(
        KV.paged_key_positions(k_att.shape[1], pos_v + 1),
        (B, k_att.shape[1]))
    return attention(q, k_att, v_att, pos_v[:, None], k_pos, window=window,
                     softcap=cfg.attn_softcap, scale=cfg.attn_scale,
                     unroll=cfg.unroll_scans)


def _paged_kernel_attention(cfg: ModelConfig, window: int, q, cache,
                            page_table, pos_v, active):
    """Paged decode attention through the Pallas kernel: each row's live
    pages are read from the pool in place, no gather and no GQA repeat."""
    with jax.named_scope("attention"):
        out = paged_decode_attention(
            q[:, 0], cache["kp"], cache["vp"], page_table, pos_v, active,
            window=window, softcap=cfg.attn_softcap, scale=cfg.attn_scale)
    return out[:, None]


def _apply_attn(cfg: ModelConfig, p, x, kind, *, mode, positions, cache, pos,
                shd, slot=None, length=None, page_table=None, active=None):
    B, S, _ = x.shape
    q = x @ p["attn"]["q"]
    k = x @ p["attn"]["k"]
    v = x @ p["attn"]["v"]
    if cfg.qkv_bias:
        q, k, v = q + p["attn"]["q_b"], k + p["attn"]["k_b"], v + p["attn"]["v_b"]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if not (mode == "decode" and shd.kv_seq_sharded):
        q = shd.cs(q, "b", None, "m", None)
    if cfg.qk_norm:
        q = L.rmsnorm(q, p["attn"]["q_norm"], cfg.norm_eps)
        k = L.rmsnorm(k, p["attn"]["k_norm"], cfg.norm_eps)
    if cfg.pos_embedding == "rope":
        inv, rot = L.rope_freqs(cfg)
        q = L.apply_rope(q, positions, inv, rot)
        k = L.apply_rope(k, positions, inv, rot)

    window = cfg.window if kind == LOCAL_ATTN else 0
    new_cache = None
    if mode == "decode" and KV.is_paged(cache):
        # paged pool: scatter the new token by page table, then attend to
        # each row's chain (Pallas kernel on TPU, dense gather elsewhere)
        pos_v = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos, jnp.int32)),
                                 (B,))
        with jax.named_scope("kv_cache"):
            new_cache = KV.paged_cache_write_decode(cache, k, v, pos_v,
                                                    page_table)
        args = (q, new_cache, page_table, pos_v, active)
        gather = functools.partial(_paged_gather_attention, cfg, shd, window)
        if paged_decode_kernel_ok(cfg, shd):
            out = jax.lax.platform_dependent(
                *args, default=gather,
                tpu=functools.partial(_paged_kernel_attention, cfg, window))
        else:
            out = gather(*args)
    elif mode == "decode":
        with jax.named_scope("kv_cache"):
            new_cache = KV.cache_write_decode(cache, k, v, pos)
            k_full, v_full = KV.cache_kv_arrays(new_cache, q.dtype)
        k_pos = KV.cache_key_positions(new_cache, pos, B)
        buf_len = k_full.shape[1]
        if window == 0 and buf_len < cfg.max_seq:
            window = buf_len          # long-context ring buffer on full attn
        k_att = k_full
        v_att = v_full
        if shd.kv_seq_sharded and cfg.num_heads != cfg.num_kv_heads:
            # pre-expand GQA and pin the expanded KV to the cache's sequence
            # sharding; otherwise the o-projection's head sharding propagates
            # backwards and XLA all-gathers the whole cache per step.
            rep = cfg.num_heads // cfg.num_kv_heads
            k_att = shd.cs(jnp.repeat(k_att, rep, axis=2), "b", "m", None, None)
            v_att = shd.cs(jnp.repeat(v_att, rep, axis=2), "b", "m", None, None)
        out = attention(q, k_att, v_att,
                        positions, k_pos, window=window,
                        softcap=cfg.attn_softcap, scale=cfg.attn_scale,
                        unroll=cfg.unroll_scans)
        if shd.kv_seq_sharded:
            out = shd.cs(out, "b", None, None, None)
    elif mode == "chunk":
        # chunked prefill: attend to [cached past context | raw current
        # chunk], then write the chunk into the cache for later chunks and
        # decode.  The past is read BEFORE the write so the current chunk
        # contributes raw (unquantized, uncast) K/V, matching one-shot
        # prefill; ``pos`` is the chunk's start position (traced scalar).
        start = jnp.asarray(pos, jnp.int32)
        if KV.is_paged(cache):
            with jax.named_scope("kv_cache"):
                pk, pv = KV.paged_cache_kv_arrays(cache, page_table, q.dtype)
            past_pos = KV.paged_key_positions(pk.shape[1], start)
            with jax.named_scope("kv_cache"):
                new_cache = KV.paged_cache_write_chunk(
                    cache, k, v, page_table[0], start, length)
        else:
            pk, pv = KV.cache_row_kv_arrays(cache, slot, q.dtype)
            past_pos = KV.ring_slot_positions(pk.shape[1], start)[None]
            new_cache = KV.cache_write_chunk_slot(cache, k, v, slot, start,
                                                  length)
            if window == 0 and pk.shape[1] < cfg.max_seq:
                window = pk.shape[1]  # long-context ring: bounded lookback
        i = jnp.arange(S, dtype=jnp.int32)
        cur_pos = jnp.where(i[None, :] < jnp.asarray(length, jnp.int32),
                            positions, -1)
        out = attention(q, jnp.concatenate([pk, k.astype(pk.dtype)], axis=1),
                        jnp.concatenate([pv, v.astype(pv.dtype)], axis=1),
                        positions, jnp.concatenate([past_pos, cur_pos], axis=1),
                        window=window, softcap=cfg.attn_softcap,
                        scale=cfg.attn_scale, unroll=cfg.unroll_scans)
    else:
        if mode == "prefill":
            if slot is not None and KV.is_paged(cache):
                # slot-native one-shot prefill into this stream's page chain
                # (bucket pads >= length are dropped, not masked: their pages
                # may not be allocated)
                new_cache = KV.paged_cache_write_chunk(
                    cache, k, v, page_table[0], jnp.asarray(0, jnp.int32),
                    S if length is None else length)
            elif slot is not None:
                # slot-native: write this prompt's K/V into one row of the
                # batch cache; other rows flow through untouched.
                new_cache = KV.cache_write_prefill_slot(cache, k, v, slot)
            else:
                new_cache = KV.cache_write_prefill(cache, k, v)
            if not KV.is_paged(new_cache):
                buf_len = new_cache["k"].shape[1]
                if window == 0 and buf_len < S:
                    window = buf_len
        out = attention(q, k, v, positions, positions, window=window,
                        softcap=cfg.attn_softcap, scale=cfg.attn_scale,
                        unroll=cfg.unroll_scans)
    if not (mode == "decode" and shd.kv_seq_sharded):
        out = shd.cs(out, "b", None, "m", None)
    out = out.reshape(B, S, cfg.q_dim) @ p["attn"]["o"]
    return out, new_cache


def _freeze_inactive(new_cache, cache, active):
    """Recurrent decode steps advance state for every batch row; rows outside
    the active set (retired, or mid-chunked-prefill) must keep their cached
    state — unlike K/V buffers there is no position masking to hide a bogus
    update, so an unfrozen mid-prefill row would resume its next chunk from
    state polluted by other streams' decode blocks."""
    if active is None:
        return new_cache
    return jax.tree.map(
        lambda n, o: jnp.where(
            active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
        new_cache, cache)


def _apply_block(cfg: ModelConfig, kind: str, p, x, *, mode, positions,
                 cache, pos, shd, slot=None, length=None, valid=None,
                 page_table=None, active=None):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(cfg, p["norm"], x)
    new_cache = None
    if kind in (FULL_ATTN, LOCAL_ATTN):
        mix, new_cache = _apply_attn(cfg, p, h, kind, mode=mode,
                                     positions=positions, cache=cache,
                                     pos=pos, shd=shd, slot=slot,
                                     length=length, page_table=page_table,
                                     active=active)
    elif kind == SSM:
        if mode == "decode":
            mix, new_cache = ssm_decode_step(cfg, p["ssm"], h, cache)
            new_cache = _freeze_inactive(new_cache, cache, active)
        elif mode in ("prefill", "chunk"):
            row = KV.state_row_slot(cache, slot) if mode == "chunk" else None
            mix, new_cache = ssm_forward(cfg, p["ssm"], h, return_state=True,
                                         cache=row, length=length)
            if slot is not None:
                new_cache = KV.state_write_slot(cache, new_cache, slot)
        else:
            mix = ssm_forward(cfg, p["ssm"], h)
    elif kind == RGLRU:
        if mode == "decode":
            mix, new_cache = rglru_decode_step(cfg, p["rglru"], h, cache)
            new_cache = _freeze_inactive(new_cache, cache, active)
        elif mode in ("prefill", "chunk"):
            row = KV.state_row_slot(cache, slot) if mode == "chunk" else None
            mix, new_cache = rglru_forward(cfg, p["rglru"], h,
                                           return_state=True,
                                           cache=row, length=length)
            if slot is not None:
                new_cache = KV.state_write_slot(cache, new_cache, slot)
        else:
            mix = rglru_forward(cfg, p["rglru"], h)
    else:
        raise ValueError(kind)
    if new_cache is not None and cache is not None:
        # match the caller-allocated buffer dtypes (e.g. f32 test caches)
        new_cache = jax.tree.map(lambda n, o: n.astype(o.dtype), new_cache, cache)
    if cfg.post_block_norm:
        mix = L.apply_norm(cfg, p["post_norm"], mix)
    x = x + mix
    x = shd.cs(x, "b", None, None)

    if cfg.d_ff > 0 and kind != SSM:
        h = L.apply_norm(cfg, p["mlp_norm"], x)
        if cfg.is_moe:
            m, a = apply_moe(cfg, p["moe"], h, shd, valid)
            aux = aux + a
        else:
            m = L.apply_mlp(cfg, p["mlp"], h)
        if cfg.post_block_norm:
            m = L.apply_norm(cfg, p["post_mlp_norm"], m)
        x = x + m
        x = shd.cs(x, "b", None, None)
    return x, new_cache, aux


# -- stage execution -------------------------------------------------------------------

def _run_stages(cfg: ModelConfig, params, x, *, mode, positions, caches, pos,
                shd: ShardCtx, remat: bool, slot=None, length=None,
                valid=None, page_table=None, active=None):
    """caches: list (per stage) of stacked per-group caches or None."""
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    for si, ((kinds, n_rep), sp) in enumerate(zip(stages_of(cfg), params["stages"])):
        stage_cache = caches[si] if caches is not None else None

        def group_fn(x, group_p, group_c, pt=page_table):
            auxs = jnp.zeros((), jnp.float32)
            outs = []
            for j, kind in enumerate(kinds):
                c = group_c[j] if group_c is not None else None
                x, nc, a = _apply_block(cfg, kind, group_p["blocks"][j], x,
                                        mode=mode, positions=positions,
                                        cache=c, pos=pos, shd=shd, slot=slot,
                                        length=length, valid=valid,
                                        page_table=pt, active=active)
                auxs = auxs + a
                outs.append(nc)
            return x, tuple(outs), auxs

        if remat:
            group_fn = jax.checkpoint(group_fn)

        paged = tuple(KV.is_paged(c) for c in stage_cache) \
            if stage_cache is not None else ()
        if mode == "decode" and any(paged) and paged_decode_kernel_ok(cfg,
                                                                      shd):
            # the paged pools ride in the scan carry, flattened to (n_rep*P,
            # ps, ...): layer i addresses its pool as pages i*P.. through an
            # offset page table, so the token write lands in place and the
            # attention reads the pool where it lies — no per-layer slice of
            # the pool is copied out and back
            P = next(c["kp"].shape[1] for c, g in zip(stage_cache, paged) if g)
            pools = tuple(
                jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), c)
                if g else None for c, g in zip(stage_cache, paged))
            rest = tuple(None if g else c for c, g in zip(stage_cache, paged))

            def body(carry, xs):
                x, aux, pools = carry
                gp, gc, i = xs
                gc = tuple(pool if g else c
                           for pool, c, g in zip(pools, gc, paged))
                x, ncache, a = group_fn(x, gp, gc, page_table + i * P)
                pools = tuple(n if g else None for n, g in zip(ncache, paged))
                ncache = tuple(None if g else n for n, g in zip(ncache, paged))
                return (x, aux + a, pools), ncache

            (x, aux_total, pools), ys = jax.lax.scan(
                body, (x, aux_total, pools),
                (sp, rest, jnp.arange(n_rep, dtype=jnp.int32)),
                unroll=cfg.unroll_scans)
            new_caches.append(tuple(
                jax.tree.map(lambda a, o: a.reshape(o.shape), pool, c)
                if g else y for pool, y, c, g in zip(pools, ys, stage_cache,
                                                     paged)))
        elif stage_cache is not None:
            def body(carry, xs):
                x, aux = carry
                gp, gc = xs
                x, ncache, a = group_fn(x, gp, gc)
                return (x, aux + a), ncache

            (x, aux_total), ys = jax.lax.scan(body, (x, aux_total),
                                              (sp, stage_cache),
                                              unroll=cfg.unroll_scans)
            new_caches.append(ys)
        else:
            def body(carry, gp):
                x, aux = carry
                x, ncache, a = group_fn(x, gp, None)
                return (x, aux + a), ncache

            (x, aux_total), ys = jax.lax.scan(body, (x, aux_total), sp,
                                              unroll=cfg.unroll_scans)
            new_caches.append(ys if mode == "prefill" else None)
    return x, new_caches, aux_total


# -- embedding helpers -------------------------------------------------------------------

def _embed_inputs(cfg: ModelConfig, params, tokens, prefix_embeds, shd, start_pos=0):
    x = L.embed_tokens(cfg, params["embed"], tokens)
    if prefix_embeds is not None:
        pe = prefix_embeds.astype(x.dtype) @ params["embed"]["prefix_proj"]
        x = jnp.concatenate([pe, x], axis=1)
    B, S, _ = x.shape
    positions = start_pos + jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if cfg.pos_embedding == "sincos":
        x = x + L.sincos_embedding(positions, cfg.d_model).astype(x.dtype)
    x = shd.cs(x, "b", None, None)
    return x, positions


# -- public API -----------------------------------------------------------------------------

def forward_train(params, cfg: ModelConfig, tokens, prefix_embeds=None,
                  shd: ShardCtx = NOSHARD, remat: bool = True):
    """tokens (B,S) -> logits (B,S_total,vocab), aux_loss."""
    x, positions = _embed_inputs(cfg, params, tokens, prefix_embeds, shd)
    x, _, aux = _run_stages(cfg, params, x, mode="train", positions=positions,
                            caches=None, pos=None, shd=shd, remat=remat)
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], x)
    logits = shd.cs(logits, "b", None, "m")
    return logits, aux


def _hidden_train(params, cfg: ModelConfig, tokens, prefix_embeds, shd, remat):
    x, positions = _embed_inputs(cfg, params, tokens, prefix_embeds, shd)
    x, _, aux = _run_stages(cfg, params, x, mode="train", positions=positions,
                            caches=None, pos=None, shd=shd, remat=remat)
    return L.apply_norm(cfg, params["final_norm"], x), aux


def _ce_block(cfg: ModelConfig, params, h, tgt, shd, valid=None):
    """h (B,T,d), tgt (B,T) -> (sum_ce, count). Logits live only per block."""
    logits = L.unembed(cfg, params["embed"], h)
    logits = shd.cs(logits, "b", None, "m")
    pred = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(pred, axis=-1)
    onehot = jax.nn.one_hot(tgt, cfg.vocab_size, dtype=jnp.bfloat16)
    gold = jnp.sum(pred * onehot, axis=-1)
    ce = lse - gold
    if valid is not None:
        ce = ce * valid
    return jnp.sum(ce), lse.size


def loss_fn(params, cfg: ModelConfig, batch, shd: ShardCtx = NOSHARD,
            remat: bool = True, ce_chunk: int = 1024):
    """batch: {tokens (B,S), [prefix_embeds]}; next-token CE over token span.

    The unembed + cross-entropy is computed in sequence chunks under remat so
    the (B, S, vocab) logits tensor is never materialized (vocab up to 256k).
    """
    tokens = batch["tokens"]
    h, aux = _hidden_train(params, cfg, tokens, batch.get("prefix_embeds"),
                           shd, remat)
    Pn = h.shape[1] - tokens.shape[1]
    h = h[:, Pn:-1]
    tgt = tokens[:, 1:]
    T = h.shape[1]
    if T <= ce_chunk:
        ce_sum, n = _ce_block(cfg, params, h, tgt, shd)
        ce = ce_sum / n
    else:
        # pad T up to a chunk multiple; padded positions are masked out
        nc = -(-T // ce_chunk)
        pad = nc * ce_chunk - T
        B = h.shape[0]
        hp = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        tp = jnp.pad(tgt, ((0, 0), (0, pad)))
        vp = jnp.pad(jnp.ones((B, T), jnp.float32), ((0, 0), (0, pad)))
        hc = hp.reshape(B, nc, ce_chunk, -1).swapaxes(0, 1)
        tc = tp.reshape(B, nc, ce_chunk).swapaxes(0, 1)
        vc = vp.reshape(B, nc, ce_chunk).swapaxes(0, 1)

        @jax.checkpoint
        def body(acc, xs):
            hi, ti, vi = xs
            s, n = _ce_block(cfg, params, hi, ti, shd, vi)
            return acc + s, None

        ce_sum, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                 (hc, tc, vc), unroll=cfg.unroll_scans)
        ce = ce_sum / (T * B)
    return ce + cfg.router_aux_loss * aux, {"ce": ce, "aux": aux}


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               long_context: bool = False, dtype=jnp.bfloat16,
               paged_pool=None) -> List:
    """Stacked cache pytree parallel to params['stages'].

    ``paged_pool=(num_pages, page_size)`` switches every *full-length*
    attention buffer (the ones whose size gates concurrent-stream capacity)
    to a shared paged pool addressed through a page table (see
    ``kvcache.init_paged_attn_cache``); bounded buffers (sliding window /
    long-context rings) and recurrent states keep the dense batch layout.
    """
    caches = []
    for kinds, n_rep in stages_of(cfg):
        group = []
        for k in kinds:
            if (paged_pool is not None and k in (FULL_ATTN, LOCAL_ATTN)
                    and KV.attn_buffer_len(cfg, k, max_len,
                                           long_context) == max_len):
                group.append(KV.init_paged_attn_cache(cfg, *paged_pool, dtype))
            else:
                group.append(KV.init_block_cache(cfg, k, batch, max_len,
                                                 long_context, dtype))
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_rep,) + x.shape),
            tuple(group))
        caches.append(stacked)
    return caches


def prefill(params, cfg: ModelConfig, tokens, caches, prefix_embeds=None,
            shd: ShardCtx = NOSHARD):
    """Run the prompt, fill caches. Returns (last_logits (B,vocab), caches, next_pos)."""
    x, positions = _embed_inputs(cfg, params, tokens, prefix_embeds, shd)
    x, new_caches, _ = _run_stages(cfg, params, x, mode="prefill",
                                   positions=positions, caches=caches, pos=None,
                                   shd=shd, remat=False)
    last = L.apply_norm(cfg, params["final_norm"], x[:, -1:])
    logits = L.unembed(cfg, params["embed"], last)[:, 0]
    logits = shd.cs(logits, "b", "m")
    return logits, new_caches, x.shape[1]


def prefill_into_slot(params, cfg: ModelConfig, tokens, length, caches, slot,
                      shd: ShardCtx = NOSHARD, page_table=None):
    """Bucket-padded prefill of ONE prompt written into row ``slot`` of the
    shared batch caches, as a single jittable computation.

    ``tokens`` is (1, S_pad): the prompt right-padded to a static bucket
    length (a small set of buckets bounds compile count); ``length`` is the
    true prompt length (traced scalar); ``slot`` is the traced batch-row
    index.  K/V (and SSM/RG-LRU states) are written directly into the batch
    cache row via ``dynamic_update_slice`` — no fresh per-request cache is
    allocated and no full-batch splice happens on the host, so the caller can
    donate ``caches`` and XLA updates them in place.  Pad positions >= length
    hold garbage K/V that the position mask hides until the decode loop
    overwrites them (see ``kvcache.cache_write_prefill_slot``).

    Requires S_pad <= every dense attention buffer length (asserted at trace
    time); longer prompts go through ``prefill_chunk_into_slot`` (or the
    reference ``prefill`` path).  Pad tokens are masked out of expert-capacity
    competition (MoE) and out of the recurrent-state updates (SSM / RG-LRU),
    so a bucketed prompt matches its unpadded reference.  ``page_table``
    ((1, n_pages) row) addresses the paged K/V pools when the caches were
    built with ``init_cache(..., paged_pool=...)``.

    Returns (last_logits (1, vocab), caches, next_pos == length).
    """
    x, positions = _embed_inputs(cfg, params, tokens, None, shd)
    valid = (jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
             < jnp.asarray(length, jnp.int32))
    x, new_caches, _ = _run_stages(cfg, params, x, mode="prefill",
                                   positions=positions, caches=caches,
                                   pos=None, shd=shd, remat=False, slot=slot,
                                   length=length, valid=valid,
                                   page_table=page_table)
    last = jax.lax.dynamic_slice_in_dim(x, jnp.asarray(length, jnp.int32) - 1,
                                        1, axis=1)
    last = L.apply_norm(cfg, params["final_norm"], last)
    logits = L.unembed(cfg, params["embed"], last)[:, 0]
    logits = shd.cs(logits, "b", "m")
    return logits, new_caches, length


def prefill_chunk_into_slot(params, cfg: ModelConfig, tokens, start, length,
                            caches, slot, shd: ShardCtx = NOSHARD,
                            page_table=None):
    """One *chunk* of a chunked prefill: process ``tokens`` (1, S_pad) at
    absolute positions ``start..start+S_pad-1`` (``length`` of them valid)
    for the stream in row ``slot``, attending to all context this stream has
    already written (earlier chunks live in its cache row / page chain).

    Chunks must be fed in position order; attention reads the cached past
    (dense ring row or gathered page chain) and the raw current chunk, then
    writes the chunk's K/V — pads are *dropped*, not masked, because chunk
    writes may wrap a ring buffer onto valid earlier context.  SSM / RG-LRU
    states resume from the cached row state and are written back, so hybrid
    archs chunk exactly like attention-only ones.  Used by the serving
    engine to admit prompts longer than the smallest attention buffer across
    successive decode blocks instead of falling back to the eager reference
    prefill.

    Returns (last_logits (1, vocab), caches): logits at position
    ``start+length-1`` — only the final chunk's logits seed decoding.
    """
    x, positions = _embed_inputs(cfg, params, tokens, None, shd,
                                 start_pos=jnp.asarray(start, jnp.int32))
    valid = (jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
             < jnp.asarray(length, jnp.int32))
    x, new_caches, _ = _run_stages(cfg, params, x, mode="chunk",
                                   positions=positions, caches=caches,
                                   pos=start, shd=shd, remat=False, slot=slot,
                                   length=length, valid=valid,
                                   page_table=page_table)
    last = jax.lax.dynamic_slice_in_dim(x, jnp.asarray(length, jnp.int32) - 1,
                                        1, axis=1)
    last = L.apply_norm(cfg, params["final_norm"], last)
    logits = L.unembed(cfg, params["embed"], last)[:, 0]
    logits = shd.cs(logits, "b", "m")
    return logits, new_caches


def sample_tokens(logits, temperature: float = 0.0, key=None):
    """On-device sampling: (B, vocab) logits -> (B,) int32 token ids.

    ``temperature <= 0`` (or no key) is greedy argmax; otherwise categorical
    sampling at the given temperature.  Kept inside the jitted serving step so
    the steady-state decode loop never ships logits to the host.  The scalar
    (batch-global) legacy surface — the serving engines sample per slot via
    ``sample_tokens_batched``.
    """
    if temperature > 0.0 and key is not None:
        return jax.random.categorical(
            key, logits.astype(jnp.float32) / temperature, axis=-1
        ).astype(jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def sample_tokens_batched(logits, temps, top_k, top_p, keys):
    """Per-row vectorized sampling: (B, vocab) logits -> (B,) int32 tokens.

    Each batch row carries its own sampling lane, so heterogeneous requests
    (greedy code completion next to creative-writing nucleus sampling) share
    one jitted decode step with no static sampling arguments:

    * ``temps`` (B,) float: ``0`` rows are greedy argmax — bit-identical to
      ``jnp.argmax`` — all other rows sample at their own temperature.
    * ``top_k`` (B,) int: keep only the k highest logits per row (``<= 0``
      or ``>= vocab`` disables the filter for that row).
    * ``top_p`` (B,) float: nucleus filtering — keep the smallest prefix of
      the (top-k-filtered, temperature-scaled) distribution whose
      cumulative probability reaches ``top_p`` (``>= 1.0`` disables; the
      disabled filters leave the logits untouched, so ``top_k=vocab,
      top_p=1.0`` reduces *exactly* to plain temperature sampling).
    * ``keys`` (B, 2) uint32: one PRNG key per row, consumed whole for this
      draw — callers derive one subkey per draw (the serving engine folds
      the token's sequence position into the stream's base lane), keeping
      rows independent: row i's draw never reads row j's key or logits.

    Fully on-device (one sort per draw, no host syncs), safe under
    ``lax.scan``.
    """
    V = logits.shape[-1]
    lg = logits.astype(jnp.float32)
    greedy_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    temps = jnp.asarray(temps, jnp.float32)
    safe_t = jnp.where(temps > 0.0, temps, 1.0)
    scaled = lg / safe_t[:, None]
    # rank rows once (descending); both filters are masks in sorted space
    order = jnp.argsort(scaled, axis=-1)[:, ::-1]
    s = jnp.take_along_axis(scaled, order, axis=-1)
    ranks = jnp.arange(V, dtype=jnp.int32)[None, :]
    k = jnp.asarray(top_k, jnp.int32)
    k_eff = jnp.where((k <= 0) | (k >= V), V, k)
    keep = ranks < k_eff[:, None]
    # nucleus mass over the top-k survivors; cum_prev is the mass *before*
    # each token, so rank 0 is always kept (the filter can never mask the
    # entire row) and exactly the smallest covering prefix survives
    probs = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    cum_prev = jnp.cumsum(probs, axis=-1) - probs
    p = jnp.asarray(top_p, jnp.float32)
    keep &= (cum_prev < p[:, None]) | (p[:, None] >= 1.0)
    inv = jnp.argsort(order, axis=-1)
    keep = jnp.take_along_axis(keep, inv, axis=-1)
    filtered = jnp.where(keep, scaled, -jnp.inf)
    draw = jax.vmap(
        lambda kk, row: jax.random.categorical(kk, row))(keys, filtered)
    return jnp.where(temps > 0.0, draw.astype(jnp.int32), greedy_tok)


def decode_step(params, cfg: ModelConfig, tokens, caches, pos,
                shd: ShardCtx = NOSHARD, page_table=None, active=None):
    """tokens (B,1) -> (logits (B,vocab), caches).

    ``pos`` is either a traced scalar (all rows decode at one shared stream
    position — the lockstep path used by training-style eval) or a (B,) int32
    vector of per-slot positions (slot-native serving: each row attends to its
    own context length, RoPE/masks/cache-writes are per-row).

    ``page_table`` ((B, n_pages) int32) must be passed when ``caches`` hold
    paged pools (``init_cache(..., paged_pool=...)``): each row's K/V write
    and attention go through its page chain, ctx-bounded by the
    caller-sliced table width.

    ``active`` ((B,) bool) freezes inactive rows' *recurrent* (SSM/RG-LRU)
    states, and lets the paged attention kernel skip inactive rows' chains
    (their output is discarded): K/V writes of inactive rows are hidden by
    position masking, but recurrent state has no positions, so without the
    mask a mid-chunked-prefill row would be polluted by other streams'
    decode blocks.
    """
    B = tokens.shape[0]
    if shd.mesh is not None:
        # one-hot matmul lookup: with a vocab-sharded table this lowers to a
        # sharded matmul + tiny psum instead of all-gathering the table
        # (gemma2's table alone is 1.8 GB) on every decode step.
        oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=params["embed"]["embedding"].dtype)
        x = oh @ params["embed"]["embedding"]
        if cfg.embed_scale:
            x = x * jnp.asarray(jnp.sqrt(jnp.asarray(cfg.d_model, jnp.float32)), x.dtype)
    else:
        x = L.embed_tokens(cfg, params["embed"], tokens)
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.broadcast_to(pos.reshape(-1, 1), (B, 1))
    if cfg.pos_embedding == "sincos":
        x = x + L.sincos_embedding(positions, cfg.d_model).astype(x.dtype)
    x = shd.cs(x, "b", None, None)
    x, new_caches, _ = _run_stages(cfg, params, x, mode="decode",
                                   positions=positions, caches=caches, pos=pos,
                                   shd=shd, remat=False,
                                   page_table=page_table, active=active)
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], x)[:, 0]
    logits = shd.cs(logits, "b", "m")
    return logits, new_caches
