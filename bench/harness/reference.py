"""The plain reference: a dense Qwen2/Qwen3 decoder forward pass in
float32 at ``highest`` matmul precision, written from the published model
description and importing nothing of the program.

Pre-norm RMSNorm blocks; q/k/v projections (with bias where the
configuration says), per-head RMSNorm of q and k before RoPE where it says
(Qwen3), rotate-half RoPE, causal grouped-query softmax attention, SwiGLU
MLP, final RMSNorm, tied or untied output head.

It runs layer by layer over whole sequences, and the head only at the
positions asked for, so a 14B-wide model fits one chip in float32 (the
bfloat16 weights are widened one layer at a time).  It computes on one
chip, the first that holds the weights: where they are split over several,
each layer, and the embedding table for the head, is gathered to it in
turn, so the numbers do not depend on how the weights are placed.

At each position it also applies the request's sampling rule to its own
logits, as ``SamplingParams`` states it: greedy is the argmax; a sampled
token is the argmax of ``logits / temperature + gumbel`` over the tokens that
top-k (the k largest) and then top-p (the smallest prefix, by descending
probability, of the top-k distribution whose mass reaches top_p) keep, the
gumbel noise drawn from the key ``fold_in(PRNGKey(seed), position)``.

``quant="fp8"`` is the control: the same forward with every matmul operand
rounded to float8 e4m3 (weights per tensor, activations per row, scaled to
the format's range), the nearest precision below the bfloat16 that the
configurations serve in.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import numpy as np

FP8_MAX = 448.0
Q_CHUNK = 512            # query rows per attention block
ROW_CHUNK = 1024         # rows per MLP block
HEAD_CHUNK = 256         # positions per output-head block


def _q8(x, axis):
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ops(quant: Optional[str]):
    """(weight cast, activation cast, dot) for a precision."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    if quant is None:
        ident = lambda x, *_: x
        return ident, ident, functools.partial(jnp.einsum, precision=hi)
    if quant != "fp8":
        raise ValueError(f"unknown precision {quant!r}")
    return (lambda w: _q8(w, None),
            lambda x, axis=-1: _q8(x, axis),
            functools.partial(jnp.einsum, precision=hi))


def _rms(x, w, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _rope(x, pos, theta):
    import jax.numpy as jnp
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_one(s, quant, x, w):
    """One decoder layer over one sequence x (T, d), float32."""
    import jax
    import jax.numpy as jnp
    cw, ca, dot = _ops(quant)
    T = x.shape[0]
    H, KH, hd = s["heads"], s["kv_heads"], s["head_dim"]
    G = H // KH
    pos = jnp.arange(T, dtype=jnp.int32)
    f = lambda a: jnp.asarray(a, jnp.float32)
    h = _rms(x, f(w["norm/scale"]), s["eps"])
    q = dot("td,de->te", ca(h), cw(f(w["attn/q"])))
    k = dot("td,de->te", ca(h), cw(f(w["attn/k"])))
    v = dot("td,de->te", ca(h), cw(f(w["attn/v"])))
    if s["qkv_bias"]:
        q, k, v = (q + f(w["attn/q_b"]), k + f(w["attn/k_b"]),
                   v + f(w["attn/v_b"]))
    q, k, v = (q.reshape(T, H, hd), k.reshape(T, KH, hd),
               v.reshape(T, KH, hd))
    if s["qk_norm"]:
        q = _rms(q, f(w["attn/q_norm"]), s["eps"])
        k = _rms(k, f(w["attn/k_norm"]), s["eps"])
    q, k = _rope(q, pos, s["rope_theta"]), _rope(k, pos, s["rope_theta"])
    q = q.reshape(T, KH, G, hd) * (hd ** -0.5)
    kq, vq = ca(k), ca(v, 0)

    def attend(args):
        qc, pc = args                                  # (C, KH, G, hd), (C,)
        sc = dot("cjgd,tjd->jgct", ca(qc), kq)
        sc = jnp.where(pos[None, None, None, :] <= pc[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return dot("jgct,tjd->cjgd", ca(p), vq)

    C = min(Q_CHUNK, T)
    att = jax.lax.map(attend, (q.reshape(T // C, C, KH, G, hd),
                               pos.reshape(T // C, C)))
    att = att.reshape(T, H * hd)
    x = x + dot("te,ed->td", ca(att), cw(f(w["attn/o"])))

    def mlp(xc):
        hc = ca(_rms(xc, f(w["mlp_norm/scale"]), s["eps"]))
        g = dot("td,df->tf", hc, cw(f(w["mlp/gate"])))
        u = dot("td,df->tf", hc, cw(f(w["mlp/up"])))
        return xc + dot("tf,fd->td", ca(jax.nn.silu(g) * u),
                        cw(f(w["mlp/down"])))

    R = min(ROW_CHUNK, T)
    return jax.lax.map(mlp, x.reshape(T // R, R, -1)).reshape(T, -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(skey, quant):
    import jax
    s = dict(skey)
    return jax.jit(lambda x, w: jax.lax.map(
        lambda xi: _layer_one(s, quant, xi, w), x))


def _kept_floor(sc, top_k, top_p):
    """Per row of scaled logits, the lowest one that top-k and top-p keep."""
    import jax
    import jax.numpy as jnp
    V = sc.shape[-1]
    desc = -jnp.sort(-sc, axis=-1)
    rank = jnp.arange(V)[None, :]
    k = jnp.where((top_k <= 0) | (top_k >= V), V, top_k)
    keep = rank < k[:, None]
    prob = jax.nn.softmax(jnp.where(keep, desc, -jnp.inf), axis=-1)
    before = jnp.cumsum(prob, axis=-1) - prob
    keep &= (before < top_p[:, None]) | (top_p[:, None] >= 1.0)
    n = jnp.sum(keep, axis=-1)
    return jnp.take_along_axis(desc, (n - 1)[:, None], -1)[:, 0]


def _rule(lg, temp, top_k, top_p, key, pos):
    """Of a block of positions under their requests' sampling rules: greedy
    rows, temperatures, scaled logits, the filters' floor, and the scaled
    logits plus gumbel noise, unfiltered and filtered."""
    import jax
    import jax.numpy as jnp
    greedy = temp <= 0.0
    t = jnp.where(greedy, 1.0, temp)
    sc = lg / t[:, None]
    floor = _kept_floor(sc, top_k, top_p)
    sub = jax.vmap(jax.random.fold_in)(key, pos)
    g = jax.vmap(lambda kk: jax.random.gumbel(kk, lg.shape[-1:],
                                              jnp.float32))(sub)
    return greedy, t, sc, floor, sc + g, jnp.where(sc >= floor[:, None],
                                                    sc + g, -jnp.inf)


@functools.lru_cache(maxsize=None)
def _head_fn(skey, quant, mode):
    import jax
    import jax.numpy as jnp
    s = dict(skey)
    cw, ca, dot = _ops(quant)

    def head(h, norm, table, target, temp, top_k, top_p, key, pos):
        h = ca(_rms(h, norm, s["eps"]))
        t = cw(table.astype(jnp.float32))
        lg = dot("pd,vd->pv", h, t) if s["tied"] else dot("pd,dv->pv", h, t)
        greedy, t, sc, floor, zall, z = _rule(lg, temp, top_k, top_p, key,
                                              pos)
        if mode == "choose":
            return jnp.where(greedy, jnp.argmax(lg, -1),
                             jnp.argmax(z, -1)).astype(jnp.int32)
        pick = lambda a: jnp.take_along_axis(a, target[:, None], -1)[:, 0]
        # greedy: how far the token's logit lies below the best.  Sampled,
        # in logits: how far its scaled logit lies below the filters' floor,
        # or the most that any kept token that outscores it would have to
        # move to lose: its lead in score, or its height above the floor,
        # whichever is less (a token at the edge of top-k or top-p may
        # fall either side of it under rounding)
        zt = pick(zall)[:, None]
        beat = jnp.where((sc >= floor[:, None]) & (zall > zt),
                         jnp.minimum(zall - zt, sc - floor[:, None]), 0.0)
        drawn = jnp.maximum(jnp.max(beat, -1),
                            jnp.maximum(floor - pick(sc), 0.0)) * t
        return jnp.where(greedy, jnp.max(lg, -1) - pick(lg), drawn)

    return jax.jit(lambda h, norm, table, *rows: jax.lax.map(
        lambda a: head(a[0], norm, table, *a[1:]), (h,) + rows))


def _pad_len(n: int) -> int:
    """A power of two >= n and >= Q_CHUNK: whole attention and MLP blocks,
    few compiled shapes."""
    return max(Q_CHUNK, 1 << (n - 1).bit_length())


def _skey(s: dict) -> tuple:
    return tuple(sorted(s.items()))


@functools.lru_cache(maxsize=None)
def _gather_fn(mesh):
    """A reshard to replicated over ``mesh``: an all-gather on the chips."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.jit(lambda t: t,
                   out_shardings=NamedSharding(mesh, PartitionSpec()))


def _home(params):
    """A function that brings a tree of arrays to the one device the
    reference computes on, the first that holds the weights; arrays split
    over several chips are gathered on the chips and the first one's
    copy is kept."""
    import jax
    emb = params["embed"]["embedding"]
    dev = min(emb.devices(), key=lambda d: d.id)
    if len(emb.devices()) == 1:
        return lambda tree: jax.device_put(tree, dev)
    gather = _gather_fn(emb.sharding.mesh)

    def one(a):
        return next(sh.data for sh in gather(a).addressable_shards
                    if sh.device == dev)
    return lambda tree: jax.tree.map(one, tree)


def hidden(s: dict, params, seqs: Sequence[np.ndarray], quant=None):
    """Final hidden states (before the last norm), (len(seqs), T, d) f32,
    T the padded length; pads sit after each sequence and, attention
    being causal, touch nothing before them."""
    import jax
    import jax.numpy as jnp
    T = _pad_len(max(len(q) for q in seqs))
    toks = np.zeros((len(seqs), T), np.int32)
    for i, q in enumerate(seqs):
        toks[i, :len(q)] = q
    blk = params["stages"][0]["blocks"][0]
    home = _home(params)
    table = params["embed"]["embedding"]
    x = home(jnp.take(table, jnp.asarray(toks), axis=0)).astype(jnp.float32)
    fn = _layer_fn(_skey(s), quant)
    for layer in range(s["layers"]):
        w = home({f"{a}/{b}": blk[a][b][layer] for a in blk for b in blk[a]})
        x = fn(x, w)
    return jax.block_until_ready(x)


def read_positions(s, params, seqs, starts, targets, rules, quant=None,
                   mode="gap") -> List[np.ndarray]:
    """For sequence i, positions ``starts[i] .. starts[i] + n_i - 1``, each
    predicting the token one position later under the request's sampling
    rule ``rules[i]`` = (temperature or 0 for greedy, top_k, top_p, seed):
    ``mode="gap"``: by how much, in logits, the reference would have to
    move for ``targets[i][j]`` to be its token (0 where it is);
    ``mode="choose"``: the token it produces (``n_i`` from ``targets[i]``'s
    length)."""
    import jax
    import jax.numpy as jnp
    x = hidden(s, params, seqs, quant)
    rows, tgts, cols = [], [], []
    for i, (t, (temp, top_k, top_p, seed)) in enumerate(zip(targets, rules)):
        n = len(t)
        idx = np.arange(starts[i], starts[i] + n)
        rows.append(np.stack([np.full(n, i), idx], 1))
        tgts.append(np.asarray(t, np.int32))
        key = np.zeros(2, np.uint32) if not temp else \
            np.asarray(jax.random.PRNGKey(int(seed)), np.uint32)
        cols.append((np.full(n, temp or 0.0, np.float32),
                     np.full(n, top_k, np.int32),
                     np.full(n, top_p, np.float32),
                     np.tile(key, (n, 1)), (idx + 1).astype(np.int32)))
    sizes = [len(t) for t in tgts]
    rows = np.concatenate(rows)
    P = len(rows)
    Pp = -(-P // HEAD_CHUNK) * HEAD_CHUNK

    def blocks(a):
        a = np.concatenate(a)
        a = np.pad(a, [(0, Pp - P)] + [(0, 0)] * (a.ndim - 1))
        return jnp.asarray(a.reshape((Pp // HEAD_CHUNK, HEAD_CHUNK)
                                     + a.shape[1:]))

    sel = x[jnp.asarray(rows[:, 0]), jnp.asarray(rows[:, 1])]
    sel = jnp.pad(sel, ((0, Pp - P), (0, 0)))
    del x
    home = _home(params)
    table = home(params["embed"]["embedding"] if s["tied"]
                 else params["embed"]["lm_head"])
    norm = home(jnp.asarray(params["final_norm"]["scale"], jnp.float32))
    out = _head_fn(_skey(s), quant, mode)(
        sel.reshape(Pp // HEAD_CHUNK, HEAD_CHUNK, -1), norm, table,
        blocks(tgts), *(blocks([c[j] for c in cols]) for j in range(5)))
    out = np.asarray(out).reshape(-1)[:P]
    return np.split(out, np.cumsum(sizes)[:-1])
