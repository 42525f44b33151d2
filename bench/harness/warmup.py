"""Set-up: compile every program shape the cell's traffic can reach, before
the window, and touch the small eager programs of admission and retirement
with two short requests.

The shapes follow from the engine's own bucketing (prefill buckets, decode
context buckets, fused block lengths, sampled or greedy variants) and from
the traffic's length ranges, so the set is fixed by the cell, not by what a
run happens to meet.  Programs are lowered one by one and compiled on a
thread pool; the persistent compilation cache makes later runs read them.
"""
from __future__ import annotations

import concurrent.futures
import os
from typing import List

import numpy as np

from .traffic import Plan


def _bucket(sizes: List[int], n: int) -> int:
    return next((b for b in sizes if b >= n), sizes[-1])


def program_shapes(eng, plan: Plan) -> dict:
    """The static shapes the window can reach, by program."""
    reqs = plan.initial + plan.queue
    prompts = [len(r.prompt) for r in reqs]
    contexts = [len(r.prompt) + r.max_tokens for r in reqs]
    greedy = {r.greedy for r in reqs}
    prefill_modes = sorted({not g for g in greedy})
    decode_modes = [True] if False in greedy else [False]
    b_lo, b_hi = _bucket(eng.buckets, min(prompts)), \
        _bucket(eng.buckets, max(prompts))
    prefill = [b for b in eng.buckets if b_lo <= b <= b_hi]
    c_lo = _bucket(eng.ctx_buckets, min(prompts) + 1)
    c_hi = _bucket(eng.ctx_buckets, max(contexts))
    ctx = [c for c in eng.ctx_buckets if c_lo <= c <= c_hi]
    # recompute after preemption replays prompt + output through chunked
    # prefill: one chunk up to chunk_len, then the rest at start chunk_len
    chunk = set()
    if eng.pager is not None:
        pool = (eng.pager.num_pages - 1) * eng.ecfg.page_size
        clients = plan.clients or eng.ecfg.max_batch
        if pool < clients * max(contexts):
            longest = max(contexts)
            for b in eng.buckets:
                chunk.add((0, b, _bucket(eng.ctx_buckets, b)))
                if longest > eng.chunk_len and \
                        b <= _bucket(eng.buckets, longest - eng.chunk_len):
                    chunk.add((eng.chunk_len, b, _bucket(
                        eng.ctx_buckets, eng.chunk_len + b)))
    return dict(prefill=prefill, prefill_modes=prefill_modes, ctx=ctx,
                k_blocks=list(eng._k_blocks), decode_modes=decode_modes,
                chunk=sorted(chunk))


def lowered_programs(eng, shapes: dict):
    """Every program shape, lowered with the engine's own state as
    arguments, so the compiled executables are the ones its calls look
    up."""
    import jax.numpy as jnp
    from repro.serving import engine as E
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    ps = eng.ecfg.page_size
    lanes = (eng._keys, eng._temps, eng._topk, eng._topp)
    for s in shapes["prefill_modes"]:
        for b in shapes["prefill"]:
            yield E._prefill_kernel.lower(
                eng.cfg, eng._shd, s, eng.params, jnp.zeros((1, b), jnp.int32),
                i32(1), eng.caches, i32(0), eng._pt_rows(0, b), eng._tok,
                eng._pos, *lanes)
        for start, b, c in shapes["chunk"]:
            pt = eng.pager.table_device()[0:1, :min(c // ps, eng._max_pages)]
            yield E._chunk_prefill_kernel.lower(
                eng.cfg, eng._shd, s, eng.params, jnp.zeros((1, b), jnp.int32),
                i32(start), i32(1), eng.caches, i32(0), pt, eng._tok,
                eng._pos, *lanes)
    for s in shapes["decode_modes"]:
        for c in shapes["ctx"]:
            pt = eng.pager.table_device()[:, :min(c // ps, eng._max_pages)]
            for k in shapes["k_blocks"]:
                yield E._paged_decode_block_kernel.lower(
                    eng.cfg, eng._shd, k, s, eng.params, eng._tok, eng.caches,
                    pt, eng._pos, eng._active, *lanes)


def compile_all(eng, shapes: dict, log, workers: int = 0) -> int:
    workers = workers or min(8, os.cpu_count() or 1)
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        futs = [pool.submit(lowered.compile)
                for lowered in lowered_programs(eng, shapes)]
        for f in futs:
            f.result()
        n = len(futs)
    log(f"[setup] programs compiled or read: {n} ({shapes})")
    return n


def touch_small_programs(server, plan: Plan) -> None:
    """Two short requests, one per sampling kind the traffic has, served
    together: admission, lane writes, first-token read, retirement."""
    from repro.core import SamplingParams
    reqs = plan.initial + plan.queue
    L = min(len(r.prompt) for r in reqs)
    kinds = {}
    for r in reqs:
        kinds.setdefault(r.greedy, r)
    for g, r in kinds.items():
        sp = SamplingParams(max_tokens=2, temperature=r.temperature,
                            top_k=r.top_k, top_p=r.top_p, seed=r.lane_seed)
        server.submit(np.asarray(r.prompt[:L]), sp)
    server.run()
