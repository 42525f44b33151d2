"""Slot-native real-execution serving engine: fully-jitted continuous batching
driven by the same GreenLLM control plane as the simulator.

Data-plane design (the hot path):

* **Bucketed slot prefill** — prompts are right-padded to a small set of
  power-of-two buckets (bounding compile count to O(log max_len)) and run
  through ``models.prefill_into_slot``, which writes K/V (and SSM/RG-LRU
  states) directly into one row of the shared batch cache via
  ``dynamic_update_slice`` inside the jitted computation.  Admission never
  allocates a per-request cache and never splices the full batch cache on the
  host.  Prompts longer than every attention buffer (sliding-window /
  long-context ring caches) fall back to the reference ``models.prefill`` +
  host splice path.
* **Donated decode step** — one ``jax.jit(..., donate_argnums=...)`` step
  carries per-slot position vectors and an active-slot mask: each stream
  attends to *its own* context (not the batch-wide ``max(pos)``), inactive
  rows hold position, and the donated caches update in place instead of being
  copied twice per token.
* **On-device per-slot sampling** — sampling is a per-slot vectorized
  property of the jitted step: each batch row carries its own temperature /
  top-k / top-p lane plus a PRNG *base* key in device vectors
  (``sample_tokens_batched``), so heterogeneous requests (greedy code
  completion next to nucleus-sampled creative writing) share one batch with
  no static sampling arguments and **no per-token host transfer**: the
  per-slot token ids are drained once per block, sized to the next stream
  join/leave event.  Draw subkeys fold the token's sequence position into
  the row's base lane — the lane itself never advances, so a stream's i-th
  draw is a pure function of ``(lane, position)`` and seeded streams replay
  identical tokens across runs, migrations, and recompute-on-resume.
* **Paged KV cache** (``EngineConfig.paged=True``) — full-length attention
  buffers become a shared pool of fixed-size pages (``serving.pager``);
  streams hold page chains that grow at decode-block boundaries, so capacity
  is bounded by tokens in flight instead of ``max_batch x max_len`` and pool
  exhaustion preempts the youngest stream (freed pages + recompute-on-resume)
  rather than failing.  Page-table updates ride the existing block cadence —
  the no-per-token-host-sync invariant holds.
* **Chunked prefill** (``EngineConfig.chunked_prefill=True``, the default) —
  prompts longer than the largest bucket are split into bucket-sized chunks
  admitted across successive decode blocks (Sarathi-style), each chunk a
  jitted ``prefill_chunk_into_slot`` call that attends to the stream's cached
  context; sliding-window and long-context configs stay on the slot-native
  path end to end instead of falling back to the eager reference prefill.
* **Stream migration** (``export_stream`` / ``import_stream``) — a decodable
  stream is a first-class movable object: its page-chain K/V, bounded dense
  rows, recurrent (SSM/RG-LRU) row state, position and last token transfer
  into another engine's pool in O(context) data (no full-length buffer is
  ever copied), which is what makes disaggregated prefill/decode replicas
  (``serving.cluster``) a cheap placement decision instead of a data-plane
  rewrite.  Pool pressure can preempt streams in *either* phase (decoding or
  mid-chunked-prefill), youngest-first, with recompute-on-resume.

*Virtual time* for SLO/energy accounting comes from the calibrated plant
model on every backend, while the token *values* are produced by the real
network — the reduced ``.smoke()`` models on CPU, published widths on a TPU
(``chip_smoke.py``).  ``use_wall_clock=True`` accounts decode blocks with
measured per-block latencies instead.

``EngineConfig(slot_native=False)`` keeps the pre-slot data plane (per-request
prefill + full-cache splice, per-step host sync, batch-wide ``max(pos)``) as a
benchmark baseline; it is deprecated for serving because mixed-position
batches attend to the wrong context there.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (CounterfactualPricer, DualLoopController,
                        MaxFreqController, Request, RequestState,
                        SamplingParams, ServingReport, SLOConfig, StateEvent,
                        TokenEvent, build_report, make_router)
from repro.core.telemetry import OccupancyMeter, TBTMeter
from repro.core.tracing import host_span
from repro.launch.shardings import (gather_replicated, make_serving_shard_ctx,
                                    named, serving_param_specs,
                                    shard_serving_caches)
from repro.models import (ModelConfig, NOSHARD, init_cache, init_params,
                          prefill, prefill_into_slot, prefill_chunk_into_slot,
                          decode_step, sample_tokens_batched)
from repro.models.transformer import paged_decode_kernel_ok
from repro.models.config import FULL_ATTN, LOCAL_ATTN
from repro.models.kvcache import (attn_buffer_len, is_paged,
                                  paged_chain_extract, paged_chain_insert,
                                  paged_page_copy,
                                  cache_row_extract, cache_row_insert)
from repro.sim import PlantModel
from repro.sim.profiling import profile_decode_table
from repro.core.hardware import HardwareProfile, A100_SXM4_40G
from .pager import PageAllocator
from .prefix_cache import PrefixCache


# -- jitted kernels (module level: JAX's global jit cache shares compiles
# across engine instances; cfg/temp/ctx/k/max_len are static) -----------------

def _sliceable(leaf_len: int, ctx: int, max_len: int) -> bool:
    # only full-length attention buffers are position==slot and safe to
    # truncate; windowed/long-context ring buffers are already bounded
    return leaf_len == max_len and ctx < leaf_len


def _slice_caches(caches, ctx: int, max_len: int):
    out = []
    for stage in caches:
        blocks = []
        for d in stage:
            if "k" in d and _sliceable(d["k"].shape[2], ctx, max_len):
                blocks.append({kk: vv[:, :, :ctx] for kk, vv in d.items()})
            else:
                blocks.append(d)
        out.append(tuple(blocks))
    return out


def _unslice_caches(caches, sliced, ctx: int, max_len: int):
    out = []
    for stage, sstage in zip(caches, sliced):
        blocks = []
        for d, sd in zip(stage, sstage):
            if "k" in d and _sliceable(d["k"].shape[2], ctx, max_len):
                blocks.append({
                    kk: jax.lax.dynamic_update_slice(
                        d[kk], sd[kk], (0,) * d[kk].ndim)
                    for kk in d})
            else:
                blocks.append(sd)
        out.append(tuple(blocks))
    return out


def _row_subkeys(keys, positions):
    """One draw subkey per batch row: fold each token's sequence position
    into the row's PRNG *base* lane.  Lanes never advance — draw i is a pure
    function of (lane, position i) — which is exactly what makes seeded
    streams replay identical tokens across migration and recompute-on-resume
    (the lane and the position both travel with the stream)."""
    return jax.vmap(jax.random.fold_in)(
        keys, jnp.asarray(positions, jnp.int32))


def _sample_rows(sampled, logits, pos_next, keys, temps, topk, topp):
    """Shared sampling tail of the decode/prefill kernels: per-row
    temperature/top-k/top-p lanes when ``sampled`` (a host-known static:
    does any live row sample?), plain argmax otherwise — all-greedy blocks
    never pay for the sampler's sort."""
    with jax.named_scope("sampler"):
        if sampled:
            return sample_tokens_batched(logits, temps, topk, topp,
                                         _row_subkeys(keys, pos_next))
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5),
                   donate_argnums=(8,))
def _decode_block_kernel(cfg, shd, ctx, k, max_len, sampled,
                         params, tok, caches, pos, active, keys, temps,
                         topk, topp):
    """k fused decode steps (lax.scan) over caches sliced to ``ctx`` positions.

    One compile per (cfg, shd, ctx_bucket, k_block, sampled).  While every
    active position stays < ctx, the sliced cache behaves exactly like a
    max_len==ctx cache (slot == position, nothing masked away), so the block
    is equivalent to k single full-cache steps; the donated full caches are
    updated in place via a slice-in/slice-out pair amortized over the k
    steps.  The sampled token at row r lands at position ``pos[r] + 1``, so
    its subkey is ``fold_in(keys[r], pos[r] + 1)`` — no key state threads
    through the scan.

    ``shd`` (a hashable ShardCtx; NOSHARD off-mesh) is the serving mesh
    context: storage-sharded params are gathered to replicated at entry and
    every other operand stays sharded along the data axis only, so the
    sharded block is bit-identical to the single-device one.
    """
    with jax.named_scope("decode_block"):
        params = gather_replicated(params, shd.mesh)
        sliced = _slice_caches(caches, ctx, max_len)

        def body(carry, _):
            tok, sl, pos = carry
            logits, sl = decode_step(params, cfg, tok[:, None], sl, pos,
                                     shd=shd, active=active)
            nxt = _sample_rows(sampled, logits, pos + 1, keys, temps, topk,
                               topp)
            tok = jnp.where(active, nxt, tok)
            pos = pos + active.astype(jnp.int32)
            return (tok, sl, pos), tok

        (tok, sliced, pos), toks = jax.lax.scan(
            body, (tok, sliced, pos), None, length=k)
        caches = _unslice_caches(caches, sliced, ctx, max_len)
        return tok, caches, pos, toks


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), donate_argnums=(6,))
def _paged_decode_block_kernel(cfg, shd, k, sampled, params, tok, caches, pt,
                               pos, active, keys, temps, topk, topp):
    """k fused decode steps against paged K/V pools.

    Context bucketing rides on the *shape* of ``pt`` (the page table sliced to
    the pages covering the current ctx bucket): one compile per (cfg, shd,
    n_ctx_pages, k_block, sampled).  The caller guarantees every active chain
    covers ``pos + k`` before dispatch, so the in-scan writes never leave the
    table slice; retired rows' table entries point at the scratch page.

    On a serving mesh (``shd.mesh`` set) the pool's page axis and the
    table's slot axis are sharded along 'data'; the page gather/scatter is
    cross-shard data movement, so tokens stay bit-identical to the
    single-device kernel.
    """
    with jax.named_scope("decode_block"):
        params = gather_replicated(params, shd.mesh)

        def body(carry, _):
            tok, cs, pos = carry
            logits, cs = decode_step(params, cfg, tok[:, None], cs, pos,
                                     shd=shd, page_table=pt, active=active)
            nxt = _sample_rows(sampled, logits, pos + 1, keys, temps, topk,
                               topp)
            tok = jnp.where(active, nxt, tok)
            pos = pos + active.astype(jnp.int32)
            return (tok, cs, pos), tok

        (tok, caches, pos), toks = jax.lax.scan(
            body, (tok, caches, pos), None, length=k)
        return tok, caches, pos, toks


@functools.partial(jax.jit, static_argnums=(0,))
def _decode_legacy_kernel(cfg, params, tok, caches, pos):
    return decode_step(params, cfg, tok, caches, pos)


def _slot_row(v, slot):
    """(1, ...) slice of per-slot sampling state at a traced slot index."""
    return jax.lax.dynamic_slice_in_dim(v, slot, 1, axis=0)


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(6,))
def _prefill_kernel(cfg, shd, sampled, params, toks, length, caches, slot,
                    pt_row, tok, pos, keys, temps, topk, topp):
    """Bucketed slot prefill + first-token sampling (one compile per
    (bucket size, shd, sampled), the former carried by the static shape of
    ``toks``).  ``pt_row`` is the stream's (1, n_pages) page-table row for
    paged caches, or None.  The first token lands at position ``length``,
    so its draw subkey is ``fold_in(keys[slot], length)``."""
    with jax.named_scope("prefill"):
        params = gather_replicated(params, shd.mesh)
        logits, caches, _ = prefill_into_slot(params, cfg, toks, length,
                                              caches, slot, shd=shd,
                                              page_table=pt_row)
        L = jnp.asarray(length, jnp.int32)
        ptok = _sample_rows(sampled, logits, L[None], _slot_row(keys, slot),
                            _slot_row(temps, slot), _slot_row(topk, slot),
                            _slot_row(topp, slot))[0]
        tok = tok.at[slot].set(ptok)
        pos = pos.at[slot].set(length)
        return tok, caches, pos


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(7,))
def _chunk_prefill_kernel(cfg, shd, sampled, params, toks, start, length,
                          caches, slot, pt_row, tok, pos, keys, temps, topk,
                          topp):
    """One chunk of a chunked prefill + (provisional) next-token sampling.

    Compile count is |chunk buckets| x |ctx buckets| x sampled (the ctx
    buckets via the static shape of ``pt_row`` for paged caches; dense rows
    are read at their full static buffer length).  Every chunk samples into
    ``tok[slot]`` — cheap, and only the final chunk's sample survives to
    seed decoding — and advances ``pos[slot]`` to ``start + length`` so
    occupancy tracking sees partially-prefilled streams.  The final chunk's
    draw position ``start + length`` equals the total prompt length, i.e.
    exactly ``_prefill_kernel``'s subkey for the same prompt; intermediate
    chunks' provisional draws are discarded and touch no lane state, so a
    recompute-on-resume replay (which discards even the final draw in favor
    of ``resume_tok``) cannot perturb the stream's draw sequence.
    """
    with jax.named_scope("prefill_chunk"):
        params = gather_replicated(params, shd.mesh)
        logits, caches = prefill_chunk_into_slot(params, cfg, toks, start,
                                                 length, caches, slot,
                                                 shd=shd, page_table=pt_row)
        end = jnp.asarray(start, jnp.int32) + jnp.asarray(length, jnp.int32)
        ptok = _sample_rows(sampled, logits, end[None], _slot_row(keys, slot),
                            _slot_row(temps, slot), _slot_row(topk, slot),
                            _slot_row(topp, slot))[0]
        tok = tok.at[slot].set(ptok)
        pos = pos.at[slot].set(start + length)
        return tok, caches, pos


@functools.partial(jax.jit, donate_argnums=(0,))
def _page_copy_kernel(caches, src, dst):
    """Copy physical page ``src`` onto ``dst`` in every paged pool leaf —
    the device half of copy-on-write (``PageAllocator.cow_page`` is the host
    half).  Only dispatched when the engine is fully paged (every cache leaf
    a page pool), so a uniform tree map is safe; donation keeps it from
    duplicating the pools."""
    out = []
    for stage in caches:
        out.append(tuple(paged_page_copy(d, src, dst) for d in stage))
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _import_kernel(caches, payloads, chain, slot):
    """Scatter a migrated stream's page-chain K/V (paged leaves) and batch
    rows (bounded rings, recurrent state) into the donated caches: adoption
    writes O(context) in place instead of copying every pool leaf.  One
    compile per chain length."""
    out = []
    for stage, pstage in zip(caches, payloads):
        out.append(tuple(
            (d if p is None else paged_chain_insert(d, p, chain))
            if is_paged(d) else cache_row_insert(d, p, slot)
            for d, p in zip(stage, pstage)))
    return out


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    max_len: int = 256
    governor: str = "greenllm"      # greenllm | defaultnv
    use_wall_clock: bool = False    # account measured latency per decode block
    slot_native: bool = True        # False -> legacy data plane (benchmarks)
    decode_block: int = 64          # max decode steps in flight per host drain
    min_bucket: int = 16            # smallest prefill padding bucket
    # paged KV cache (serving.pager): full-length attention buffers become a
    # shared page pool; capacity = tokens in flight, not max_batch * max_len
    paged: bool = False
    page_size: int = 16             # tokens per page
    num_pages: int = 0              # per-layer pool size incl. scratch page;
    #                                 0 -> dense-equivalent capacity
    # split prompts longer than the largest bucket into bucket-sized chunks
    # admitted across successive decode blocks (False -> legacy eager-prefill
    # fallback; forced True when paged)
    chunked_prefill: bool = True
    cache_dtype: str = "bfloat16"   # K/V buffer dtype (f32 for exactness tests)
    # deadline-aware admission (graceful degradation under overload): a
    # request whose absolute deadline has already passed when it reaches the
    # queue head is SHED instead of served — burning prefill+decode energy
    # on a guaranteed SLO miss only delays every request behind it
    shed_past_deadline: bool = True
    # content-addressed prefix cache (serving.prefix_cache): admission
    # matches the longest cached page-aligned prompt prefix and shares those
    # pages (refcounted, copy-on-write) instead of re-prefilling them.
    # Requires paged; only fully-paged models (dense/GQA/kv_quant full
    # attention) actually share — hybrids with ring/recurrent state always
    # miss.  Off by default: bare runs are step-for-step identical to
    # pre-cache behavior.
    prefix_cache: bool = False
    prefix_cache_pages: int = 0     # retained-page cap (0 = pool-pressure
    #                                 bounded: reclaim on allocation failure)
    # deadline-aware eviction of *admitted* decoding streams (opt-in): a
    # stream whose absolute deadline lapses mid-decode is freed via the
    # cancel machinery and reported SHED — the tokens it would still emit
    # are guaranteed-late, so the energy belongs to streams that can pass
    evict_lapsed: bool = False
    # SLO targets for stats() pass-rate reporting (parity with
    # sim.replay.Metrics); virtual-time accounting itself is unaffected
    slo: SLOConfig = dataclasses.field(default_factory=SLOConfig)
    # (data, model) serving mesh shape: the replica's data plane spans a
    # device mesh slice instead of one chip.  Per-slot state, cache rows and
    # the page pool/table shard along 'data'; params are storage-sharded and
    # gathered at kernel entry, so every mesh shape serves bit-identically
    # to mesh=None (the sharded==single-device invariant).  None: unsharded.
    mesh: Optional[tuple] = None

    def __post_init__(self):
        """Reject impossible configurations here, with a readable message,
        instead of letting them fail deep inside jitted shape logic."""
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {self.max_len}")
        if self.decode_block < 1:
            raise ValueError(
                f"decode_block must be >= 1, got {self.decode_block}")
        if self.min_bucket < 1:
            raise ValueError(
                f"min_bucket must be >= 1, got {self.min_bucket}")
        if self.min_bucket > max(self.max_len // 2, 1):
            raise ValueError(
                f"min_bucket={self.min_bucket} exceeds the prefill bucket "
                f"cap max_len//2={self.max_len // 2} (prompts are truncated "
                f"to max_len//2, so no bucket could ever be used)")
        if self.paged:
            if not self.slot_native:
                raise ValueError(
                    "paged KV requires the slot-native data plane "
                    "(slot_native=True)")
            if self.page_size < 1:
                raise ValueError(
                    f"page_size must be >= 1, got {self.page_size}")
            if self.max_len % self.page_size:
                raise ValueError(
                    f"max_len={self.max_len} must be divisible by "
                    f"page_size={self.page_size}: pages are linear "
                    "(position == logical index) and ctx buckets round to "
                    "page multiples")
            if self.num_pages and self.num_pages < 2:
                # undersized pools (< one page per slot) are legal: pool
                # pressure is handled by preemption + recompute-on-resume.
                # But page 0 is the reserved scratch page, so the pool
                # needs at least one usable page beyond it.
                raise ValueError(
                    f"num_pages={self.num_pages} leaves no usable pages: "
                    "page 0 is the reserved scratch page (need num_pages "
                    ">= 2, or 0 for dense-equivalent capacity)")
        if self.mesh is not None:
            try:
                dp, tp = (int(v) for v in self.mesh)
            except (TypeError, ValueError):
                raise ValueError(
                    f"mesh must be a (data, model) pair, got {self.mesh!r}")
            self.mesh = (dp, tp)
            if dp < 1 or tp < 1:
                raise ValueError(
                    f"mesh axes must be >= 1, got mesh=({dp},{tp})")
            if not self.slot_native:
                raise ValueError(
                    "mesh serving requires the slot-native data plane "
                    "(slot_native=True): the legacy plane is a single-"
                    "device benchmark baseline")
            if self.max_batch % dp:
                raise ValueError(
                    f"max_batch={self.max_batch} is not divisible by the "
                    f"data axis dp={dp}: per-slot state, cache rows, and "
                    "the page table shard max_batch rows along 'data' — "
                    "raise max_batch or shrink dp")
            if self.paged and self.num_pages and self.num_pages % dp:
                raise ValueError(
                    f"num_pages={self.num_pages} is not divisible by the "
                    f"data axis dp={dp}: the paged KV pool shards its page "
                    "axis along 'data' — round num_pages up to a multiple "
                    "of dp (or pass num_pages=0 for an auto-sized pool)")
        if self.prefix_cache and not self.paged:
            raise ValueError(
                "prefix_cache=True requires paged=True: cache entries are "
                "refcounted pages in the PageAllocator pool")
        if self.prefix_cache_pages < 0:
            raise ValueError(
                f"prefix_cache_pages must be >= 0, "
                f"got {self.prefix_cache_pages}")


@dataclasses.dataclass
class StreamHandoff:
    """A stream extracted from one engine for adoption by another (the
    disaggregated prefill->decode migration unit).

    ``blocks`` parallels the engine cache pytree: per stage, a tuple of
    ``("pages", extracted_chain_dict | None)`` for paged attention pools
    (only the live chain's pages — O(context) data, never a full-length
    buffer) or ``("row", row_dict)`` for bounded dense buffers (sliding-
    window rings) and recurrent SSM/RG-LRU states.  Together with ``pos``,
    ``last_token``, the sampling params and the PRNG lane this is the
    *complete* decodable state of the stream: import followed by decode is
    token-for-token identical to never having migrated — including sampled
    streams, because ``rng_lane`` (the never-advancing base key; draw i
    folds in token position i) travels with the stream and the adopter
    continues the same draw sequence.
    """
    req: Request
    pos: int
    last_token: int
    n_pages: int                    # chain length to adopt (0 = nothing paged)
    blocks: List                    # per-stage tuples of (kind, payload)
    export_time: float              # exporter's vtime at extraction
    page_size: int = 0              # 0 when the exporter is unpaged
    cfg_name: str = ""              # guard against cross-model migration
    sampling: Optional[SamplingParams] = None   # per-request sampling config
    rng_lane: Optional[object] = None  # (2,) uint32 base lane (np.ndarray)
    # the stream's partial energy ledger (core.attribution.LedgerCarry):
    # migrated requests keep their attributed joules across replicas.  A
    # no-op on adoption when both replicas share one ledger (the cluster).
    ledger_carry: Optional[object] = None
    # exporter's (data, model) mesh shape (None = unsharded): the adopter
    # rejects a mismatch the same way it rejects cfg/page_size mismatches —
    # handoff payloads are sharded pytrees, and adopting them onto a
    # different mesh would silently reshard mid-stream
    mesh_shape: Optional[tuple] = None


class _Stream:
    def __init__(self, req: Request, slot: int, last_token: int, pos: int,
                 order: int = 0):
        self.req = req
        self.slot = slot
        self.last_token = last_token
        self.pos = pos
        self.order = order          # admission sequence; preemption victims
        #                             are chosen youngest-first


class _ChunkState:
    """A stream mid-chunked-prefill: owns a slot (and page chain) but does
    not decode yet; ``tokens`` is the full context to prefill and ``start``
    the next chunk's absolute position.  ``resume_tok`` carries the
    already-sampled next token of a preempted stream being recomputed."""

    def __init__(self, req: Request, slot: int, tokens: np.ndarray,
                 resume_tok: Optional[int] = None, order: int = 0):
        self.req = req
        self.slot = slot
        self.tokens = tokens
        self.start = 0
        self.resume_tok = resume_tok
        self.order = order          # admission sequence (preemption victims
        #                             are youngest-first across phases)
        self.billed = False         # first *computed* chunk sets
        #                             prefill_start (prefix-cache hits start
        #                             at start > 0, so "start == 0" can't
        #                             identify the first chunk)


class ServingEngine:
    """Batched decode over a shared slotted KV cache (continuous batching)."""

    def __init__(self, cfg: ModelConfig, params=None, *,
                 ecfg: Optional[EngineConfig] = None,
                 hw: HardwareProfile = A100_SXM4_40G, seed: int = 0,
                 plant_cfg: ModelConfig = None, plant: PlantModel = None,
                 decode_table=None, controller=None, name: str = "engine",
                 metrics=None, tracer=None, ledger=None):
        # plant_cfg: config used for virtual-time/energy accounting (e.g. the
        # FULL model) while `cfg` (possibly reduced) produces real tokens.
        # plant / decode_table / controller: cluster injection points — a
        # multi-replica cluster shares one offline profiling pass and gives
        # each replica its role's controller (prefill-optimizer-driven or
        # dual-loop) instead of re-profiling per engine.
        # name / metrics / tracer: observability — `name` labels this
        # engine's series and spans (the cluster passes the replica name);
        # metrics is a core.MetricsRegistry, tracer a core.tracing.Tracer.
        # Both default to None = every emission site is skipped (the
        # events_on zero-overhead pattern).
        self.cfg = cfg
        self.ecfg = ecfg = ecfg if ecfg is not None else EngineConfig()
        self.params = params if params is not None else init_params(
            jax.random.PRNGKey(seed), cfg)
        self.router = make_router(ecfg.governor.lower() != "defaultnv")
        self.plant = plant if plant is not None else PlantModel(
            cfg=plant_cfg or cfg, hw=hw, n_chips=1, seed=seed)
        if controller is not None:
            self.controller = controller
        elif ecfg.governor.lower() == "greenllm":
            table = decode_table if decode_table is not None else \
                profile_decode_table(self.plant)
            self.controller = DualLoopController(hw, table)
        else:
            self.controller = MaxFreqController(hw)

        B = ecfg.max_batch
        # serving mesh (None = classic single-device plane).  Built before
        # any device allocation so params/caches/slot vectors land sharded.
        self.mesh = None
        self._shd = NOSHARD
        if ecfg.mesh is not None:
            self._validate_mesh(cfg, ecfg)
            from repro.launch.mesh import make_serving_mesh
            self.mesh = make_serving_mesh(*ecfg.mesh)
            self._shd = make_serving_shard_ctx(self.mesh)
            from jax.sharding import NamedSharding, PartitionSpec
            self._dp_rows = NamedSharding(self.mesh, PartitionSpec("data"))
            self._dp_keys = NamedSharding(self.mesh,
                                          PartitionSpec("data", None))
            specs, _ = serving_param_specs(cfg, self.mesh)
            self.params = jax.device_put(self.params,
                                         named(self.mesh, specs))
        # paged mode needs chunking (preemption resume replays arbitrary-
        # length contexts); tracked engine-side, the caller's config is
        # never mutated
        self._chunked = bool(ecfg.chunked_prefill or ecfg.paged)
        if ecfg.paged:
            ps = ecfg.page_size
            self._max_pages = -(-ecfg.max_len // ps)
            n_pages = ecfg.num_pages or (B * self._max_pages + 1)
            if self.mesh is not None:
                # auto-sized pools round up so the page axis stays divisible
                dp = ecfg.mesh[0]
                n_pages = -(-n_pages // dp) * dp
            self.pager = PageAllocator(n_pages, ps, B, self._max_pages)
            if self.mesh is not None:
                # (max_streams, max_pages) table rows shard along 'data'
                self.pager.device_sharding = self._dp_keys
            pool = (n_pages, ps)
        else:
            self.pager = None
            pool = None
        # how paged decode blocks attend: the Pallas kernel where the
        # program is lowered for a TPU, else the dense gather (the choice
        # itself is made inside the traced program; this only reports it)
        devices = next(iter(jax.tree.leaves(self.params))).devices()
        self._attn = "kernel" if (
            ecfg.paged and paged_decode_kernel_ok(cfg, self._shd)
            and {d.platform for d in devices} == {"tpu"}) else "gather"
        self.caches = init_cache(cfg, B, ecfg.max_len,
                                 dtype=jnp.dtype(ecfg.cache_dtype),
                                 paged_pool=pool)
        if self.mesh is not None:
            self.caches = shard_serving_caches(self.caches, self.mesh)
        # prefix sharing is only sound when *every* cache leaf is a page
        # pool: ring buffers and recurrent states carry per-position context
        # outside the pages, so a shared chain would not reconstruct the
        # stream.  Hybrid models keep the cache object (counters report the
        # misses) but never share or register.
        self._cacheable = ecfg.prefix_cache and all(
            is_paged(d) for stage in self.caches for d in stage)
        self.prefix_cache = PrefixCache(
            self.pager, ecfg.prefix_cache_pages) \
            if ecfg.prefix_cache else None
        self.active: Dict[int, _Stream] = {}
        self.prefilling: Dict[int, _ChunkState] = {}
        self.free_slots = list(range(B))
        self.pending: List[Request] = []
        self.vtime = 0.0
        self.energy_j = 0.0
        # per-phase accounting (matches sim.replay.Metrics: prefill vs decode
        # energy and token counts so real-engine and simulator runs compare)
        self.prefill_energy_j = 0.0
        self.decode_energy_j = 0.0
        self.idle_energy_j = 0.0    # billed when waiting on future arrivals
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self._occupancy = OccupancyMeter()   # pool-pressure telemetry
        self._order = 0
        self._tbt: Dict[int, List[float]] = {}
        self._completed = 0
        self._preempted = 0
        self._cancelled = 0
        self._failed = 0     # given up via fail() (watchdog / crash cleanup)
        self._shed = 0       # dropped by deadline-aware admission
        self._imported = 0   # adopted handoffs (report().migrated);
        #                      exports are counted by the cluster's Replica
        self.requests: List[Request] = []  # everything this engine has seen
        self._events: List = []     # buffered stream events (drain_events)
        # False -> skip event buffering entirely (serving.api.Server clears
        # this unless an on_event callback is installed)
        self.events_on = True
        # observability: always-on host-sync audit counter (one int += per
        # block — the zero-overhead regression test compares it across
        # sinks-on/sinks-off runs), plus optional metric/trace sinks
        self.name = name
        self._host_drains = 0
        self._decode_blocks = 0         # decode block programs dispatched
        self._kernel_blocks = 0         # ... of them through the paged kernel
        self.metrics = None
        self.tracer = None
        self.ledger = None          # core.attribution.EnergyLedger (opt-in)
        self._cf = None             # counterfactual pricer (with ledger)
        self._m = None              # bound metric children (when metrics)
        self._obs_tbt = None        # engine-level TBT window for p95/p99
        if metrics is not None or tracer is not None or ledger is not None:
            self.install_observability(metrics, tracer, ledger)

        # device-resident decode state (slot-native path)
        self._tok = self._row_pin(jnp.zeros((B,), jnp.int32))
        self._pos = self._row_pin(jnp.zeros((B,), jnp.int32))
        self._active_host = np.zeros(B, bool)
        self._active = self._row_pin(jnp.asarray(self._active_host))
        # per-slot sampling lanes: temperature / top-k / top-p vectors plus
        # each row's PRNG *base* key.  Draw subkeys fold the token position
        # into the base lane (see _row_subkeys), so lanes never advance —
        # a stream's i-th draw is a pure function of (lane, position), which
        # is what makes migration and recompute-on-resume replay identical
        # draws.  Rows are written at slot assignment (admission / chunked
        # start / import), read only inside the jitted kernels.
        self._temps = self._row_pin(jnp.zeros((B,), jnp.float32))
        self._topk = self._row_pin(jnp.zeros((B,), jnp.int32))
        self._topp = self._row_pin(jnp.ones((B,), jnp.float32))
        self._keys = self._row_pin(jnp.zeros((B, 2), jnp.uint32))
        self._sampled_host = np.zeros(B, bool)  # host mirror of temps > 0
        self._base_key = jax.random.PRNGKey(seed + 1)  # unseeded-lane source

        # prefill buckets: powers of two, capped by the smallest attention
        # buffer (window / long-context ring) — longer prompts take the
        # reference path — and by the prompt truncation length.
        attn_kinds = [k for k in set(cfg.layer_kinds)
                      if k in (FULL_ATTN, LOCAL_ATTN)]
        slot_cap = min([attn_buffer_len(cfg, k, ecfg.max_len, False)
                        for k in attn_kinds] or [ecfg.max_len])
        if slot_cap < ecfg.min_bucket:
            raise ValueError(
                f"min_bucket={ecfg.min_bucket} exceeds the smallest "
                f"attention buffer ({slot_cap} positions — sliding-window / "
                f"long-context ring) of model '{cfg.name}': no prefill "
                "bucket would fit a slot write; lower EngineConfig.min_bucket"
            )
        cap = min(slot_cap, max(ecfg.max_len // 2, 1))
        self.buckets: List[int] = []
        b = ecfg.min_bucket
        while b <= cap:
            self.buckets.append(b)
            b *= 2
        if not self.buckets or self.buckets[-1] != cap:
            # close the (largest_pow2, cap] gap: prompts are truncated to at
            # most cap, so with a final cap-sized bucket nothing falls back
            # to the legacy path for length alone
            self.buckets.append(cap)

        # context buckets for decode: attention cost is O(cache buffer), so
        # the decode kernel runs over the cache sliced to the smallest bucket
        # covering every active position in the block, then splices back.
        # Paged mode slices the *page table* instead, so buckets are rounded
        # up to page multiples (compile count stays |ctx_buckets|).
        self.ctx_buckets: List[int] = []
        b = max(ecfg.min_bucket, 32)
        while b < ecfg.max_len:
            self.ctx_buckets.append(b)
            b *= 2
        self.ctx_buckets.append(ecfg.max_len)
        if ecfg.paged:
            ps = ecfg.page_size
            self.ctx_buckets = sorted({-(-c // ps) * ps
                                       for c in self.ctx_buckets})
        # chunked prefill: chunk length = the largest admission bucket, so
        # every chunk reuses the existing bucket set (no extra compiles)
        self.chunk_len = self.buckets[-1]
        # fixed block sizes (steps fused into one jitted lax.scan) bound the
        # (ctx_bucket, k) compile count to |ctx_buckets| * |K_BLOCKS|
        self._k_blocks = tuple(sorted({1, 4, 16, ecfg.decode_block},
                                      reverse=True))
        # (ctx, kb) kernels this engine has already dispatched: wall-clock
        # accounting excludes a kernel's first block (XLA compile time would
        # otherwise be billed as decode latency and wreck the controller)
        self._warmed: set = set()

    # -- serving mesh ----------------------------------------------------------
    @staticmethod
    def _validate_mesh(cfg: ModelConfig, ecfg: "EngineConfig") -> None:
        """Model-dependent mesh divisibility, rejected with an actionable
        message instead of an opaque XLA sharding failure deep inside the
        first jitted kernel.  (Model-independent checks — max_batch/num_pages
        vs dp — live in ``EngineConfig.__post_init__``.)"""
        dp, tp = ecfg.mesh
        if tp > 1 and cfg.num_heads % tp:
            raise ValueError(
                f"model '{cfg.name}' has num_heads={cfg.num_heads}, not "
                f"divisible by the model axis tp={tp}: attention heads "
                "partition over 'model' — pick tp from the divisors of "
                "num_heads (or tp=1)")
        if tp > 1 and cfg.is_moe and cfg.num_experts % tp:
            raise ValueError(
                f"MoE model '{cfg.name}' has num_experts={cfg.num_experts}, "
                f"not divisible by the model axis tp={tp}: expert weights "
                "place each expert on exactly one model shard — pick tp "
                "from the divisors of num_experts (or tp=1)")

    def _row_pin(self, x):
        """Pin a per-slot device vector (leading dim max_batch) to its
        data-axis sharding.  Functional updates (``.at[slot].set``) and
        host re-uploads can silently drop to single-device placement; the
        re-put is a device-to-device no-op when the sharding already
        matches, and keeping operand shardings stable is what holds the
        kernel compile count at its single-device budget.  Identity off
        mesh."""
        if self.mesh is None:
            return x
        return jax.device_put(
            x, self._dp_keys if x.ndim >= 2 else self._dp_rows)

    def _pin_caches(self, caches):
        """Re-pin a cache pytree after an eager host-side rebuild (legacy
        splice, handoff import).  Device-to-device no-op when layouts already
        match; identity off mesh."""
        if self.mesh is None:
            return caches
        from repro.launch.shardings import shard_serving_caches
        return shard_serving_caches(caches, self.mesh)

    def _sync_active(self) -> None:
        """Re-upload the host active mask (one small transfer per stream
        join/leave, the pre-mesh cadence; sharded along 'data' on a mesh)."""
        self._active = self._row_pin(jnp.asarray(self._active_host))

    # -- observability ---------------------------------------------------------
    def install_observability(self, metrics=None, tracer=None,
                              ledger=None) -> None:
        """Install metric / trace / attribution sinks (``Server(metrics=...,
        tracer=..., ledger=...)`` and the cluster route through here).  Any
        may be None; with all None every emission site below is a skipped
        ``is not None`` check — the PR 5 ``events_on`` zero-overhead
        pattern.  Emission rides the existing host-sync points only:
        publishing reads host floats the engine already computed, never a
        device value.  ``ledger`` (a ``core.attribution.EnergyLedger``,
        shareable across replicas) mirrors every billed joule — and prices
        the same intervals at max frequency through a noiseless plant
        clone, so the live plant's RNG (and hence the run) is untouched."""
        self.metrics = metrics
        self.tracer = tracer
        if ledger is not None:
            self.ledger = ledger
            ledger.register(self.name)
            self._cf = CounterfactualPricer(self.plant)
        if tracer is not None:
            self.controller.on_decision = tracer.bind(self.name)
        if metrics is not None:
            self._init_metrics(metrics)

    def _init_metrics(self, reg) -> None:
        """Bind this replica's metric children once (hot paths touch bound
        children — a float add — not the label-resolution path).  Metric
        names are a stable API; see README "Observability"."""
        r = self.name
        ev = reg.counter("greenllm_requests_total",
                         "request lifecycle events", ("replica", "event"))
        slo = reg.counter("greenllm_slo_total",
                          "per-request SLO verdicts at finish",
                          ("replica", "kind", "outcome"))
        self._m = {
            "ev": {k: ev.labels(replica=r, event=k) for k in
                   ("submitted", "completed", "cancelled", "failed", "shed",
                    "preempted", "imported", "exported")},
            "slo": {(k, o): slo.labels(replica=r, kind=k, outcome=o)
                    for k in ("ttft", "tbt") for o in ("pass", "miss")},
            "tok_pf": reg.counter("greenllm_tokens_total",
                                  "tokens processed by phase",
                                  ("replica", "phase"))
                         .labels(replica=r, phase="prefill"),
            "tok_dec": reg.counter("greenllm_tokens_total", "",
                                   ("replica", "phase"))
                          .labels(replica=r, phase="decode"),
            "e_pf": reg.counter("greenllm_energy_joules_total",
                                "energy by phase (virtual-clock accounting)",
                                ("replica", "phase"))
                       .labels(replica=r, phase="prefill"),
            "e_dec": reg.counter("greenllm_energy_joules_total", "",
                                 ("replica", "phase"))
                        .labels(replica=r, phase="decode"),
            "e_idle": reg.counter("greenllm_energy_joules_total", "",
                                  ("replica", "phase"))
                         .labels(replica=r, phase="idle"),
            "e_saved": reg.counter(
                "greenllm_energy_saved_joules_total",
                "counterfactual joules saved vs max frequency (estimate)",
                ("replica",)).labels(replica=r),
            "freq": reg.gauge("greenllm_frequency_mhz",
                              "controller SM clock set point", ("replica",))
                       .labels(replica=r),
            "occ": reg.gauge("greenllm_page_occupancy",
                             "KV page-pool occupancy [0,1]", ("replica",))
                      .labels(replica=r),
            "frag": reg.gauge("greenllm_page_fragmentation",
                              "last-page slack fraction", ("replica",))
                       .labels(replica=r),
            "q_pending": reg.gauge("greenllm_queue_depth",
                                   "streams by lifecycle stage",
                                   ("replica", "queue"))
                            .labels(replica=r, queue="pending"),
            "q_prefill": reg.gauge("greenllm_queue_depth", "",
                                   ("replica", "queue"))
                            .labels(replica=r, queue="prefilling"),
            "q_active": reg.gauge("greenllm_queue_depth", "",
                                  ("replica", "queue"))
                           .labels(replica=r, queue="active"),
            "ttft": reg.histogram("greenllm_ttft_seconds",
                                  "time to first token", ("replica",),
                                  buckets=(0.05, 0.1, 0.2, 0.4, 0.8, 1.6,
                                           3.2, 6.4))
                       .labels(replica=r),
            "tbt": reg.histogram("greenllm_tbt_seconds",
                                 "time between tokens", ("replica",),
                                 buckets=(0.005, 0.01, 0.02, 0.04, 0.08,
                                          0.1, 0.15, 0.25, 0.5))
                      .labels(replica=r),
            "p95": reg.gauge("greenllm_tbt_p95_seconds",
                             "sliding-window p95 TBT", ("replica",))
                      .labels(replica=r),
            "p99": reg.gauge("greenllm_tbt_p99_seconds",
                             "sliding-window p99 TBT", ("replica",))
                      .labels(replica=r),
        }
        if self.tracer is not None:
            # ring-buffer overflow in the tracer is otherwise silent
            # truncation; surface the drop counts where dashboards look
            self._m["drop_spans"] = reg.gauge(
                "greenllm_tracer_dropped_spans",
                "trace spans lost to ring-buffer overflow").labels()
            self._m["drop_decisions"] = reg.gauge(
                "greenllm_tracer_dropped_decisions",
                "DVFS decisions lost to ring-buffer overflow").labels()
        if self.ecfg.prefix_cache:
            # registered only when caching is on: a bare engine's metric
            # families are byte-identical to pre-cache exposition
            self._m["pc_hits"] = reg.counter(
                "greenllm_prefix_cache_hits_total",
                "admissions that matched >= 1 cached prompt page",
                ("replica",)).labels(replica=r)
            self._m["pc_misses"] = reg.counter(
                "greenllm_prefix_cache_misses_total",
                "admissions with no cached prefix", ("replica",)) \
                .labels(replica=r)
            self._m["pc_evictions"] = reg.counter(
                "greenllm_prefix_cache_evictions_total",
                "cache entries reclaimed under pool pressure",
                ("replica",)).labels(replica=r)
            self._m["pc_shared"] = reg.gauge(
                "greenllm_prefix_cache_shared_pages",
                "cached pages currently shared with live streams",
                ("replica",)).labels(replica=r)
        # published-so-far totals: counters publish deltas at block cadence
        self._pub = {"e_pf": 0.0, "e_dec": 0.0, "e_idle": 0.0,
                     "e_saved": 0.0, "tok_pf": 0, "tok_dec": 0,
                     "pc_hits": 0, "pc_misses": 0, "pc_evictions": 0}
        self._obs_tbt = TBTMeter(horizon=1.0)

    def _publish_metrics(self) -> None:
        """Flush gauges + counter deltas and stamp a timeline snapshot at
        the current virtual time.  Called only from existing host-side
        points (end of a decode block, after prefill/idle accounting) —
        this is bookkeeping over already-host-resident floats."""
        m = self._m
        if m is None:
            return
        pub = self._pub
        for key, cur in (("e_pf", self.prefill_energy_j),
                         ("e_dec", self.decode_energy_j),
                         ("e_idle", self.idle_energy_j),
                         ("tok_pf", self.prefill_tokens),
                         ("tok_dec", self.decode_tokens)):
            d = cur - pub[key]
            if d > 0:
                m[key].inc(d)
                pub[key] = cur
        if self.ledger is not None:
            cur = self.ledger.replica_saved_j(self.name)
            d = cur - pub["e_saved"]
            if d > 0:                   # counters are monotone; savings can
                m["e_saved"].inc(d)     # dip (noise near f_max) — hold then
                pub["e_saved"] = cur
        if self.tracer is not None and "drop_spans" in m:
            m["drop_spans"].set(self.tracer.dropped_spans)
            m["drop_decisions"].set(self.tracer.dropped_decisions)
        m["freq"].set(self.controller.freq)
        m["q_pending"].set(len(self.pending))
        m["q_prefill"].set(len(self.prefilling))
        m["q_active"].set(len(self.active))
        if self.pager is not None:
            occ = self.pager.occupancy()
            m["occ"].set(occ["occupancy"])
            m["frag"].set(occ["fragmentation"])
        if self.prefix_cache is not None and "pc_hits" in m:
            pc = self.prefix_cache
            for key, cur in (("pc_hits", pc.hits),
                             ("pc_misses", pc.misses),
                             ("pc_evictions", pc.evictions)):
                d = cur - pub[key]
                if d > 0:
                    m[key].inc(d)
                    pub[key] = cur
            m["pc_shared"].set(pc.shared_pages())
        if self._obs_tbt is not None and len(self._obs_tbt):
            p95 = self._obs_tbt.p95(self.vtime)
            if p95 > 0.0:               # nan-safe: hold last on empty window
                m["p95"].set(p95)
                m["p99"].set(self._obs_tbt.p99(self.vtime))
        self.metrics.record_snapshot(self.vtime)

    def _obs_finish(self, req: Request) -> None:
        """Score a FINISHED request's SLO verdicts into the counters (the
        same targets ``core.report.slo_pass_metrics`` scores post-hoc)."""
        m = self._m
        if m is None:
            return
        m["ev"]["completed"].inc()
        slo = self.ecfg.slo
        if req.first_token >= 0:
            ttft = req.first_token - req.arrival
            ok = ttft <= slo.ttft_target(req.cls or "S")
            m["slo"][("ttft", "pass" if ok else "miss")].inc()
        recs = self._tbt.get(req.rid)
        if recs:
            ok = float(np.percentile(recs, 95)) <= slo.tbt_target
            m["slo"][("tbt", "pass" if ok else "miss")].inc()

    def evict(self, rid: int) -> bool:
        """Backend protocol: drop a *terminal* request's bookkeeping (its
        report row and TBT records) so a long-lived server doesn't grow
        with total traffic served.  Counters and already-published metrics
        are unaffected; ``report()`` afterwards no longer includes the
        request.  Returns False for unknown or non-terminal requests."""
        for i, req in enumerate(self.requests):
            if req.rid == rid:
                if not req.state.terminal:
                    return False
                self.requests.pop(i)
                self._tbt.pop(rid, None)
                return True
        # already gone from the report rows; still drop stray TBT records
        return self._tbt.pop(rid, None) is not None

    # -- request intake --------------------------------------------------------
    def submit(self, req: Request, prompt_tokens: Optional[np.ndarray] = None):
        if not self.ecfg.slot_native and self._resolve_sampling(req)[0] > 0.0:
            # the legacy data plane decodes host-side argmax only; silently
            # dropping a request's sampling params would be worse than the
            # old engine-global temperature mismatch error
            raise ValueError(
                "per-request sampling (temperature > 0) requires the "
                "slot-native data plane; the legacy slot_native=False "
                "baseline decodes greedily")
        if not req.cls:      # a cluster dispatcher may have classified already
            req.cls = self.router.class_names[
                self.router.classify(req.prompt_len)]
        if prompt_tokens is None:
            rng = np.random.default_rng(req.rid)
            prompt_tokens = rng.integers(
                0, self.cfg.vocab_size, size=max(req.prompt_len, 1))
        req.prompt = np.asarray(prompt_tokens, np.int32)[-self.ecfg.max_len // 2:]
        req.state = RequestState.QUEUED
        self.pending.append(req)
        self.requests.append(req)
        if self._m is not None:
            self._m["ev"]["submitted"].inc()
        if self.tracer is not None:
            self.tracer.instant("submit", req.rid, self.vtime, self.name,
                                prompt_len=req.prompt_len, cls=req.cls)

    # -- per-slot sampling lanes ------------------------------------------------
    def _emit(self, ev) -> None:
        """Buffer a stream event for ``drain_events`` consumers — skipped
        entirely when nobody listens (``events_on`` False)."""
        if self.events_on:
            self._events.append(ev)

    def _resolve_sampling(self, req: Request):
        """(temperature, top_k, top_p) for a request.  Sampling is purely
        per-request: ``temperature=None`` means greedy argmax, same as 0
        (the old ``EngineConfig.greedy``/``temperature`` engine-wide
        defaults are gone)."""
        sp = req.sampling
        if sp is None or sp.temperature is None:
            return 0.0, (int(sp.top_k) if sp else 0), \
                (float(sp.top_p) if sp else 1.0)
        return float(sp.temperature), int(sp.top_k), float(sp.top_p)

    def _lane_for(self, req: Request) -> np.ndarray:
        """The request's PRNG base lane, created on *first* admission
        (seeded requests: ``PRNGKey(seed)``; unseeded: the engine key folded
        with the rid) and pinned on the request so preemption/recompute and
        migration reuse the same draw stream instead of resampling."""
        if req.rng_lane is None:
            sp = req.sampling
            if sp is not None and sp.seed is not None:
                lane = jax.random.PRNGKey(sp.seed)
            else:
                lane = jax.random.fold_in(self._base_key, req.rid)
            req.rng_lane = np.asarray(lane, np.uint32)
        return req.rng_lane

    def _set_slot_sampling(self, slot: int, req: Request):
        """Write a stream's sampling lane into row ``slot`` of the device
        vectors (admission-time host work, amortized like the prompt copy —
        the decode loop itself never touches these from the host).  Returns
        the resolved (temperature, top_k, top_p) for callers that also
        sample host-side."""
        temp, top_k, top_p = self._resolve_sampling(req)
        self._temps = self._row_pin(self._temps.at[slot].set(temp))
        self._topk = self._row_pin(self._topk.at[slot].set(top_k))
        self._topp = self._row_pin(self._topp.at[slot].set(top_p))
        self._keys = self._row_pin(self._keys.at[slot].set(
            jnp.asarray(self._lane_for(req), jnp.uint32)))
        self._sampled_host[slot] = temp > 0.0
        return temp, top_k, top_p

    def _account_prefill_tokens(self, n_tokens: int, first: bool,
                                req: Request):
        """Bill ``n_tokens`` of prefill work (one-shot prompt or one chunk) to
        the prefill phase.  Chunk billing approximates attention-to-past as
        part of the per-chunk latency fit (Sarathi-style accounting)."""
        t_pf = self.plant.prefill_latency(n_tokens, self.controller.freq)
        p_pf = self.plant.prefill_power(n_tokens, self.controller.freq, t_pf)
        self.energy_j += t_pf * p_pf
        self.prefill_energy_j += t_pf * p_pf
        self.prefill_tokens += n_tokens
        self.vtime += t_pf
        if self.ledger is not None:
            # the prefilling stream is this interval's only resident; the
            # mirror sees the identical float the counters above added
            e = t_pf * p_pf
            self.ledger.record_prefill(
                self.name, req.rid, e, tokens=n_tokens,
                saved_j=self._cf.prefill_j(n_tokens) - e)
        if first:
            req.prefill_start = self.vtime - t_pf

    def _account_prefill(self, req: Request):
        self._account_prefill_tokens(req.prompt_len, True, req)
        req.first_token = self.vtime

    def _start_stream(self, req: Request, slot: int, tok: int, pos: int,
                      resumed: bool = False):
        self._order += 1
        st = _Stream(req, slot, tok, pos, self._order)
        if not resumed:
            req.tokens.append(tok)
            req.tokens_emitted = 1
            self._emit(TokenEvent(req.rid, self.vtime, (tok,), 1))
            if self._m is not None and req.first_token >= 0:
                self._m["ttft"].observe(
                    max(req.first_token - req.arrival, 0.0))
        req.state = RequestState.DECODING
        self._emit(StateEvent(req.rid, self.vtime, RequestState.DECODING))
        self.active[slot] = st
        self._active_host[slot] = True
        self._sync_active()

    def _pt_rows(self, slot: int, upto: int):
        """(1, n_ctx) page-table row covering positions < the smallest ctx
        bucket >= upto (static widths bound compile count)."""
        ctx = next((c for c in self.ctx_buckets if c >= upto),
                   self.ctx_buckets[-1])
        n_ctx = min(-(-ctx // self.ecfg.page_size), self._max_pages)
        return self.pager.table_device()[slot:slot + 1, :n_ctx]

    def _admit_slot(self, req: Request, slot: int):
        prompt = req.prompt
        L = len(prompt)
        bucket = next(b for b in self.buckets if b >= L)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :L] = prompt
        with host_span("engine.prefill", rid=req.rid, tokens=L,
                       bucket=bucket):
            pt_row = None
            if self.pager is not None:
                ok = self.pager.ensure(slot, L)      # gated by _admit
                assert ok, "admission gate let an unallocatable prompt through"
                pt_row = self._pt_rows(slot, bucket)
            self._set_slot_sampling(slot, req)
            self._tok, self.caches, self._pos = _prefill_kernel(
                self.cfg, self._shd, bool(self._sampled_host[slot]),
                self.params, jnp.asarray(padded), jnp.asarray(L, jnp.int32),
                self.caches, jnp.asarray(slot, jnp.int32), pt_row,
                self._tok, self._pos, self._keys, self._temps, self._topk,
                self._topp)
            t0 = self.vtime
            self._account_prefill(req)
            if self.tracer is not None:
                self.tracer.span("prefill", req.rid, t0, self.vtime,
                                 self.name, tokens=L, bucket=bucket)
            self._register_prefix(req, slot, L)
            self._publish_metrics()
            # one tiny host read per admission (the first sampled token id)
            self._start_stream(req, slot, int(self._tok[slot]), L)

    def _admit_legacy(self, req: Request, slot: int):
        """Reference path: per-request prefill + host-side batch-cache splice.

        Used for prompts that exceed an attention ring buffer (bucketed slot
        writes need S_pad <= buf_len) and by ``slot_native=False``.
        """
        toks = jnp.asarray(req.prompt, jnp.int32)[None]
        caches = init_cache(self.cfg, 1, self.ecfg.max_len,
                            dtype=jnp.dtype(self.ecfg.cache_dtype))
        logits, caches, pos = prefill(self.params, self.cfg, toks, caches)
        self.caches = self._pin_caches(jax.tree.map(
            lambda full, one: full.at[:, slot:slot + 1].set(one)
            if full.ndim >= 2 else full, self.caches, caches))
        temp, top_k, top_p = self._set_slot_sampling(slot, req)
        sub = jax.random.fold_in(
            jnp.asarray(self._lane_for(req), jnp.uint32), len(req.prompt))
        tok = int(sample_tokens_batched(
            logits, jnp.asarray([temp], jnp.float32),
            jnp.asarray([top_k], jnp.int32),
            jnp.asarray([top_p], jnp.float32), sub[None])[0])
        self._tok = self._row_pin(self._tok.at[slot].set(tok))
        self._pos = self._row_pin(self._pos.at[slot].set(len(req.prompt)))
        t0 = self.vtime
        self._account_prefill(req)
        if self.tracer is not None:
            self.tracer.span("prefill", req.rid, t0, self.vtime, self.name,
                             tokens=len(req.prompt), legacy=True)
        self._publish_metrics()
        self._start_stream(req, slot, tok, len(req.prompt))

    def _admit(self):
        with host_span("engine.admit") as span:
            span.set_metadata(admitted=self._admit_fifo())

    def _admit_fifo(self) -> int:
        """Admit arrived requests from the FIFO head while slots and pages
        allow; returns how many were admitted."""
        admitted = 0
        while self.pending and self.free_slots:
            req = self.pending[0]
            if max(req.arrival, req.not_before) > self.vtime + 1e-12:
                break        # FIFO head not arrived yet (online traffic /
                #              crash-recovery gate); the driver jumps the
                #              clock when fully idle
            if self.ecfg.shed_past_deadline and req.deadline >= 0 \
                    and self.vtime > req.deadline + 1e-12:
                # deadline already blown before any work started: shed
                # instead of burning prefill+decode on a guaranteed miss
                # (load shedding under overload — the queue behind the head
                # is exactly what the energy would be stolen from)
                self.pending.pop(0)
                self._mark_shed(req)
                continue
            resume = bool(req.tokens)        # preempted stream: recompute
            ctx_toks = req.prompt if not resume else np.concatenate(
                [req.prompt, np.asarray(req.tokens[:-1], np.int32)])
            need = min(len(ctx_toks), self.chunk_len)
            if self.pager is not None and not self.pager.can_admit(need):
                # cached prefixes are strictly less valuable than admitting
                # live work: evict before stalling the FIFO head
                if not (self._reclaim_cached()
                        and self.pager.can_admit(need)):
                    break                    # FIFO head-of-line: wait for pages
            self.pending.pop(0)
            slot = self.free_slots.pop(0)
            # longest-cached-prefix match (after the admission gates: a
            # lookup that can't admit must not skew hit/miss counters).
            # Resumed streams match too — their prompt pages are often
            # still cached, so recompute-on-resume skips them as well.
            hit_pages: List[int] = []
            hit_tok = 0
            if self._cacheable:
                hit_pages, hit_tok = self.prefix_cache.lookup(ctx_toks)
            if self.tracer is not None:
                self.tracer.span("queue", req.rid,
                                 max(req.arrival, req.not_before),
                                 self.vtime, self.name, slot=slot,
                                 resume=resume)
            admitted += 1
            if not self.ecfg.slot_native:
                self._admit_legacy(req, slot)
            elif hit_tok or resume or len(ctx_toks) > self.buckets[-1]:
                if self._chunked:
                    self._start_chunked(req, slot, ctx_toks, resume,
                                        hit_pages, hit_tok)
                else:
                    self._admit_legacy(req, slot)
            else:
                self._admit_slot(req, slot)
        return admitted

    def _start_chunked(self, req: Request, slot: int, ctx_toks: np.ndarray,
                       resume: bool, hit_pages: Optional[List[int]] = None,
                       hit_tok: int = 0):
        """Admit via chunked prefill: the stream owns ``slot`` now but joins
        the decode batch only after its last chunk (``_advance_chunks``).

        A prefix-cache hit (``hit_tok`` > 0) seeds the slot's chain with the
        shared pages and starts chunking at ``hit_tok`` instead of 0 — the
        matched tokens' K/V is the cached bits, never recomputed.  When the
        match isn't page-aligned (a fully-covered prompt, capped so one real
        token remains for the first-token logits) the partially-reused last
        page is copied-on-write first: the chunk at ``hit_tok`` rewrites that
        page's final position, and shared pages are immutable."""
        if hit_tok:
            hit_tok = self._share_prefix(slot, hit_pages, hit_tok)
        self._order += 1
        self._set_slot_sampling(slot, req)
        cs = _ChunkState(
            req, slot, np.asarray(ctx_toks, np.int32),
            resume_tok=req.tokens[-1] if resume else None, order=self._order)
        cs.start = hit_tok
        self.prefilling[slot] = cs
        req.state = RequestState.PREFILLING
        self._emit(StateEvent(req.rid, self.vtime, RequestState.PREFILLING))

    def _share_prefix(self, slot: int, pages: List[int], hit_tok: int) -> int:
        """Adopt cached pages into ``slot``'s chain (refcount bump, no data
        movement), CoW the last page if the hit ends mid-page, and seed the
        device position so the held-position write of the still-inactive row
        lands at ``hit_tok`` (inside the private/unallocated region, never a
        shared page).  Returns the effective hit length — 0 when the CoW
        cannot get a page even after reclaiming, in which case the share is
        rolled back and admission proceeds as a miss."""
        ps = self.ecfg.page_size
        self.pager.share_chain(slot, pages)
        if hit_tok % ps:
            # the hit ends inside pages[-1]: CoW before the chunk at
            # hit_tok rewrites its final position
            old = pages[-1]
            new = self.pager.cow_page(slot, len(pages) - 1)
            if new is None and self._reclaim_cached():
                new = self.pager.cow_page(slot, len(pages) - 1)
            if new is None:
                self.pager.free_chain(slot)     # roll back: admit as a miss
                return 0
            if new != old:
                self.caches = _page_copy_kernel(
                    self.caches, jnp.asarray(old, jnp.int32),
                    jnp.asarray(new, jnp.int32))
        self._pos = self._row_pin(self._pos.at[slot].set(hit_tok))
        if self.tracer is not None:
            self.tracer.instant("prefix_hit", -1, self.vtime, self.name,
                                pages=len(pages), tokens=hit_tok)
        return hit_tok

    def _reclaim_cached(self) -> bool:
        """Evict up to a chunk's worth of LRU cache-only pages back to the
        pool; False when caching is off or nothing is evictable (the caller
        falls through to preemption / head-of-line wait)."""
        if self.prefix_cache is None:
            return False
        return self.prefix_cache.reclaim(
            -(-self.chunk_len // self.ecfg.page_size)) > 0

    def _register_prefix(self, req: Request, slot: int, upto: int) -> None:
        """Publish the fully-written prompt pages of ``slot``'s chain into
        the cache (dedup by digest: already-known pages are LRU-touched,
        not re-retained)."""
        if not self._cacheable or req.prompt is None:
            return
        chain = self.pager.chains.get(slot)
        if chain:
            self.prefix_cache.register(req.prompt, chain,
                                       min(upto, len(req.prompt)))

    def _advance_chunks(self) -> bool:
        """Process one chunk for every mid-prefill stream (called once per
        decode block: chunked admission interleaves with decoding instead of
        stalling it for a full long prompt).  Returns True if any advanced."""
        progressed = False
        finished: List[int] = []
        for slot, cs in list(self.prefilling.items()):
            if slot not in self.prefilling:
                continue        # preempted by a later-iterated stream's growth
            chunk = cs.tokens[cs.start: cs.start + self.chunk_len]
            if self.pager is not None:
                ok = self.pager.ensure(slot, cs.start + len(chunk))
                while not ok and (self._reclaim_cached()
                                  or self._preempt_for_pages(exclude=slot)):
                    ok = self.pager.ensure(slot, cs.start + len(chunk))
                if not ok:
                    continue             # stall this chunk; retry next block
            bucket = next(b for b in self.buckets if b >= len(chunk))
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(chunk)] = chunk
            with host_span("engine.chunk", rid=cs.req.rid, start=cs.start,
                           tokens=len(chunk)):
                pt_row = None
                if self.pager is not None:
                    pt_row = self._pt_rows(slot, cs.start + bucket)
                self._tok, self.caches, self._pos = \
                    _chunk_prefill_kernel(
                        self.cfg, self._shd, bool(self._sampled_host[slot]),
                        self.params, jnp.asarray(padded),
                        jnp.asarray(cs.start, jnp.int32),
                        jnp.asarray(len(chunk), jnp.int32),
                        self.caches, jnp.asarray(slot, jnp.int32), pt_row,
                        self._tok, self._pos, self._keys, self._temps,
                        self._topk, self._topp)
                # resumed streams keep their original prefill_start /
                # first_token
                t0 = self.vtime
                self._account_prefill_tokens(
                    len(chunk), not cs.billed and cs.resume_tok is None,
                    cs.req)
                cs.billed = True
                if self.tracer is not None:
                    self.tracer.span("prefill_chunk", cs.req.rid, t0,
                                     self.vtime, self.name,
                                     chunk_start=cs.start, tokens=len(chunk))
            cs.start += len(chunk)
            self._register_prefix(cs.req, slot, cs.start)
            progressed = True
            if cs.start >= len(cs.tokens):
                finished.append(slot)
        for slot in finished:
            cs = self.prefilling.pop(slot, None)
            if cs is None:
                continue        # preempted after its last chunk this round:
                #                 the request recomputes from the queue head
            if cs.resume_tok is not None:
                # recomputed stream: next token was already sampled before
                # preemption; restore it instead of the chunk's provisional
                self._tok = self._row_pin(self._tok.at[slot].set(cs.resume_tok))
                self._start_stream(cs.req, slot, cs.resume_tok,
                                   len(cs.tokens), resumed=True)
            else:
                cs.req.first_token = self.vtime
                self._start_stream(cs.req, slot, int(self._tok[slot]),
                                   len(cs.tokens))
        if progressed:
            self._publish_metrics()
        return progressed

    def _preempt_for_pages(self, exclude: Optional[int] = None) -> bool:
        """Free the youngest stream's pages and requeue it for recompute-on-
        resume (emitted tokens are replayed through chunked prefill).

        Victims are chosen youngest-first by admission order across *both*
        phases: decoding streams and mid-chunked-prefill streams — a pool
        full of prefilling streams must not deadlock a grower (``exclude``
        keeps a chunk from preempting itself).  A preempted mid-prefill
        stream discards its chunk progress entirely; its request re-enters
        the queue head and re-admits when pages free up.  Returns False when
        there is nothing (else) to preempt.
        """
        order = {s: st.order for s, st in self.active.items()}
        order.update({s: cs.order for s, cs in self.prefilling.items()
                      if s != exclude})
        if not order:
            return False
        slot = max(order, key=order.get)
        if slot in self.active:
            req = self.active.pop(slot).req
        else:
            req = self.prefilling.pop(slot).req
        self._release_slot(slot)
        self.pending.insert(0, req)
        self._preempted += 1
        req.state = RequestState.QUEUED
        self._emit(StateEvent(req.rid, self.vtime, RequestState.QUEUED))
        if self._m is not None:
            self._m["ev"]["preempted"].inc()
        if self.tracer is not None:
            self.tracer.instant("preempt", req.rid, self.vtime, self.name)
        return True

    # -- cancellation / failure ------------------------------------------------
    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it currently lives — queued,
        mid-chunked-prefill, or mid-decode — freeing its slot and page chain
        immediately (the preemption machinery minus the requeue/recompute).
        The recurrent row state is frozen by the inactive mask and the freed
        pages' future held-pos writes land in the scratch page, so surviving
        streams are untouched.  Returns False for unknown or already-terminal
        requests; operates at block granularity like every host-side
        decision (no mid-block aborts, no new host syncs)."""
        return self._terminate(rid, RequestState.CANCELLED)

    def fail(self, rid: int) -> bool:
        """Give up on a request (``Backend.fail``): same clean release as
        ``cancel`` but the terminal state is FAILED — the system's verdict
        (watchdog wall-budget breach, stuck backend, crash cleanup), not the
        caller's.  Tokens already emitted stay readable."""
        return self._terminate(rid, RequestState.FAILED)

    def _terminate(self, rid: int, state: RequestState) -> bool:
        for i, req in enumerate(self.pending):
            if req.rid == rid:
                self.pending.pop(i)
                return self._mark_terminal(req, state)
        for slot, cs in list(self.prefilling.items()):
            if cs.req.rid == rid:
                del self.prefilling[slot]
                self._release_slot(slot)
                return self._mark_terminal(cs.req, state)
        for slot, st in list(self.active.items()):
            if st.req.rid == rid:
                del self.active[slot]
                self._release_slot(slot)
                return self._mark_terminal(st.req, state)
        return False

    def _release_slot(self, slot: int) -> None:
        """Return a slot (and its page chain) to the free pool and drop its
        batch row from the active mask."""
        if self.pager is not None:
            self.pager.free_chain(slot)
        self._active_host[slot] = False
        self._sampled_host[slot] = False
        self._sync_active()
        self.free_slots.append(slot)

    def _mark_terminal(self, req: Request, state: RequestState) -> bool:
        req.state = state
        if state is RequestState.CANCELLED:
            self._cancelled += 1
            kind = "cancelled"
        else:
            self._failed += 1
            kind = "failed"
        self._emit(StateEvent(req.rid, self.vtime, state))
        if self._m is not None:
            self._m["ev"][kind].inc()
        if self.tracer is not None:
            self.tracer.instant("cancel" if kind == "cancelled" else "fail",
                                req.rid, self.vtime, self.name,
                                tokens_emitted=req.tokens_emitted)
        return True

    def _evict_lapsed(self) -> None:
        """Deadline-aware eviction of *admitted* decoding streams (opt-in
        via ``EngineConfig.evict_lapsed``): a stream whose absolute deadline
        has lapsed mid-decode is freed through the same release path as
        ``cancel`` and reported SHED — every further token it would emit is
        guaranteed-late, so its slot, pages, and energy go to streams that
        can still pass.  Block-granular like every host-side decision;
        survivors are untouched (freed pages' held-position writes land in
        the scratch page)."""
        if not self.ecfg.evict_lapsed:
            return
        for slot, st in list(self.active.items()):
            req = st.req
            if req.deadline >= 0 and self.vtime > req.deadline + 1e-12:
                del self.active[slot]
                self._release_slot(slot)
                self._mark_shed(req)

    def _mark_shed(self, req: Request) -> None:
        req.state = RequestState.SHED
        self._shed += 1
        self._emit(StateEvent(req.rid, self.vtime, RequestState.SHED))
        if self._m is not None:
            self._m["ev"]["shed"].inc()
        if self.tracer is not None:
            self.tracer.instant("shed", req.rid, self.vtime, self.name,
                                deadline=req.deadline)

    # -- replica-to-replica migration (disaggregated serving) ------------------
    def export_stream(self, slot: int) -> StreamHandoff:
        """Extract an active (decodable) stream for adoption by another
        engine: the live page-chain K/V, bounded dense rows (sliding-window
        rings), recurrent SSM/RG-LRU row state, position and last sampled
        token.  The slot, its pages, and the batch row are released here —
        export is atomic from this engine's point of view: after it returns,
        the stream has no residue on this replica beyond scratch-page writes
        by the (now inactive) batch row.

        Only host-visible state at block granularity is touched, so exports
        ride the existing block cadence; the copied data is O(context), never
        a full-length buffer.
        """
        st = self.active.pop(slot)
        self._active_host[slot] = False
        self._sampled_host[slot] = False
        self._sync_active()
        self.free_slots.append(slot)
        chain = list(self.pager.chains.get(slot, [])) \
            if self.pager is not None else []
        blocks = []
        for stage in self.caches:
            sblocks = []
            for d in stage:
                if is_paged(d):
                    sblocks.append(("pages", paged_chain_extract(d, chain)
                                    if chain else None))
                else:
                    sblocks.append(("row", cache_row_extract(d, slot)))
            blocks.append(tuple(sblocks))
        if self.pager is not None:
            self.pager.export_chain(slot)
        # snapshot the *resolved* sampling config (None temperature becomes
        # an explicit 0.0): the handoff is the stream's complete decodable
        # state, so the adopter never re-resolves anything
        sp = st.req.sampling
        if sp is None or sp.temperature is None:
            temp, top_k, top_p = self._resolve_sampling(st.req)
            sp = SamplingParams(
                max_tokens=sp.max_tokens if sp else st.req.output_len,
                temperature=temp, top_k=top_k, top_p=top_p,
                seed=sp.seed if sp else None)
        if self._m is not None:
            self._m["ev"]["exported"].inc()
        if self.tracer is not None:
            self.tracer.instant("handoff_export", st.req.rid, self.vtime,
                                self.name, pages=len(chain), pos=st.pos)
        return StreamHandoff(
            req=st.req, pos=st.pos, last_token=st.last_token,
            n_pages=len(chain), blocks=blocks, export_time=self.vtime,
            page_size=self.ecfg.page_size if self.pager is not None else 0,
            cfg_name=self.cfg.name, sampling=sp,
            rng_lane=self._lane_for(st.req),
            ledger_carry=self.ledger.export_carry(self.name, st.req.rid)
            if self.ledger is not None else None,
            mesh_shape=self.ecfg.mesh)

    def import_stream(self, ho: StreamHandoff) -> bool:
        """Adopt a migrated stream: allocate a slot + an equal-length page
        chain, scatter the extracted pages/rows in, and join the decode
        batch at the handed-off position and token.  All-or-nothing: returns
        False — taking nothing — when no slot is free or the pool cannot
        cover the chain (the caller retries after streams retire).
        """
        assert ho.cfg_name == self.cfg.name, (
            f"cross-model handoff: {ho.cfg_name} -> {self.cfg.name}")
        assert ho.mesh_shape == self.ecfg.mesh, (
            f"cross-mesh handoff: exporter mesh {ho.mesh_shape} -> adopter "
            f"mesh {self.ecfg.mesh}; replicas in one cluster must share a "
            "mesh shape (handoff blocks are extracted per-shard-agnostic, "
            "but mixed shapes break the bit-exactness contract)")
        if ho.n_pages:
            assert self.pager is not None and \
                ho.page_size == self.ecfg.page_size, \
                "handoff requires matching paged layouts on both replicas"
        if not self.free_slots:
            return False
        slot = self.free_slots[0]
        chain = None
        if ho.n_pages:
            chain = self.pager.adopt_chain(slot, ho.n_pages)
            if chain is None and self._reclaim_cached():
                chain = self.pager.adopt_chain(slot, ho.n_pages)
            if chain is None:
                return False
        self.free_slots.pop(0)
        payloads = [tuple(p for _, p in hstage) for hstage in ho.blocks]
        self.caches = self._pin_caches(_import_kernel(
            self.caches, payloads,
            jnp.asarray(chain, jnp.int32) if chain else None,
            jnp.asarray(slot, jnp.int32)))
        self._tok = self._row_pin(self._tok.at[slot].set(ho.last_token))
        self._pos = self._row_pin(self._pos.at[slot].set(ho.pos))
        # the RNG lane and the exporter-resolved sampling config travel with
        # the stream: the adopter continues the exporter's draw sequence and
        # sampling mode instead of re-resolving against its own defaults
        # (draw i is fold_in(lane, position i), so this is all the state
        # needed)
        if ho.rng_lane is not None:
            ho.req.rng_lane = np.asarray(ho.rng_lane, np.uint32)
        if ho.sampling is not None:
            ho.req.sampling = ho.sampling
        self._set_slot_sampling(slot, ho.req)
        # the adopted chain's prompt pages are this pool's bits now: re-share
        # them so later arrivals with the same prompt hit on this replica too
        self._register_prefix(ho.req, slot, ho.pos)
        if self.ledger is not None:
            # no-op when the exporter billed into this same ledger (the
            # cluster shares one); across distinct ledgers the request's
            # partial attribution travels with the stream
            self.ledger.adopt_carry(ho.ledger_carry, ho.req.rid)
        self._imported += 1
        self.requests.append(ho.req)
        if self._m is not None:
            self._m["ev"]["imported"].inc()
        if self.tracer is not None:
            # the span covers the stream's in-flight window between replicas
            # (clamped: the adopter's clock may lag the exporter's)
            self.tracer.span("handoff", ho.req.rid, ho.export_time,
                             max(self.vtime, ho.export_time), self.name,
                             pages=ho.n_pages, pos=ho.pos)
        self._start_stream(ho.req, slot, ho.last_token, ho.pos, resumed=True)
        return True

    # -- decode ----------------------------------------------------------------
    def _account_decode_step(self, batch: int, ctx: float, dur=None,
                             rids=None) -> float:
        f = self.controller.maybe_tick(self.vtime)
        if dur is None:
            dur = self.plant.decode_step_latency(batch, ctx, f)
        e = dur * self.plant.decode_power(batch, ctx, f, dur)
        self.energy_j += e
        self.decode_energy_j += e
        self.decode_tokens += batch
        self.vtime += dur
        if rids is not None:
            # each alive row produced exactly one token this step, so
            # "shared by tokens produced" is an equal per-rid split
            self.ledger.record_decode(
                self.name, rids, e,
                saved_j=self._cf.decode_j(batch, ctx) - e)
        self.controller.record_tokens(self.vtime, batch, dur)
        return dur

    def _finish_check(self, st: _Stream) -> bool:
        """Mark a stream finished when it has emitted its budget (or hit
        max_len).  The FINISHED StateEvent is emitted by the caller *after*
        the stream's TokenEvent so drain_events consumers never see
        end-of-stream before the final tokens."""
        if (st.req.tokens_emitted >= st.req.output_len
                or st.pos >= self.ecfg.max_len - 1):
            st.req.finish = self.vtime
            st.req.state = RequestState.FINISHED
            self._completed += 1
            return True
        return False

    def _retire(self, slots: List[int]):
        for slot in slots:
            self.free_slots.append(slot)
            del self.active[slot]
            self._active_host[slot] = False
            self._sampled_host[slot] = False
            if self.pager is not None:
                self.pager.free_chain(slot)   # whole chain back to the pool
        if slots:
            self._sync_active()

    def _grow_for_block(self, k: int) -> int:
        """Grow every active chain to cover ``pos + k`` before the block is
        dispatched (the in-scan writes must stay inside allocated pages).
        Shrinks ``k``, then preempts youngest streams, if the pool runs dry.
        """
        while True:
            ordered = sorted(self.active.items(),
                             key=lambda kv: kv[1].order)   # oldest first
            if all(self.pager.ensure(s, st.pos + k) for s, st in ordered):
                return k
            if self._reclaim_cached():
                continue        # cache-only pages go before k shrinks or
                #                 anything live is preempted
            if k > 1:
                k = max(k // 2, 1)
                continue
            if len(self.active) + len(self.prefilling) > 1:
                self._preempt_for_pages()
                continue
            raise RuntimeError(
                "page pool exhausted: a lone stream cannot grow by one page "
                f"({self.pager.pages_used}/{self.pager.num_pages - 1} used)")

    def _decode_block(self, k: int) -> int:
        """Run ``k`` decode steps with a single host drain at the end;
        returns the number of steps actually executed (pool pressure may
        shrink ``k``).

        The batch composition is fixed for the block (the caller sizes ``k``
        to the next join/leave event), so virtual-time accounting needs no
        device data and the jitted steps pipeline without a host sync.
        Stream events (tokens, finishes) are emitted here, once per block —
        the streaming API inherits the no-per-token-host-sync invariant.
        """
        if self.pager is not None and self.active:
            with host_span("engine.grow_pages") as span:
                k = self._grow_for_block(k)
                span.set_metadata(k=k, pages_used=self.pager.pages_used)
        snapshot = list(self.active.items())
        batch = len(snapshot)
        if batch == 0:
            return 0
        max_pos = max(st.pos for st in self.active.values())
        if self.prefilling:
            # mid-prefill rows are inactive but still receive the held-pos
            # write each step; the ctx bucket (cache slice / page-table
            # slice) must cover their positions or that write wraps onto
            # position pos % ctx and corrupts their already-written context
            max_pos = max(max_pos,
                          max(cs.start for cs in self.prefilling.values()))
        wall = self.ecfg.use_wall_clock
        # host-known static: does any *decoding* row sample?  All-greedy
        # blocks compile (and run) without the sampler's per-step sort, and
        # a sampled stream that is still mid-chunked-prefill (inactive, its
        # draws masked anyway) doesn't force the sampled kernel variant.
        # Computed from stream metadata at block granularity — no device
        # read.
        sampled = bool(self._sampled_host[self._active_host].any())
        toks_dev = []
        durs: List[Optional[float]] = []   # per-step; None -> plant model
        left = k
        while left > 0:
            # fill the current ctx bucket before stepping up to the next one:
            # attention cost is O(ctx), so prefer many steps at small ctx
            ctx = next((c for c in self.ctx_buckets if c > max_pos),
                       self.ecfg.max_len)
            room = max(ctx - max_pos, 1)
            kb = next((b for b in self._k_blocks if b <= min(left, room)), 1)
            t0 = time.perf_counter() if wall else 0.0
            with host_span("engine.dispatch", ctx=ctx, kb=kb, rows=batch,
                           sampled=sampled):
                self._decode_blocks += 1
                self._kernel_blocks += self._attn == "kernel"
                if self.pager is not None:
                    n_ctx = min(ctx // self.ecfg.page_size, self._max_pages)
                    pt = self.pager.table_device()[:, :n_ctx]
                    (self._tok, self.caches, self._pos, tk) = \
                        _paged_decode_block_kernel(
                            self.cfg, self._shd, kb, sampled,
                            self.params, self._tok, self.caches, pt, self._pos,
                            self._active, self._keys, self._temps, self._topk,
                            self._topp)
                else:
                    (self._tok, self.caches, self._pos, tk) = \
                        _decode_block_kernel(
                            self.cfg, self._shd, ctx, kb, self.ecfg.max_len,
                            sampled,
                            self.params, self._tok, self.caches, self._pos,
                            self._active, self._keys, self._temps, self._topk,
                            self._topp)
            toks_dev.append(tk)        # (kb, B) device, drained at block end
            if wall:
                # wall-clock mode syncs per chunk (still amortized over kb
                # steps); a kernel's first chunk includes compile time, so
                # bill those steps to the plant model instead
                jax.block_until_ready(tk)
                seen = (ctx, kb) in self._warmed
                self._warmed.add((ctx, kb))
                dt = (time.perf_counter() - t0) / kb
                durs.extend([dt if seen else None] * kb)
            else:
                durs.extend([None] * kb)
            max_pos += kb
            left -= kb
        # single drain per block: (k, B) int32
        self._host_drains += 1
        with host_span("engine.drain", steps=k):
            toks = np.concatenate(jax.device_get(toks_dev), axis=0)
        with host_span("engine.bookkeep", steps=k, rows=batch) as span:
            span.set_metadata(
                finished=self._bookkeep_block(k, snapshot, toks, durs))
        return k

    def _bookkeep_block(self, k: int, snapshot, toks: np.ndarray,
                        durs: List[Optional[float]]) -> int:
        """Host side of a drained block: per-step accounting, the tokens
        into each stream, finishes, stream events, retirement, pool
        occupancy and publication.  Returns the streams it finished."""
        batch = len(snapshot)
        t_block = self.vtime
        done: List[int] = []
        block_toks: Dict[int, List[int]] = {slot: [] for slot, _ in snapshot}
        for i in range(k):
            ctx = float(np.mean([st.pos for st in self.active.values()
                                 if st.slot not in done]))
            alive = batch - len(done)
            rids = None if self.ledger is None else \
                [st.req.rid for slot, st in snapshot if slot not in done]
            dur = self._account_decode_step(alive, ctx, durs[i], rids)
            if self._m is not None:
                # one bucketed observation per step, weighted by the rows
                # that shared it — exact, without alive python calls
                self._m["tbt"].observe(dur, alive)
                self._obs_tbt.record_tbt(self.vtime, dur)
            for slot, st in snapshot:
                if slot in done:
                    continue
                st.last_token = int(toks[i, slot])
                st.req.tokens.append(st.last_token)
                block_toks[slot].append(st.last_token)
                st.pos += 1
                st.req.tokens_emitted += 1
                self._tbt.setdefault(st.req.rid, []).append(dur)
                if self._finish_check(st):
                    done.append(slot)
                    self._obs_finish(st.req)
                    if self.tracer is not None:
                        self.tracer.instant(
                            "finish", st.req.rid, self.vtime, self.name,
                            tokens=st.req.tokens_emitted)
        for slot, st in snapshot:       # one TokenEvent per stream per block
            if block_toks[slot]:
                self._emit(TokenEvent(
                    st.req.rid, self.vtime, tuple(block_toks[slot]),
                    len(block_toks[slot])))
        by_slot = dict(snapshot)        # FINISHED strictly after the tokens
        for slot in done:
            self._emit(StateEvent(by_slot[slot].req.rid, self.vtime,
                                  RequestState.FINISHED))
        self._retire(done)
        if self.pager is not None:
            # occupancy_live excludes cache-only (evictable) pages: a pool
            # full of reclaimable prefixes is not memory pressure, and the
            # controller's occ_high bias must not chase it.  Bitwise equal
            # to raw occupancy whenever the cache holds nothing.
            occ = self.pager.occupancy()["occupancy_live"]
            self._occupancy.record(self.vtime, occ)
            # memory pressure is a controller input: sustained high pool
            # occupancy biases the coarse loop toward higher clocks so
            # streams drain before the pool forces preemption
            record = getattr(self.controller, "record_occupancy", None)
            if record is not None:
                record(self.vtime, occ)
        if self.tracer is not None:
            self.tracer.span("decode_block", -1, t_block, self.vtime,
                             self.name, steps=k, batch=batch,
                             freq_mhz=self.controller.freq)
        self._publish_metrics()
        return len(done)

    def _step_legacy(self) -> int:
        """Pre-slot data plane: host argmax + batch-wide max(pos).  Kept only
        as the benchmark baseline; wrong for mixed-position batches."""
        B = self.ecfg.max_batch
        tok = np.zeros((B, 1), np.int32)
        for slot, st in self.active.items():
            tok[slot, 0] = st.last_token
        pos = max(st.pos for st in self.active.values())
        logits, self.caches = _decode_legacy_kernel(
            self.cfg, self.params, jnp.asarray(tok), self.caches,
            jnp.asarray(pos))
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        batch = len(self.active)
        ctx = float(np.mean([st.pos for st in self.active.values()]))
        rids = None if self.ledger is None else \
            [st.req.rid for st in self.active.values()]
        dur = self._account_decode_step(batch, ctx, rids=rids)
        done = []
        for slot, st in self.active.items():
            st.last_token = int(nxt[slot])
            st.req.tokens.append(st.last_token)
            st.pos += 1
            st.req.tokens_emitted += 1
            self._tbt.setdefault(st.req.rid, []).append(dur)
            self._emit(TokenEvent(st.req.rid, self.vtime,
                                  (st.last_token,), 1))
            if self._finish_check(st):
                self._emit(StateEvent(st.req.rid, self.vtime,
                                      RequestState.FINISHED))
                done.append(slot)
        self._retire(done)
        return batch

    def has_work(self) -> bool:
        """Backend protocol: anything queued, mid-prefill, or decoding."""
        return bool(self.pending or self.prefilling or self.active)

    def drain_events(self) -> List:
        """Backend protocol: hand out (and clear) the buffered stream
        events.  Events accumulate at block granularity — draining them is
        a host-side list swap, never a device sync."""
        ev, self._events = self._events, []
        return ev

    def _advance_idle(self) -> bool:
        """Nothing running and the FIFO head not yet arrived: jump the
        virtual clock to the *head's* arrival, billing the gap at idle
        power (same accounting as a cluster replica waiting on arrivals).
        The head — not the minimum over the queue — because ``_admit`` is
        strictly FIFO by submission order: jumping to a later-submitted
        earlier arrival would leave the head still unadmittable and
        deadlock the driver."""
        if not self.pending:
            return False
        head = self.pending[0]
        nxt = max(head.arrival, head.not_before)
        if nxt <= self.vtime + 1e-12:
            return False
        e_idle = (nxt - self.vtime) * self.plant.idle_power
        self.idle_energy_j += e_idle
        if self.ledger is not None:
            self.ledger.record_idle(self.name, e_idle)
        self.vtime = nxt
        self._publish_metrics()
        return True

    def step(self, k: Optional[int] = None) -> int:
        """One scheduling round: admit arrived requests, advance chunked
        prefills, then decode a block of ``k`` steps (default: the horizon
        to the next guaranteed join/leave event).  Returns the number of
        decode steps executed — 0 for admission/chunk/idle-only rounds.

        This is the ``Backend.step`` entry point: the ``serving.api``
        driver loop calls it with no argument; pass ``k=1`` for
        single-step-granularity tests."""
        with host_span("engine.step", pending=len(self.pending),
                       active=len(self.active)):
            self._evict_lapsed()     # opt-in: lapsed decoders free slots first
            self._admit()
            progressed = False
            if self.ecfg.slot_native:
                progressed = self._advance_chunks()
            if not self.active:
                if progressed or self._advance_idle():
                    return 0
                if self.prefilling or self.pending:
                    raise RuntimeError(
                        "serving stalled: pending/prefilling streams cannot "
                        "obtain pages or slots and nothing is decoding")
                return 0
            if not self.ecfg.slot_native:
                self._step_legacy()
                return 1
            # clamp to the horizon: _decode_block's batch composition is fixed
            # for the whole block, so k may never cross a guaranteed leave event
            horizon = self._horizon()
            return self._decode_block(max(min(k, horizon) if k is not None
                                          else horizon, 1))

    def _horizon(self) -> int:
        """Steps until the next guaranteed stream leave (no joins possible:
        the caller admits first).  Capped at ``decode_block`` — which also
        bounds how long a mid-prefill stream waits for its next chunk."""
        rem_out = min(max(st.req.output_len - st.req.tokens_emitted, 1)
                      for st in self.active.values())
        rem_len = min(self.ecfg.max_len - 1 - st.pos
                      for st in self.active.values())
        return max(1, min(rem_out, rem_len, self.ecfg.decode_block))

    @property
    def now(self) -> float:
        """Backend protocol: the engine's current virtual time (the clock
        the ``Server.run`` watchdog compares request wall-budgets against)."""
        return self.vtime

    def effective_prefill_tokens(self, req: Request) -> int:
        """Prefill tokens this engine would actually *compute* for ``req``:
        the prompt length minus the currently-cached prefix (a pure probe —
        no counters, no LRU touch).  ``PrefillOptimizer.busy_time`` and the
        cluster's routing/retuning consume this so clock selection and
        placement see the real work, not the nominal prompt length.  Exactly
        ``req.prompt_len`` whenever caching is off or the prompt tokens are
        not yet materialized."""
        if not self._cacheable or req.prompt is None:
            return req.prompt_len
        return max(req.prompt_len - self.prefix_cache.probe(req.prompt), 1)

    def page_occupancy_peak(self) -> float:
        """Peak page-pool occupancy over the run (0 when unpaged)."""
        if self.pager is None:
            return 0.0
        live = {sl: st.pos for sl, st in self.active.items()}
        live.update({sl: cs.start for sl, cs in self.prefilling.items()})
        return self.pager.occupancy(live)["peak_occupancy"]

    def report(self) -> ServingReport:
        """Backend protocol: the typed serving report (single scoring
        definition shared with the cluster and the simulator)."""
        peak = self.page_occupancy_peak()
        led = {}
        if self.ledger is not None:
            led = dict(energy_by_rid=self.ledger.energy_by_rid(),
                       saved_by_rid=self.ledger.saved_by_rid(),
                       energy_saved_j=self.ledger.replica_saved_j(self.name))
        return build_report(
            backend="engine", requests=self.requests, tbt_records=self._tbt,
            slo=self.ecfg.slo, class_names=self.router.class_names,
            prefill_energy_j=self.prefill_energy_j,
            decode_energy_j=self.decode_energy_j,
            idle_energy_j=self.idle_energy_j,
            prefill_tokens=self.prefill_tokens,
            decode_tokens=self.decode_tokens,
            duration_s=self.vtime, preempted=self._preempted,
            # adopted handoffs only, matching the cluster-level definition
            # (summing imports counts each migration exactly once)
            migrated=self._imported,
            page_occupancy_peak=peak, **led)

    def _slo_stats(self) -> Dict:
        """Per-class p90 TTFT and TTFT/TBT SLO pass rates —
        ``core.report.slo_pass_metrics`` is the single scoring definition,
        applied to the same population as ``report()`` (every request with
        a first token, cancelled included), so the legacy dict and the
        typed report can never diverge."""
        from repro.core.report import slo_pass_metrics
        m = slo_pass_metrics(self.requests, self._tbt, self.ecfg.slo,
                             self.router.class_names)
        return {"ttft_pass": m["ttft_pass"], "tbt_pass": m["tbt_pass"],
                "p90_ttft_s": m["p90_ttft"]}

    def stats(self) -> Dict:
        tbts = [x for v in self._tbt.values() for x in v]
        s = {
            "completed": self._completed,
            "cancelled": self._cancelled,
            "failed": self._failed,
            "shed": self._shed,
            "pending": len(self.pending),
            "active": len(self.active),
            "decode_blocks": self._decode_blocks,
            "decode_blocks_kernel": self._kernel_blocks,
            "prefilling": len(self.prefilling),
            "vtime_s": self.vtime,
            # active + idle, matching the cluster's legacy dict (idle is 0
            # for batch workloads; billed only while waiting on arrivals)
            "energy_j": self.energy_j + self.idle_energy_j,
            "idle_energy_j": self.idle_energy_j,
            # per-phase split, comparable with sim.replay.Metrics
            "prefill_energy_j": self.prefill_energy_j,
            "decode_energy_j": self.decode_energy_j,
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "p95_tbt_ms": float(np.percentile(tbts, 95)) * 1e3 if tbts else 0,
            "p99_tbt_ms": float(np.percentile(tbts, 99)) * 1e3 if tbts else 0,
            "freq_mhz": self.controller.freq,
        }
        s.update(self._slo_stats())
        if self.pager is not None:
            # a stream at position pos holds K/V for positions 0..pos-1
            live = {sl: st.pos for sl, st in self.active.items()}
            live.update({sl: cs.start for sl, cs in self.prefilling.items()})
            occ = self.pager.occupancy(live)
            s.update({
                "pages_used": occ["pages_used"],
                "pages_total": occ["pages_total"],
                "pages_shared": occ["pages_shared"],
                "pages_reserved": occ["pages_reserved"],
                "pages_cached": occ["pages_cached"],
                "page_occupancy": occ["occupancy"],
                "page_occupancy_live": occ["occupancy_live"],
                "page_occupancy_peak": occ["peak_occupancy"],
                "page_fragmentation": occ["fragmentation"],
                "preempted": self._preempted,
            })
        if self.prefix_cache is not None:
            pc = self.prefix_cache.stats()
            s.update({f"prefix_cache_{k}": v for k, v in pc.items()})
        return s
