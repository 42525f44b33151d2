#!/usr/bin/env python3
"""Chip smoke: serve qwen2-1.5b at published width on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: mesh vs one-device only

One chip runs three phases, all in this process (it holds the chip; nothing
here starts a JAX child):

* ``logits`` — the engine's slot-prefill and paged-decode logits for one
  prompt against the model's plain ``prefill`` / dense ``decode_step``, both
  in float32 under ``default_matmul_precision("highest")``.
* ``engine`` — ``Server`` -> ``ServingEngine`` (paged KV, chunked prefill,
  ``max_batch=32, max_len=4096``) serving 12 requests, greedy and seeded-
  sampled in one batch.  A warm-up pass compiles; the identical second pass
  is the timed serving window and must repeat the first pass's tokens.
* ``cluster`` — the same requests through the disaggregated 1 prefill + 1
  decode ``ServingCluster`` on the same chip (stream export/import).

``--chips 4`` runs only the mesh phase: the same kind of requests on the
one-device engine and on a (2, 2) ``EngineConfig(mesh=...)`` engine, which
must produce identical tokens with its shards on four distinct devices.

The model is qwen2-1.5b at its published widths (28 layers, d_model 1536,
vocab 151936, bf16) with random weights from ``--seed``.  Energy numbers are
the plant model's, not measured.  Any failed check exits non-zero; a TPU is
required (no CPU fallback).  The last stdout line on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time
import traceback
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen2-1.5b"
ECFG = dict(paged=True, max_batch=32, max_len=4096)
# float32 engine path vs float32 plain forward: max |delta logit| over the
# largest |reference logit|.  Expected f32 rounding drift across 28 layers is
# ~1e-6 of the logit range; a wrong mask, position or page moves logits by
# O(1) of the range.  1e-4 sits two decades from each.
LOGIT_RTOL = 1e-4
DONATION_WARNING = "Some donated buffers were not usable"


class CompileLog:
    """Counts XLA compiles (persistent-cache reads included) and their
    seconds through ``jax.monitoring``."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.by_name = {}               # program name -> compile seconds
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, fun_name="", **_):
            if name == event:
                self.n += 1
                self.seconds += secs
                self.by_name[fun_name] = self.by_name.get(fun_name, 0) + secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return self.n, self.seconds, self.cache_hits

    def since(self, mark) -> str:
        n, s, h = mark
        return (f"compiles={self.n - n} compile_s={self.seconds - s:.3f} "
                f"persistent_cache_hits={self.cache_hits - h}")


def workload(vocab: int, seed: int, n: int, max_prompt: int, outputs):
    """``n`` (prompt tokens, SamplingParams): prompts of 64..``max_prompt``
    tokens (the first is the longest, so the decode context bucket is set
    from the first block); even requests greedy, odd ones seeded sampling
    with per-request temperature / top-k / top-p.  Output lengths are
    1 mod 16: after the prefill's first token every horizon is a multiple of
    16, which keeps the fused decode block sizes to {16, 64}."""
    import numpy as np
    from repro.core import SamplingParams
    rng = np.random.default_rng(seed)
    lens = rng.integers(64, max_prompt + 1, size=n)
    lens[0] = max_prompt
    reqs = []
    for i, L in enumerate(lens):
        prompt = rng.integers(0, vocab, size=int(L)).astype(np.int32)
        out = int(rng.choice(outputs))
        if i % 2 == 0:
            sp = SamplingParams(max_tokens=out)
        else:
            sp = SamplingParams(max_tokens=out, temperature=0.8,
                                top_k=(0, 50)[(i // 2) % 2],
                                top_p=(1.0, 0.95)[(i // 2) % 2],
                                seed=seed + i)
        reqs.append((prompt, sp))
    return reqs


def serve(server, reqs):
    """Submit ``reqs``, drain the server; returns (handles, report, wall s).
    The window ends when the backend's device work is done."""
    import jax
    handles = [server.submit(p, sp) for p, sp in reqs]
    t0 = time.perf_counter()
    rep = server.run()
    backend = server.backend
    engines = [r.engine for r in backend.replicas] \
        if hasattr(backend, "replicas") else [backend]
    jax.block_until_ready([(e.caches, e._tok) for e in engines])
    return handles, rep, time.perf_counter() - t0


def check_requests(handles, reqs, vocab: int):
    """Every request FINISHED with its full token count, ids in range."""
    from repro.core import RequestState
    errors = []
    for h, (_, sp) in zip(handles, reqs):
        toks = h.request.tokens
        if h.state is not RequestState.FINISHED:
            errors.append(f"rid {h.rid} ended {h.state.name}")
        if len(toks) != sp.max_tokens:
            errors.append(f"rid {h.rid}: {len(toks)} tokens, "
                          f"wanted {sp.max_tokens}")
        if any(not 0 <= t < vocab for t in toks):
            errors.append(f"rid {h.rid}: token id outside [0, {vocab})")
    if errors:
        raise AssertionError("; ".join(errors))
    return [list(h.request.tokens) for h in handles]


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} ({peak / 1e9:.3f} GB)"


def _rel_err(got, ref) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def logits_phase(cfg, params, prompt, log):
    """float32 engine path vs float32 plain forward (prefill + one decode
    step), both at ``highest`` matmul precision; plus the serving-dtype
    (bf16) engine path, which must be finite.  The engine path's decode step
    is fed the reference's greedy token, so both next-token rows condition
    on the same sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import decode_step, init_cache, prefill
    from repro.serving import EngineConfig, ServingEngine
    cfg32 = cfg.replace(dtype="float32")
    params32 = jax.jit(lambda p: jax.tree.map(
        lambda x: x.astype(jnp.float32), p))(params)
    L = len(prompt)
    results = {}
    for name, c, p, dt in (("f32", cfg32, params32, "float32"),
                           ("bf16", cfg, params, "bfloat16")):
        ctx = jax.default_matmul_precision("highest") if name == "f32" \
            else contextlib.nullcontext()
        with ctx:
            if name == "f32":
                ref_caches = init_cache(c, 1, L + 1, dtype=jnp.float32)
                ref_first, ref_caches, _ = jax.jit(
                    lambda p_, t, cc: prefill(p_, c, t, cc))(
                    p, jnp.asarray(prompt)[None], ref_caches)
                nxt = jnp.argmax(ref_first, axis=-1).astype(jnp.int32)
                ref_next, _ = jax.jit(
                    lambda p_, t, cc: decode_step(p_, c, t[:, None], cc, L))(
                    p, nxt, ref_caches)
                results["ref"] = (np.asarray(ref_first), np.asarray(ref_next))
            eng = ServingEngine(c, params=p, ecfg=EngineConfig(
                paged=True, max_batch=1, max_len=2048, governor="defaultnv",
                cache_dtype=dt))
            results[name] = path_logits(
                eng, c, prompt, next_tok=int(np.argmax(results["ref"][0])))
            del eng
    del params32
    ref_first, ref_next = results["ref"]
    errors = []
    for name in ("f32", "bf16"):
        first, nxt = results[name]
        finite = bool(np.isfinite(first).all() and np.isfinite(nxt).all())
        e1, e2 = _rel_err(first, ref_first), _rel_err(nxt, ref_next)
        same = bool(np.argmax(first) == np.argmax(ref_first)
                    and np.argmax(nxt) == np.argmax(ref_next))
        log(f"[logits] {name} engine path vs f32 plain forward: "
            f"prompt={L} rel_err first_token={e1:.3e} next_token={e2:.3e} "
            f"argmax_equal={same} finite={finite}"
            + (f" (tolerance {LOGIT_RTOL:g})" if name == "f32"
               else " (serving dtype; reported, not held to the f32 bound)"))
        if not finite:
            errors.append(f"{name} logits not finite")
        if name == "f32" and max(e1, e2) > LOGIT_RTOL:
            errors.append(f"f32 engine logits off the plain forward: "
                          f"{max(e1, e2):.3e} > {LOGIT_RTOL:g}")
    if not (np.isfinite(ref_first).all() and np.isfinite(ref_next).all()):
        errors.append("reference logits not finite")
    if errors:
        raise AssertionError("; ".join(errors))


def serving_phase(name, make_backend, reqs, vocab, device, clog, log):
    """Warm-up pass (compiles), then the identical timed pass on the same
    backend; both must finish every request, and repeat each other's
    tokens (greedy and seeded lanes are pure in (lane, position))."""
    from repro.serving import Server
    server = Server(make_backend())
    m = clog.mark()
    handles, rep, wall = serve(server, reqs)
    first = check_requests(handles, reqs, vocab)
    log(f"[{name}] warm-up pass: {clog.since(m)} wall_s={wall:.3f}")
    m = clog.mark()
    handles, rep2, wall = serve(server, reqs)
    second = check_requests(handles, reqs, vocab)
    dec = rep2.decode_tokens - rep.decode_tokens
    log(f"[{name}] serving window: wall_s={wall:.6f} "
        f"decode_tokens={dec} decode_tok_per_s={dec / wall:.3f} "
        f"prefill_tokens={rep2.prefill_tokens - rep.prefill_tokens} "
        f"requests={len(reqs)} in-window {clog.since(m)}")
    log(f"[{name}] peak_bytes_in_use={peak_bytes(device)}")
    log(f"[{name}] energy_j={rep2.total_energy_j - rep.total_energy_j:.3f} "
        f"(plant model at the controller's clocks: modelled, not measured) "
        f"migrated={rep2.migrated - rep.migrated}")
    if first != second:
        bad = [i for i, (a, b) in enumerate(zip(first, second)) if a != b]
        raise AssertionError(f"second pass repeated different tokens for "
                             f"requests {bad}")
    return first, rep2.migrated - rep.migrated


def one_chip(args, cfg, params, device, clog, log):
    from repro.serving import EngineConfig, ServingCluster, ServingEngine
    vocab = cfg.vocab_size
    reqs = workload(vocab, args.seed, 12, 1024, (49, 65, 81, 97, 113))
    failures = []

    def phase(name, fn):
        m = clog.mark()
        try:
            out = fn()
        except Exception:
            traceback.print_exc()
            failures.append(name)
            out = None
        log(f"[{name}] phase total: {clog.since(m)}")
        gc.collect()
        return out

    # a mid-length prompt: its bucket pads (300 -> 512) must stay masked
    phase("logits", lambda: logits_phase(cfg, params, reqs[3][0][:300], log))

    ecfg = EngineConfig(**ECFG)
    out = phase("engine", lambda: serving_phase(
        "engine", lambda: ServingEngine(cfg, params=params, ecfg=ecfg,
                                        seed=args.seed),
        reqs, vocab, device, clog, log))
    engine_tokens = out[0] if out else None
    del out
    gc.collect()
    out = phase("cluster", lambda: serving_phase(
        "cluster", lambda: ServingCluster(cfg, n_prefill=1, n_decode=1,
                                          params=params, ecfg=ecfg,
                                          seed=args.seed),
        reqs, vocab, device, clog, log))
    if out is not None:
        tokens, migrated = out
        if migrated != len(reqs):
            failures.append("cluster")
            print(f"cluster migrated {migrated} streams, wanted "
                  f"{len(reqs)}", file=sys.stderr)
        if engine_tokens is not None:
            same = sum(a == b for a, b in zip(tokens, engine_tokens))
            log(f"[cluster] requests token-identical to the colocated "
                f"engine: {same}/{len(reqs)} (reported; batch composition "
                f"and context buckets differ between the two)")
    return failures


def path_logits(eng, cfg, prompt, next_tok=None):
    """(first-token, next-token) logits of ``prompt`` through ``eng``'s own
    programs' bodies — its ShardCtx, parameter placement, paged pool and
    page table: the bucket-padded slot prefill into a free slot, then one
    batched paged decode step in which only that row is active, fed
    ``next_tok`` (default: the prefill's own greedy token)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.shardings import gather_replicated
    from repro.models import decode_step, prefill_into_slot
    L = len(prompt)
    bucket = next(b for b in eng.buckets if b >= L)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :L] = prompt
    slot = eng.free_slots[0]
    assert eng.pager.ensure(slot, L + 1)
    shd = eng._shd
    first, caches, _ = jax.jit(lambda p, t, n, c, pt: prefill_into_slot(
        gather_replicated(p, shd.mesh), cfg, t, n, c, slot, shd=shd,
        page_table=pt))(eng.params, toks, jnp.int32(L), eng.caches,
                        eng._pt_rows(slot, bucket))
    if next_tok is None:
        next_tok = int(np.argmax(np.asarray(first[0])))
    B = eng.ecfg.max_batch
    row = np.arange(B) == slot
    tok = eng._row_pin(jnp.asarray(np.where(row, next_tok, 0), jnp.int32))
    pos = eng._row_pin(jnp.asarray(np.where(row, L, 0), jnp.int32))
    active = eng._row_pin(jnp.asarray(row))
    pt = eng._pt_rows(slot, L + 1)
    pt = eng.pager.table_device()[:, :pt.shape[1]]
    nxt, _ = jax.jit(lambda p, t, c, ps, pt_, a: decode_step(
        gather_replicated(p, shd.mesh), cfg, t[:, None], c, ps, shd=shd,
        page_table=pt_, active=a))(eng.params, tok, caches, pos, pt, active)
    eng.pager.free_chain(slot)
    return (np.asarray(first, np.float32)[0],
            np.asarray(nxt, np.float32)[slot])


def four_chips(args, cfg, params, clog, log):
    """The mesh-serving invariant on real chips: a (2, 2) mesh engine serves
    the same requests token-identically to the one-device engine, with its
    parameter and pool shards on four distinct devices."""
    import jax
    from repro.serving import EngineConfig, Server, ServingEngine
    vocab = cfg.vocab_size
    # one seeded-sampled lane, the longest-lived, so every decode block
    # takes the sampled program: each sampled program spends ~25 s of
    # compile on the full-vocab sort, paid here by both engines
    reqs = [(p, sp if i == 1 else dataclasses.replace(
        sp, temperature=None, top_k=0, top_p=1.0, seed=None, max_tokens=33))
        for i, (p, sp) in enumerate(
            workload(vocab, args.seed, 6, 256, (49,)))]
    failures = []
    runs, logits = {}, {}
    for mesh in (None, (2, 2)):
        m = clog.mark()
        eng = ServingEngine(cfg, params=params, seed=args.seed,
                            ecfg=EngineConfig(**ECFG, mesh=mesh))
        if mesh is not None:
            shards = {}
            for label, leaf in (("param embedding",
                                 eng.params["embed"]["embedding"]),
                                ("KV pool", eng.caches[0][0]["kp"])):
                devs = {s.device for s in leaf.addressable_shards}
                shapes = {s.data.shape for s in leaf.addressable_shards}
                shards[label] = (len(devs), shapes, leaf.shape)
                log(f"[mesh] {label} {leaf.shape}: shards on {len(devs)} "
                    f"distinct devices, shard shapes {sorted(shapes)}")
                if len(devs) != 4 or leaf.shape in shapes:
                    failures.append(f"{label} not sharded over 4 devices")
        handles, rep, wall = serve(Server(eng), reqs)
        runs[mesh] = check_requests(handles, reqs, vocab)
        logits[mesh] = path_logits(eng, cfg, reqs[0][0])
        log(f"[mesh] mesh={mesh}: {clog.since(m)} wall_s={wall:.3f} "
            f"decode_tokens={rep.decode_tokens}")
        del eng, handles
        gc.collect()
    same = [a == b for a, b in zip(runs[None], runs[(2, 2)])]
    deltas = " ".join(
        f"{k}: max|delta|={float(abs(a - b).max()):.6g} "
        f"rel={_rel_err(b, a):.3e}"
        for k, a, b in zip(("first_token", "next_token"), logits[None],
                           logits[(2, 2)]))
    log(f"[mesh] (2,2) mesh vs one device: {sum(same)}/{len(same)} requests "
        f"token-identical; request 0 logits {deltas}")
    if not all(same):
        first = [next(j for j, (x, y) in enumerate(zip(a, b)) if x != y)
                 for a, b, s in zip(runs[None], runs[(2, 2)], same) if not s]
        log(f"[mesh] first differing token index per mismatched request: "
            f"{first}")
        failures.append("mesh token identity")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: engine, cluster and logit phases on one chip; "
                         "4: only the mesh-vs-one-device phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the requests")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    from repro.configs import get_config
    from repro.models import init_params

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    def log(msg):
        print(msg, flush=True)

    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    log(f"compile cache: {cache_dir}")
    clog = CompileLog()
    cfg = get_config(ARCH)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        m = clog.mark()
        # eager, as ServingEngine(cfg) itself initializes: one small program
        # per distinct weight shape (jitting the whole init compiled for
        # ~130 s on a v5e)
        params = init_params(jax.random.PRNGKey(args.seed), cfg)
        jax.block_until_ready(params)
        n_params = sum(x.size for x in jax.tree.leaves(params))
        log(f"model: {cfg.name} layers={cfg.num_layers} "
            f"d_model={cfg.d_model} heads={cfg.num_heads}/"
            f"{cfg.num_kv_heads} vocab={cfg.vocab_size} dtype={cfg.dtype} "
            f"params={n_params} (random, seed {args.seed}) "
            f"init {clog.since(m)}")
        if args.chips == 4:
            failures = four_chips(args, cfg, params, clog, log)
        else:
            failures = one_chip(args, cfg, params, dev, clog, log)
    donation = [str(w.message) for w in caught
                if DONATION_WARNING in str(w.message)]
    if donation:
        failures.append("donation")
        print(f"donated buffers were not usable ({len(donation)}x): "
              f"{donation[0][:300]}", file=sys.stderr)
    log(f"compiles total: {clog.n} in {clog.seconds:.3f} s, "
        f"persistent-cache hits {clog.cache_hits}")
    top = sorted(clog.by_name.items(), key=lambda kv: -kv[1])[:6]
    log("compile seconds by program: "
        + ", ".join(f"{k}={v:.2f}" for k, v in top))
    log(f"peak_bytes_in_use (process): {peak_bytes(dev)}")
    if failures:
        print(f"chip_smoke: FAILED phases/checks: {failures}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
