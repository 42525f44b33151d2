"""Paged KV-cache subsystem tests: token-for-token equivalence of paged vs
dense decode and chunked vs one-shot prefill across attention variants,
allocator invariants (no double-free, chains freed at retire, occupancy never
exceeds the pool), pool-pressure preemption with recompute-on-resume, the
over-subscription capacity win, and the Pallas paged decode kernel vs its
oracle.

Equivalence runs use float32 K/V buffers on both sides: the chunked path
reads *past* chunks through the cache while one-shot prefill attends raw
activations, so bf16 buffers would make the comparison a rounding lottery
instead of a correctness check (decode-side reads go through the cache in
both engines, so they are layout-exact at any dtype).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Request
from repro.models import init_params, init_cache, prefill, decode_step
from repro.models.config import ModelConfig
from repro.serving import EngineConfig, Server, ServingEngine
from repro.serving.pager import PageAllocator, SCRATCH_PAGE
import repro.serving.engine as engine_mod

KEY = jax.random.PRNGKey(0)
MAXLEN = 96


def _cfg(variant: str) -> ModelConfig:
    kw = dict(name=f"tp-{variant}", arch_type="dense", num_layers=2,
              d_model=64, num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128,
              vocab_size=128, dtype="float32", max_seq=512)
    if variant == "gqa":
        kw["num_kv_heads"] = 2
    elif variant == "kv_quant":
        kw.update(num_kv_heads=2, kv_quant=True)
    elif variant == "local":
        kw.update(block_pattern=("local", "full"), window=16)
    return ModelConfig(**kw)


def _reference_tokens(params, cfg, prompt, output_len):
    caches = init_cache(cfg, 1, MAXLEN, dtype=jnp.float32)
    lg, caches, pos = prefill(params, cfg,
                              jnp.asarray(prompt, jnp.int32)[None], caches)
    toks = [int(jnp.argmax(lg[0]))]
    while len(toks) < max(output_len, 2) and pos < MAXLEN - 1:
        lg, caches = decode_step(params, cfg,
                                 jnp.asarray([[toks[-1]]], jnp.int32),
                                 caches, jnp.asarray(pos, jnp.int32))
        toks.append(int(jnp.argmax(lg[0])))
        pos += 1
    return toks


def _engine(cfg, params, **kw):
    kw.setdefault("cache_dtype", "float32")
    return ServingEngine(cfg, params=params,
                         ecfg=EngineConfig(max_batch=4, max_len=MAXLEN,
                                           governor="defaultnv", **kw))


def _serve(eng, prompts, out_lens):
    reqs = []
    for i, (p, o) in enumerate(zip(prompts, out_lens)):
        r = Request(rid=i, arrival=0.0, prompt_len=len(p), output_len=o)
        reqs.append(r)
        eng.submit(r, p)
    Server(eng).run()
    return [r.tokens for r in reqs]


def _force_chunk(eng, n=16):
    """Shrink the admission buckets so prompts > n take the chunked path even
    on full-attention configs (whose natural bucket cap is max_len // 2)."""
    eng.buckets = [b for b in eng.buckets if b <= n] or [n]
    eng.chunk_len = eng.buckets[-1]


# -- paged vs dense equivalence ------------------------------------------------

@pytest.mark.parametrize("variant", ["full", "gqa", "kv_quant", "local"])
def test_paged_decode_matches_dense(variant):
    """The paged engine emits token-for-token the same output as the dense
    slot-native engine over mixed-position continuous batching."""
    cfg = _cfg(variant)
    params = init_params(KEY, cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in (19, 7, 12)]
    outs = [10, 6, 8]

    t_dense = _serve(_engine(cfg, params, paged=False), prompts, outs)
    t_paged = _serve(_engine(cfg, params, paged=True), prompts, outs)
    assert t_dense == t_paged


@pytest.mark.parametrize("variant", ["full", "gqa", "local"])
def test_chunked_prefill_matches_oneshot(variant):
    """A prompt long enough to be split into chunks decodes token-for-token
    like the unchunked reference (one-shot prefill + scalar decode)."""
    cfg = _cfg(variant)
    params = init_params(KEY, cfg)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=37)
    eng = _engine(cfg, params, paged=True)
    _force_chunk(eng)
    [tokens] = _serve(eng, [prompt], [8])
    assert tokens == _reference_tokens(params, cfg, prompt, 8)


@pytest.mark.parametrize("variant", ["rglru", "ssm"])
def test_chunked_prefill_hybrid_recurrent_state_survives_interleaving(variant):
    """A hybrid (recurrent + attention) stream mid-chunked-prefill must not
    have its SSM/RG-LRU row state advanced by other streams' decode blocks:
    recurrent caches have no position masking, so inactive rows' updates are
    frozen via the active mask (regression: decode once polluted the state
    between chunks, K/V buffers alone were protected)."""
    kw = dict(name=f"tp-{variant}", d_model=64, num_heads=4, num_kv_heads=4,
              head_dim=16, d_ff=128, vocab_size=128, dtype="float32",
              max_seq=512)
    if variant == "rglru":
        kw.update(arch_type="hybrid", num_layers=3,
                  block_pattern=("rglru", "rglru", "local"), window=16,
                  lru_width=64, conv_width=4)
    else:
        kw.update(arch_type="hybrid", num_layers=2,
                  block_pattern=("ssm", "local"), window=16,
                  ssm_state=16, ssm_headdim=16, conv_width=4)
    cfg = ModelConfig(**kw)
    params = init_params(KEY, cfg)
    rng = np.random.default_rng(11)
    p_long = rng.integers(0, cfg.vocab_size, size=37)  # > window -> chunked
    p_short = rng.integers(0, cfg.vocab_size, size=9)
    eng = _engine(cfg, params)
    r_short = Request(rid=0, arrival=0.0, prompt_len=9, output_len=12)
    eng.submit(r_short, p_short)
    eng.step(1)                       # short stream decodes alone first
    r_long = Request(rid=1, arrival=0.0, prompt_len=37, output_len=8)
    eng.submit(r_long, p_long)       # chunks interleave with short's decode
    Server(eng).run()
    assert r_long.tokens == _reference_tokens(params, cfg, p_long, 8)
    assert r_short.tokens == _reference_tokens(params, cfg, p_short, 12)


def test_chunked_prefill_kv_quant_layout_equivalence():
    """Under K/V quantization, chunked one-shot equivalence is not exact by
    construction (past chunks are read dequantized, one-shot attends raw), so
    assert the *layout* equivalence instead: paged chunked == dense chunked."""
    cfg = _cfg("kv_quant")
    params = init_params(KEY, cfg)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, size=37)]

    def chunked(paged):
        eng = _engine(cfg, params, paged=paged)
        _force_chunk(eng)
        return _serve(eng, prompts, [8])

    assert chunked(True) == chunked(False)


def test_long_prompt_admits_without_legacy_fallback(monkeypatch):
    """A prompt longer than the smallest attention buffer (window=16) goes
    through the slot-native chunked path: the reference ``prefill`` and
    per-request ``init_cache`` must never run."""
    cfg = _cfg("local")
    params = init_params(KEY, cfg)
    eng = _engine(cfg, params, paged=True)   # construction may init_cache
    calls = []
    monkeypatch.setattr(engine_mod, "prefill",
                        lambda *a, **k: calls.append("prefill"))
    monkeypatch.setattr(engine_mod, "init_cache",
                        lambda *a, **k: calls.append("init_cache"))
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=33)   # > window=16
    [tokens] = _serve(eng, [prompt], [8])
    assert calls == []
    assert tokens == _reference_tokens(params, cfg, prompt, 8)


# -- capacity: the point of paging ---------------------------------------------

def test_paged_capacity_exceeds_dense_envelope():
    """With a pool of half the dense K/V memory, the paged engine still holds
    ``max_batch`` concurrent streams — strictly more than the
    ``memory / max_len`` streams the dense layout could pin at equal memory —
    with zero preemptions when the live contexts fit."""
    cfg = _cfg("full")
    params = init_params(KEY, cfg)
    ps = 16
    num_pages = (4 * MAXLEN // ps) // 2 + 1       # half dense capacity + scratch
    eng = _engine(cfg, params, paged=True, page_size=ps, num_pages=num_pages)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=16) for _ in range(4)]
    reqs = [Request(rid=i, arrival=0.0, prompt_len=16, output_len=12)
            for i in range(4)]
    for r, p in zip(reqs, prompts):
        eng.submit(r, p)
    eng.step(1)
    s = eng.stats()
    pool_tokens = s["pages_total"] * ps
    dense_streams_at_equal_memory = pool_tokens // MAXLEN
    assert s["active"] == 4 > dense_streams_at_equal_memory
    Server(eng).run()
    s = eng.stats()
    assert s["completed"] == 4 and s["preempted"] == 0
    assert s["pages_used"] == 0          # chains freed at retire


def test_prefill_only_pool_pressure_preempts_instead_of_stalling():
    """Regression: a pool exhausted entirely by *mid-chunked-prefill* streams
    used to stall forever (only decoding streams were preemption victims).
    Two long prompts that cannot both hold their chains must now complete via
    youngest-first preemption + recompute-on-resume, token-exactly."""
    cfg = _cfg("full")
    params = init_params(KEY, cfg)
    # 3 usable pages of 16 tokens; each 40-token prompt needs 3 pages, so the
    # second stream's chunks exhaust the pool while both are still prefilling
    eng = _engine(cfg, params, paged=True, page_size=16, num_pages=4)
    _force_chunk(eng)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, size=40) for _ in range(2)]
    tokens = _serve(eng, prompts, [6, 6])
    s = eng.stats()
    assert s["completed"] == 2
    assert s["preempted"] > 0
    for p, t in zip(prompts, tokens):
        assert t == _reference_tokens(params, cfg, p, 6)


def test_pool_pressure_preempts_and_recomputes_exactly():
    """An over-committed pool forces preemption; victims are recomputed via
    chunked prefill and still produce token-exact output."""
    cfg = _cfg("full")
    params = init_params(KEY, cfg)
    eng = _engine(cfg, params, paged=True, page_size=16, num_pages=8)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=30) for _ in range(4)]
    tokens = _serve(eng, prompts, [20] * 4)
    s = eng.stats()
    assert s["completed"] == 4
    assert s["preempted"] > 0            # 7 usable pages << 4 * 50 tokens
    assert s["pages_used"] == 0
    for p, t, o in zip(prompts, tokens, [20] * 4):
        assert t == _reference_tokens(params, cfg, p, o)


# -- allocator properties ------------------------------------------------------

def test_allocator_double_free_raises():
    a = PageAllocator(num_pages=8, page_size=16, max_streams=4,
                      max_pages_per_stream=4)
    assert a.ensure(0, 40)               # 3 pages
    a.free_chain(0)
    a.chains[0] = [1]                    # simulate a stale chain
    with pytest.raises(ValueError, match="double free"):
        a.free_chain(0)


def test_allocator_all_or_nothing_and_occupancy_bound():
    a = PageAllocator(num_pages=6, page_size=16, max_streams=4,
                      max_pages_per_stream=8)
    assert a.ensure(0, 48)               # 3 of 5 usable pages
    assert not a.ensure(1, 64)           # needs 4, only 2 left: refused whole
    assert a.pages_used == 3             # refused alloc took nothing
    assert a.ensure(1, 32)
    assert a.pages_used == 5 and a.pages_free == 0
    assert not a.ensure(2, 1)
    assert a.pages_used <= a.num_pages - 1


def test_allocator_random_workload_invariants():
    rng = np.random.default_rng(42)
    a = PageAllocator(num_pages=33, page_size=8, max_streams=8,
                      max_pages_per_stream=12)
    live = {}
    for step in range(400):
        slot = int(rng.integers(0, 8))
        if slot in live and rng.random() < 0.3:
            a.free_chain(slot)
            del live[slot]
            continue
        want = min(live.get(slot, 0) + int(rng.integers(1, 30)),
                   a.max_pages_per_stream * a.page_size)
        if a.ensure(slot, want):
            live[slot] = want
        # invariants: conservation, no aliasing, table consistency
        held = sum(len(c) for c in a.chains.values())
        assert held + a.pages_free == a.num_pages - 1
        assert a.pages_used <= a.num_pages - 1
        all_pages = [p for c in a.chains.values() for p in c]
        assert len(all_pages) == len(set(all_pages))
        assert SCRATCH_PAGE not in all_pages
        for s, chain in a.chains.items():
            assert list(a.table[s, :len(chain)]) == chain
            assert (a.table[s, len(chain):] == SCRATCH_PAGE).all()
    for slot in list(live):
        a.free_chain(slot)
    assert a.pages_used == 0 and a.pages_free == a.num_pages - 1


def test_allocator_rejects_overlong_chain():
    a = PageAllocator(num_pages=32, page_size=8, max_streams=2,
                      max_pages_per_stream=3)
    with pytest.raises(ValueError, match="max_pages_per_stream"):
        a.ensure(0, 8 * 4)


# -- Pallas paged decode kernel ------------------------------------------------

KERNEL_CASES = {
    # qwen2-1.5b heads (12 query, 2 kv, hd 128, page 16); the table spans a
    # 512-position bucket, walked in blocks of 4 pages
    "qwen2-bucket": dict(Hq=12, KH=2, hd=128, n=32, pos=[511, 100, 7],
                         block_pages=4),
    # qwen3-14b heads: G = 40 / 8 = 5, padded to the sublane tile
    "qwen3-g5": dict(Hq=40, KH=8, hd=128, n=8, pos=[90, 33]),
    # gemma2-like: hd 256, logit softcap and a sliding window
    "hd256-softcap-window": dict(Hq=16, KH=8, hd=256, n=8, pos=[100, 20, 39],
                                 window=40, softcap=50.0, block_pages=2),
    "position-0": dict(Hq=12, KH=2, hd=128, n=4, pos=[0, 0]),
    "page-last-slot": dict(Hq=12, KH=2, hd=128, n=4, pos=[15, 31, 63],
                           block_pages=1),
    # chains shorter than the table: the tail points at the scratch page
    "scratch-tail": dict(Hq=4, KH=4, hd=128, n=8, pos=[5, 40, 17],
                         dtype="float32"),
    # row 1 is inactive with a stale position past its (scratch) table
    "inactive-row": dict(Hq=12, KH=2, hd=128, n=6, pos=[50, 5000, 30],
                         inactive=[1], block_pages=2),
}


def _paged_chains(rng, pos, n, ps, num_pages, inactive=()):
    """Distinct physical pages for each row's chain up to its position; the
    rest of the table (and an inactive row's whole table) is scratch."""
    pt = np.full((len(pos), n), SCRATCH_PAGE, np.int32)
    free = rng.permutation(np.arange(1, num_pages))
    used = 0
    for b, p in enumerate(pos):
        if b in inactive:
            continue
        c = min(p // ps + 1, n)
        pt[b, :c] = free[used:used + c]
        used += c
    return pt


@pytest.mark.parametrize("name", list(KERNEL_CASES))
def test_paged_decode_kernel_matches_oracle(name):
    from repro.kernels import paged_decode_attention, paged_decode_attention_ref
    c = dict(dict(ps=16, P=48, window=0, softcap=0.0, inactive=(),
                  block_pages=64, dtype="bfloat16"), **KERNEL_CASES[name])
    B, ps, hd = len(c["pos"]), c["ps"], c["hd"]
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, c["Hq"], hd), jnp.float32)
    kp, vp = (jax.random.normal(k, (c["P"], ps, c["KH"], hd),
                                jnp.float32).astype(c["dtype"])
              for k in ks[1:])
    pt = jnp.asarray(_paged_chains(np.random.default_rng(0), c["pos"],
                                   c["n"], ps, c["P"], c["inactive"]))
    pos = jnp.asarray(c["pos"], jnp.int32)
    active = jnp.asarray([b not in c["inactive"] for b in range(B)])
    kw = dict(window=c["window"], softcap=c["softcap"])
    out = paged_decode_attention(q, kp, vp, pt, pos, active,
                                 block_pages=c["block_pages"],
                                 interpret=True, **kw)
    want = paged_decode_attention_ref(q, kp, vp, pt, pos, **kw)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(out)).all()


def _lane_aligned_cfg():
    """qwen2-1.5b's smoke config with 128-wide heads: statically eligible
    for the paged decode kernel."""
    from repro.configs import get_config
    return get_config("qwen2-1.5b").smoke().replace(head_dim=128,
                                                    dtype="float32")


def test_apply_attn_paged_decode_kernel_matches_gather(monkeypatch):
    """``_apply_attn``'s paged decode branch through the kernel (interpret
    mode, selected as on a TPU) equals the gather path it replaces on the
    CPU: same token write, same attention output for every active row."""
    import functools
    from repro.kernels import paged_decode_attention
    from repro.models import transformer as T
    from repro.models.config import FULL_ATTN
    cfg = _lane_aligned_cfg()
    params = init_params(KEY, cfg)
    p = jax.tree.map(lambda a: a[0], params["stages"][0]["blocks"][0])
    B, P, ps, n = 3, 16, 16, 4
    pool = init_cache(cfg, B, n * ps, paged_pool=(P, ps),
                      dtype=jnp.float32)[0][0]
    ks = jax.random.split(KEY, 3)
    cache = {k: jax.random.normal(kk, a.shape[1:], a.dtype)
             for kk, (k, a) in zip(ks, sorted(pool.items()))}
    pos = jnp.asarray([40, 9, 63], jnp.int32)
    pt = jnp.asarray(_paged_chains(np.random.default_rng(1), [40, 9, 63], n,
                                   ps, P))
    active = jnp.asarray([True, False, True])
    x = jax.random.normal(ks[2], (B, 1, cfg.d_model), jnp.float32)

    def run():
        return T._apply_attn(cfg, p, x, FULL_ATTN, mode="decode",
                             positions=pos[:, None], cache=cache, pos=pos,
                             shd=T.NOSHARD, page_table=pt, active=active)

    want, want_cache = run()                 # lowered for the CPU: gather
    monkeypatch.setattr(T, "paged_decode_attention", functools.partial(
        paged_decode_attention, interpret=True))
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *a, tpu, default: tpu(*a))
    got, got_cache = run()
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)
    for k in cache:
        np.testing.assert_array_equal(got_cache[k], want_cache[k])


def test_paged_decode_kernel_predicate():
    """The kernel serves single-device, non-int8 pools with lane-aligned
    heads; int8 pools and serving meshes keep the gather path."""
    from jax.sharding import Mesh
    from repro.launch.shardings import make_serving_shard_ctx
    from repro.models.transformer import NOSHARD, paged_decode_kernel_ok
    cfg = _lane_aligned_cfg()
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    assert paged_decode_kernel_ok(cfg, NOSHARD)
    assert not paged_decode_kernel_ok(cfg, make_serving_shard_ctx(mesh))
    assert not paged_decode_kernel_ok(cfg.replace(kv_quant=True), NOSHARD)
    assert not paged_decode_kernel_ok(cfg.replace(head_dim=64), NOSHARD)


@pytest.mark.parametrize("variant", ["lane_aligned", "kv_quant"])
def test_paged_decode_off_tpu_serves_like_dense(variant):
    """Off a TPU every paged engine attends through the gather: a
    kernel-eligible model (pools carried through the layer scan) and an
    int8 pool serve the burst token-for-token like the dense engine, and
    the engine's kernel counter reads 0."""
    cfg = _lane_aligned_cfg() if variant == "lane_aligned" else \
        _cfg("kv_quant")
    params = init_params(KEY, cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in (19, 7, 12)]
    outs = [10, 6, 8]
    eng = _engine(cfg, params, paged=True)
    assert eng._attn == "gather"
    t_paged = _serve(eng, prompts, outs)
    s = eng.stats()
    assert s["decode_blocks"] > 0 and s["decode_blocks_kernel"] == 0
    assert t_paged == _serve(_engine(cfg, params, paged=False), prompts, outs)


# -- per-phase accounting ------------------------------------------------------

def test_stats_report_per_phase_energy_and_tokens():
    """Engine stats split energy/tokens by phase like sim.replay.Metrics."""
    cfg = _cfg("full")
    params = init_params(KEY, cfg)
    eng = _engine(cfg, params, paged=True)
    rng = np.random.default_rng(1)
    _serve(eng, [rng.integers(0, cfg.vocab_size, size=20)], [10])
    s = eng.stats()
    assert s["prefill_tokens"] == 20
    assert s["decode_tokens"] == 9       # first token is sampled in prefill
    assert s["prefill_energy_j"] > 0 and s["decode_energy_j"] > 0
    assert s["energy_j"] == pytest.approx(
        s["prefill_energy_j"] + s["decode_energy_j"])
