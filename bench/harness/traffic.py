"""One general generator for every traffic mix: a mix is a data file of
parameters under ``bench/traffic/``, read here.

Every seed gets the same multiset of sizes (and, in an open loop, of
inter-arrival gaps), in another order: lengths are lognormal quantiles, gaps
a fixed set of gamma draws scaled to the window.  The seed only permutes
them, pairs prompts with outputs, assigns the sampling modes and draws the
token ids.  So seeds differ in which requests meet in a batch, not in how
much work a run offers.

Two loops:

* ``closed``: ``clients`` callers, each sending its next request when the
  last one finished (no think time).  The run starts in steady state: each
  client's first request is one already in progress, its context the
  prompt plus the tokens it would already have generated, its remaining
  output the rest.  Those sizes are the same for every seed (prompt
  quantiles, output quantiles of the length-biased lognormal, paired and
  advanced by a fixed draw), so every seed starts at the same context
  bucket; the seed orders them.  Later requests come from a pool of
  ``pool`` sizes.
* ``open``: requests due at fixed offsets from the window's start, gamma
  inter-arrivals of shape ``burst_k`` at ``rate_per_s``, from ``warm_s``
  before the window (served, not measured, so the window opens on a
  loaded server) to its end; the gaps of the warm-up and of the window are
  drawn apart, so exactly ``round(rate_per_s * seconds)`` requests are due
  inside the window.

The engine keeps only the last ``max_len // 2`` prompt tokens, so prompts
(and steady-state contexts) are clipped there, and every output to
``max_len - 1 - prompt``: what is generated is what is served.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List, Optional

import numpy as np

SEED_MASK = (1 << 31) - 1


@dataclasses.dataclass
class Req:
    idx: int                  # position in the plan (stable id)
    prompt: np.ndarray        # int32 token ids, as submitted
    max_tokens: int           # output tokens to serve
    temperature: Optional[float]
    top_k: int
    top_p: float
    lane_seed: Optional[int]  # PRNG lane of a sampled request
    due: float = 0.0          # open loop: offset from the window start, s

    @property
    def greedy(self) -> bool:
        return not self.temperature


@dataclasses.dataclass
class Plan:
    loop: str                 # "closed" | "open"
    initial: List[Req]        # closed: the clients' in-progress requests
    queue: List[Req]          # closed: later requests in order; open: all
    clients: int = 0


def lognormal_quantiles(p: dict, n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` lengths at the lognormal's quantiles (i + 0.5) / n, clipped."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.exp(p["mu"] + p["sigma"] * z), lo, hi).astype(np.int64)


def gamma_gaps(k: float, n: int, total: float) -> np.ndarray:
    """``n`` gamma(k) inter-arrival gaps from a fixed draw, scaled so that
    they sum to ``total``: the same set for every seed."""
    g = np.random.default_rng(0).gamma(k, 1.0, n)
    return g * (total / g.sum())


def sampling_modes(mix: list, n: int, rng) -> list:
    """Assign ``n`` requests to the mix's sampling modes in proportion to
    their shares (largest remainder), in a seeded order."""
    shares = np.array([m["share"] for m in mix], float)
    raw = shares / shares.sum() * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[: n - counts.sum()]:
        counts[i] += 1
    modes = [m for m, c in zip(mix, counts) for _ in range(c)]
    return [modes[i] for i in rng.permutation(n)]


def _req(idx, prompt, out, mode, seed) -> Req:
    greedy = mode.get("greedy", False)
    return Req(idx=idx, prompt=prompt, max_tokens=int(out),
               temperature=None if greedy else float(mode["temperature"]),
               top_k=int(mode.get("top_k", 0)),
               top_p=float(mode.get("top_p", 1.0)),
               lane_seed=None if greedy else
               (seed * 1_000_003 + idx) & SEED_MASK)


def plan(traffic: dict, vocab: int, max_len: int, seed: int,
         seconds: float) -> Plan:
    """The requests of one run of ``traffic`` from ``seed``."""
    rng = np.random.default_rng(seed)
    p_hi = min(traffic["prompt"]["max"], max_len // 2)
    p_lo = min(traffic["prompt"]["min"], p_hi)
    warm = float(traffic.get("warm_s", 0.0))
    if traffic["loop"] == "open":
        n_win = max(int(round(traffic["rate_per_s"] * seconds)), 1)
        n_warm = int(round(traffic["rate_per_s"] * warm))
        n = n_win + n_warm
    else:
        n = int(traffic["pool"])
    prompts = lognormal_quantiles(traffic["prompt"], n, p_lo, p_hi)
    outs = lognormal_quantiles(traffic["output"], n, traffic["output"]["min"],
                               traffic["output"]["max"])
    prompts = prompts[rng.permutation(n)]
    outs = outs[rng.permutation(n)]
    outs = np.minimum(outs, max_len - 1 - prompts)
    modes = sampling_modes(traffic["sampling"], n, rng)
    reqs = [_req(i, rng.integers(0, vocab, int(L), dtype=np.int32), o, m,
                 seed)
            for i, (L, o, m) in enumerate(zip(prompts, outs, modes))]
    if traffic["loop"] == "open":
        due = []
        for m, span, start in ((n_warm, warm, -warm), (n_win, seconds, 0.0)):
            if m:
                g = gamma_gaps(traffic["burst_k"], m, span)
                due.extend(start + np.concatenate(
                    [[0.0], np.cumsum(g[rng.permutation(m)])[:-1]]))
        for r, d in zip(reqs, due):
            r.due = float(d)
        return Plan("open", [], reqs)
    return Plan("closed", steady_state(traffic, vocab, max_len, seed, n, rng),
                reqs, int(traffic["clients"]))


def steady_state(traffic: dict, vocab: int, max_len: int, seed: int,
                 first_idx: int, rng) -> List[Req]:
    """The clients' in-progress requests.  A client is found in a request
    for a time proportional to its output length, so outputs are quantiles
    of the length-biased lognormal (mu + sigma^2, sigma); the share already
    generated is uniform.  Pairing and shares are one fixed draw."""
    k = int(traffic["clients"])
    p, o = traffic["prompt"], traffic["output"]
    p_hi = min(p["max"], max_len // 2)
    prompts = lognormal_quantiles(p, k, min(p["min"], p_hi), p_hi)
    biased = dict(o, mu=o["mu"] + o["sigma"] ** 2)
    fixed = np.random.default_rng(0)
    outs = lognormal_quantiles(biased, k, o["min"], o["max"])
    outs = np.minimum(outs[fixed.permutation(k)], max_len - 1 - prompts)
    share = ((np.arange(k) + 0.5) / k)[fixed.permutation(k)]
    done = np.minimum((share * outs).astype(np.int64), p_hi - prompts)
    modes = sampling_modes(traffic["sampling"], k, rng)
    return [_req(first_idx + j,
                 rng.integers(0, vocab, int(prompts[i] + done[i]),
                              dtype=np.int32),
                 outs[i] - done[i], modes[j], seed)
            for j, i in enumerate(rng.permutation(k))]
