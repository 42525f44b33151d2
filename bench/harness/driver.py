"""The measured window: drives ``Server`` one round at a time.

All times are host ``perf_counter`` seconds.  A token counts as delivered
when the round that produced it returns: the engine drains its tokens to the
host once per block, so that moment is real.

* Closed loop: the driving thread submits each client's next request as soon
  as the round that finished the last one returns.
* Open loop: a generator thread releases the pre-built requests at their due
  wall times into a queue; the driving thread drains that queue into
  ``submit`` before each round and waits on it while the server is idle.
  Only the driving thread touches JAX.

The window opens when the first requests are submitted and closes at the
end of the first round that ends ``seconds`` or more after it opened (or at
that moment, when the server is idle then).  An open-loop run then keeps
serving, with nothing more released, until every request due in the window
has its first token (at most ``DRAIN_S``), so that a late first token is
counted late rather than cut off.
"""
from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
from typing import Callable, List, Optional

from .traffic import Plan, Req

DRAIN_S = 60.0


@dataclasses.dataclass
class Track:
    """One request as the client saw it."""
    req: Req
    handle: object
    due: float                       # when it was due, s
    released: float                  # when the generator let it go, s
    deliveries: list = dataclasses.field(default_factory=list)
    # (round index, round end s, tokens, tokens delivered before)
    left_queue: Optional[float] = None   # end of the first round after
    #                                      which it was no longer QUEUED
    tokens: list = dataclasses.field(default_factory=list)
    state: str = ""                  # final RequestState name

    @property
    def first_token(self) -> Optional[float]:
        return self.deliveries[0][1] if self.deliveries else None

    def detach(self) -> None:
        """Keep the served tokens and final state, drop the handle (which
        holds the server, its engine and the device state)."""
        self.tokens = list(self.handle.request.tokens)
        self.state = self.handle.request.state.name
        self.handle = None


@dataclasses.dataclass
class Round:
    start: float
    end: float
    steps: int          # decode steps run (the largest decode count of a row)
    decode_tokens: int  # tokens from decode steps
    first_tokens: int   # first tokens (from prefill)


@dataclasses.dataclass
class Window:
    start: float
    end: float                   # close of the measured window
    nominal_s: float
    tracks: List[Track]
    rounds: List[Round]
    drained_until: float = 0.0   # last time the run served (open loop)

    @property
    def seconds(self) -> float:
        return self.end - self.start


def _submit(server, r: Req, due: float, released: float) -> Track:
    from repro.core import SamplingParams
    sp = SamplingParams(max_tokens=r.max_tokens, temperature=r.temperature,
                        top_k=r.top_k, top_p=r.top_p, seed=r.lane_seed)
    return Track(r, server.submit(r.prompt, sp), due, released)


class _Rounds:
    """Bookkeeping after each round: new deliveries, queue exits, ends."""

    def __init__(self, server, on_round: Optional[Callable] = None):
        self.server = server
        self.live: List[Track] = []
        self.tracks: List[Track] = []
        self.rounds: List[Round] = []
        self.on_round = on_round

    def add(self, tr: Track):
        self.live.append(tr)
        self.tracks.append(tr)

    def pump(self) -> List[Track]:
        """One round; returns the tracks that finished in it.  The round is
        a host span in a trace, so idle gaps inside it are the engine's."""
        import jax
        from repro.core import RequestState
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("Server._pump"):
            if not self.server._pump():
                return []
        t = time.perf_counter()
        idx = len(self.rounds)
        finished, steps, dec, firsts = [], 0, 0, 0
        still = []
        for tr in self.live:
            req = tr.handle.request
            had = tr.deliveries[-1][3] + tr.deliveries[-1][2] \
                if tr.deliveries else 0
            n = len(req.tokens) - had
            if n > 0:
                first = 1 if had == 0 else 0
                tr.deliveries.append((idx, t, n, had))
                firsts += first
                dec += n - first
                steps = max(steps, n - first)
            if tr.left_queue is None and req.state is not RequestState.QUEUED:
                tr.left_queue = t
            if req.state.terminal:
                finished.append(tr)
            else:
                still.append(tr)
        self.live = still
        self.rounds.append(Round(t0, t, steps, dec, firsts))
        if self.on_round is not None:
            self.on_round(self, t)
        return finished


def run_closed(server, plan: Plan, seconds: float,
               on_round: Optional[Callable] = None) -> Window:
    rs = _Rounds(server, on_round)
    nxt = itertools.cycle(plan.queue)       # a long run serves the pool again
    t0 = time.perf_counter()
    for r in plan.initial:
        rs.add(_submit(server, r, t0, t0))
    while True:
        finished = rs.pump()
        t = time.perf_counter()
        if not rs.rounds or rs.rounds[-1].end < t0:
            raise RuntimeError("closed loop: the server has no work")
        for _ in finished:
            rs.add(_submit(server, next(nxt), t, t))
        if t - t0 >= seconds:
            return Window(t0, t, seconds, rs.tracks, rs.rounds, t)


def run_open(server, plan: Plan, seconds: float,
             on_round: Optional[Callable] = None,
             drain_s: float = DRAIN_S) -> Window:
    rs = _Rounds(server, on_round)
    released: "queue.Queue" = queue.Queue()
    stop = threading.Event()
    # requests due before the window (negative offsets) load the server
    t0 = time.perf_counter() + 0.05 - min(min(r.due for r in plan.queue), 0)

    def generate():
        for r in plan.queue:
            due = t0 + r.due
            while True:
                wait = due - time.perf_counter()
                if wait <= 0 or stop.wait(wait):
                    break
            if stop.is_set():
                return
            released.put((r, due, time.perf_counter()))

    gen = threading.Thread(target=generate, name="bench-generator",
                           daemon=True)
    gen.start()
    n_due = len(plan.queue)
    end = None
    try:
        while True:
            while True:
                try:
                    r, due, rel = released.get_nowait()
                except queue.Empty:
                    break
                rs.add(_submit(server, r, due, rel))
            now = time.perf_counter()
            if end is None and now - t0 >= seconds and \
                    not server.backend.has_work():
                end = now
            if end is not None:
                waiting = [tr for tr in rs.tracks if tr.first_token is None]
                if (len(rs.tracks) >= n_due and not waiting) \
                        or now - end >= drain_s:
                    break
            if server.backend.has_work():
                rs.pump()
                if end is None and rs.rounds[-1].end - t0 >= seconds:
                    end = rs.rounds[-1].end
            else:
                limit = t0 + seconds - now if end is None else 0.05
                try:
                    r, due, rel = released.get(timeout=max(limit, 0.001))
                except queue.Empty:
                    continue
                rs.add(_submit(server, r, due, rel))
    finally:
        stop.set()
        gen.join()
    return Window(t0, end, seconds, rs.tracks, rs.rounds,
                  time.perf_counter())
