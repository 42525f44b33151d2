"""Rows per decode step: decode tokens delivered in the window over the
decode steps run (a round's steps are the most decode tokens one request
got in it; a block never crosses a finish)."""


def read(run):
    w = run.window
    rounds = [r for r in w.rounds if w.start <= r.end <= w.end]
    steps = sum(r.steps for r in rounds)
    return sum(r.decode_tokens for r in rounds) / steps if steps else None
