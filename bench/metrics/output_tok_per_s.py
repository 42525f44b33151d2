"""Every output token delivered in the window over the window's seconds."""
from harness import measures


def read(run):
    n = measures.output_tokens(run)
    return n / run.window.seconds if n else None
