"""Device time of the paged decode block program in the trace over the
decode steps it ran there."""
from harness import measures


def read(run):
    s = measures.module_seconds(run, "_paged_decode_block_kernel")
    steps = measures.traced_work(run)[2] if s else 0
    return s / steps * 1e3 if steps else None
