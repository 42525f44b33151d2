"""Decode block program's share of the HBM roofline: every weight byte once
per step plus the K/V bytes of each live row's own context, at the cell's
chips x one chip's peak HBM bytes/s, over the program's device time in the
trace (a mean over those chips)."""
from harness import flops, measures


def read(run):
    s = measures.module_seconds(run, "_paged_decode_block_kernel")
    if not s:
        return None
    _, keys, steps = measures.traced_work(run)
    if not steps:
        return None
    need = steps * flops.weight_bytes_per_step(run.spec) + \
        sum(flops.kv_bytes(run.spec, k) for k in keys)
    return 100.0 * need / (run.chips * run.peaks["hbm_bytes_per_s"]) / s
