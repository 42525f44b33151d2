"""Model FLOPs of every prompt and output token in the traced window over
(traced seconds x the cell's chips x one chip's peak bf16 FLOP/s), in
percent."""
from harness import flops, measures


def read(run):
    if run.trace is None:
        return None
    prompts, keys, _ = measures.traced_work(run)
    need = sum(flops.prefill_flops(run.spec, n) for n in prompts) + \
        sum(flops.decode_flops(run.spec, k) for k in keys)
    if not need:
        return None
    return 100.0 * need / (run.trace["window_s"]
                           * (run.chips * run.peaks["bf16_flops_per_s"]))
