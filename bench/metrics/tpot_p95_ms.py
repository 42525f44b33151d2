"""Time per output token, p95 over the requests with two or more deliveries
in the window: (last delivery - first delivery) / tokens after the first."""
from harness import measures


def read(run):
    return measures.percentile(measures.tpot_ms(run), 95)
