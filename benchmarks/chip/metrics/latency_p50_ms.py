"""Median latency of all answered requests, from each one's due time to
its logits reaching the caller."""
from loadgen import quantile


def read(run):
    return 1e3 * quantile(run.latencies(), 0.50)
