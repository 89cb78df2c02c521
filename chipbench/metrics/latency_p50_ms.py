"""Median latency of every request of the window, from when it was due to
its result (host clock)."""

from chipbench.metrics._common import percentile


def reduce(ctx):
    return percentile(ctx.latencies_ms, 50)
