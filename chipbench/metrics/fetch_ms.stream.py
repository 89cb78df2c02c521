"""Median over waves of the ``router.fetch`` span: the result and its mask
to the host, and the mask and integrity checks, after the wave's stamped
completion."""

from chipbench.metrics._common import median
from chipbench.metrics._phases import per_wave_ms


def reduce(ctx):
    return median(per_wave_ms(ctx.spans, ("router.fetch",)))
