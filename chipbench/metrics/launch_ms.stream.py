"""Median over waves of the ``executor.launch`` span: dispatch of every
segment program of the wave and of the unpadding slice (host time; the
device runs behind it)."""

from chipbench.metrics._common import median
from chipbench.metrics._phases import per_wave_ms


def reduce(ctx):
    return median(per_wave_ms(ctx.spans, ("executor.launch",)))
