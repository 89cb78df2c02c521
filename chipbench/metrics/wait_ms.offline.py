"""Median over waves of the ``wave.wait`` span: the host blocked until the
wave's result was ready on the device."""

from chipbench.metrics._common import median
from chipbench.metrics._phases import per_wave_ms


def reduce(ctx):
    return median(per_wave_ms(ctx.spans, ("wave.wait",)))
