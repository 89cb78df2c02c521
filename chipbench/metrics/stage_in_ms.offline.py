"""Median over waves of the time to stage the wave's input on the chip:
the ``replica.put`` span (``device_put`` of the stacked rows) plus the
``executor.pad`` span (the input back to the host, host padding, the
second ``device_put``), summed per wave by the wave's id."""

from chipbench.metrics._common import median
from chipbench.metrics._phases import per_wave_ms


def reduce(ctx):
    return median(per_wave_ms(ctx.spans, ("replica.put", "executor.pad")))
