"""Median ``wave`` span of the router: submission to stamped completion
(padding, transfers, dispatch, device time and the reap poll)."""

from chipbench.metrics._common import median, wave_ms


def reduce(ctx):
    return median(wave_ms(ctx.spans))
