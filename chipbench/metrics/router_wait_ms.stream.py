"""Median over requests of the time from arrival to the submission of the
request's wave, from the router's ``request`` and ``wave`` spans."""

from chipbench.metrics._common import median, router_waits_ms


def reduce(ctx):
    return median(router_waits_ms(ctx.spans))
