"""Share of the measured window in which no operation ran on the device, in
percent (mean over the chips used): the profiled window's device busy time
per sample, paced at the measured window's rate (``idle_share_pct``)."""

from chipbench.metrics._common import idle_share_pct


def reduce(ctx):
    return idle_share_pct(ctx)
