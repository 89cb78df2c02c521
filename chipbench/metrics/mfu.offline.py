"""The whole model's share of the chips' int8 peak, in percent: operations
per sample (``cost.ops_per_sample`` over the configuration's layers) times
samples per second of the measured window, which neither the span
recorder nor the profiler slows, over the peak of the chips used."""

from chipbench import cost


def reduce(ctx):
    if ctx.peaks is None or ctx.window_s <= 0 or ctx.samples <= 0:
        return None
    rate = ctx.samples / ctx.window_s
    return (100.0 * cost.ops_per_sample(ctx.layers) * rate
            / (ctx.peaks["int8_ops"] * ctx.chips))
