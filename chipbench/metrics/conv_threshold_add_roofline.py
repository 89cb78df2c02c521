"""The residual-join kernel's share of its roofline, in percent: the least
time the chip could take for the operations and bytes of the layers that
the configuration serves on that kernel (``layers(cfg)``, summed by
``cost.roofline_s``: int8 peak, HBM bandwidth) over the summed device time
of the kernel's events over the samples served in the profiled window.
Each wave calls the kernel once per such layer, and reads each layer's
weights once. Nothing to read where the configuration has no such layer
or no such kernel ran."""

from chipbench import cost

KERNEL = "conv_threshold_add"


def reduce(ctx):
    if ctx.device is None:
        return None
    layers = [layer for layer in ctx.layers if layer["kernel"] == KERNEL]
    t = ctx.device["kernel_s"].get(KERNEL, 0.0)
    calls = ctx.device["kernel_calls"].get(KERNEL, 0)
    samples = ctx.device["samples"]
    if not layers or t <= 0 or samples <= 0:
        return None
    least = cost.roofline_s(layers, samples, calls / len(layers),
                            ctx.peaks["int8_ops"],
                            ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least["least_s"] / t
