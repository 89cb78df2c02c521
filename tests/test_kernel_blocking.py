"""Kernel blocking is a schedule, never a result.

The row block of the conv kernels (``block_h``) and the tiles of the dense
kernel (``block_m``/``block_n``) only decide how a stage's work is cut into
grid steps: every choice must give the integers of the stage's XLA path
(``apply_fast``). The stages come from the benchmark's configurations at
their published channel counts, from seeded weights; the conv stages run at
small spatial sizes (interpret mode is slow), which leaves their weights,
banks and affine activations as they are.

The kernel planner's own row block (``plan_conv_blocks``) must also keep
each program's int32 accumulator inside its VMEM budget and never exceed
the output height, for every conv stage the benchmark serves.
"""

import dataclasses
import functools
import importlib
import inspect
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.deploy.lower import (ConvAddThresholdStage, FusedConvThresholdStage,
                                lower_graph)
from repro.kernels import ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the conv stages of the benchmark's schedules, by configuration (the KWS
#: MLP has none)
CONV_STAGES = {
    "cnv": ("conv0", "conv1", "conv2", "conv3", "conv4", "conv5"),
    "resnet8": ("stem", "b1_conv1", "b1_conv2", "b2_conv1", "b2_conv2",
                "b3_conv1", "b3_conv2"),
}
DENSE_STAGES = (("kws", "dense0"), ("kws", "dense1"), ("kws", "dense2"),
                ("cnv", "fc0"), ("cnv", "fc1"))


@functools.lru_cache(maxsize=None)
def config_graph(name, in_hw=None):
    """The benchmark configuration ``name`` as a QIR graph from seeded
    weights, optionally at another input size: ``(graph, in_scale)``."""
    sys.path.insert(0, ROOT)
    mod = importlib.import_module(f"chipbench.configs.{name}")
    with open(os.path.join(ROOT, "chipbench", "configs",
                           f"{name}.json")) as f:
        cfg = json.load(f)
    if in_hw is not None:
        cfg["in_hw"] = in_hw
    params = jax.jit(lambda k: mod.make_params(cfg, k))(
        jax.random.PRNGKey(3000017001))
    return mod.build_graph(cfg, params)


@functools.lru_cache(maxsize=None)
def config_stages(name):
    """The lowered stages of a configuration at its published size, by
    name."""
    graph, in_scale = config_graph(name)
    return {s.name: s for s in lower_graph(graph, in_scale=in_scale).stages}


def _codes(rng, shape, bits, signed):
    """Uniform input codes over a stage's declared input range."""
    if signed:
        hi = (1 << (bits - 1)) - 1
        return jnp.asarray(rng.integers(-hi, hi + 1, shape), jnp.int32)
    return jnp.asarray(rng.integers(0, 1 << bits, shape), jnp.int32)


def _at_out_size(s, out_hw):
    """The stage with its spatial size cut so that its output is
    ``out_hw`` square; kernel, stride, padding and channels unchanged."""
    g = s.geom
    if g.padding == "SAME":
        in_hw = out_hw * g.stride
    else:
        in_hw = (out_hw - 1) * g.stride + g.kernel
    geom = dataclasses.replace(g, in_h=in_hw, in_w=in_hw, out_h=out_hw,
                               out_w=out_hw)
    return dataclasses.replace(s, geom=geom)


def _conv_kernel(s, x, skip, block_h, affine):
    """The stage's direct-conv or join kernel at an explicit row block."""
    g = s.geom
    operand, x_offset = s.mxu
    kw = dict(kernel=g.kernel, stride=g.stride, padding=g.padding,
              out_h=g.out_h, out_w=g.out_w, block_h=block_h, operand=operand,
              x_offset=x_offset, affine=affine, interpret=True)
    if isinstance(s, ConvAddThresholdStage):
        skip_operand, skip_offset = s.skip_mxu
        return ops.conv_threshold_add(
            x, s.stage.w_int, skip, s.w_skip, jnp.asarray(s.mul),
            s.stage.thresholds, skip_stride=s.skip_stride,
            skip_operand=skip_operand, skip_offset=skip_offset, **kw)
    return ops.conv_threshold(x, s.stage.w_int, s.stage.thresholds, **kw)


@pytest.mark.parametrize("block_h", [1, 2, None], ids=["bh1", "bh2",
                                                        "planner"])
@pytest.mark.parametrize("config,stage", [(c, s) for c, names in
                                          CONV_STAGES.items()
                                          for s in names])
def test_conv_kernel_row_block_never_changes_the_integers(config, stage,
                                                          block_h):
    """The conv or join kernel at row block 1, 2 and the planner's gives
    the stage's ``apply_fast`` integers, in the count form and, where the
    lowering proved one, in the affine form. An output height of 5 leaves
    a ragged last block at row block 2."""
    s = _at_out_size(config_stages(config)[stage], 5)
    g = s.geom
    rng = np.random.default_rng(17)
    x = _codes(rng, (2, g.in_h, g.in_w, g.in_ch), s.in_bits, s.in_signed)
    skip = None
    if isinstance(s, ConvAddThresholdStage):
        if s.w_skip is None:
            shape = (2, g.out_h, g.out_w, g.out_ch)
        else:
            side = g.out_h * s.skip_stride
            shape = (2, side, side, s.w_skip.shape[0])
        skip = _codes(rng, shape, s.skip_bits, False)
        want = np.asarray(s.apply_fast(x, skip))
    else:
        want = np.asarray(s.apply_fast(x))
    assert len(np.unique(want)) > 1, f"{stage}: every code the same"
    forms = [None] + ([s.affine] if s.affine_on_kernel else [])
    assert len(forms) == (2 if config == "resnet8" else 1)
    for affine in forms:
        got = _conv_kernel(s, x, skip, block_h, affine)
        np.testing.assert_array_equal(
            np.asarray(got), want,
            err_msg=f"{config}/{stage} block_h={block_h} "
                    f"{'affine' if affine is not None else 'count'}")


@pytest.mark.parametrize("block_m,block_n", [(8, 128), (32, 256),
                                             (128, 128)])
@pytest.mark.parametrize("config,stage", DENSE_STAGES)
def test_dense_kernel_tiles_never_change_the_integers(config, stage,
                                                      block_m, block_n):
    """``threshold_matmul`` over several (block_m, block_n) tiles gives a
    dense stage's ``apply_fast`` integers, with rows and columns that do
    not divide the tile."""
    s = config_stages(config)[stage]
    rng = np.random.default_rng(18)
    x = _codes(rng, (37, s.in_dim), s.in_bits, s.in_signed)
    operand, x_offset = s.mxu
    got = ops.threshold_matmul(x, s.stage.w_int, s.stage.thresholds,
                               operand=operand, x_offset=x_offset,
                               block_m=block_m, block_n=block_n,
                               interpret=True)
    want = np.asarray(s.apply_fast(x))
    assert len(np.unique(want)) > 1, f"{stage}: every code the same"
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("config,stage", [(c, s) for c, names in
                                          CONV_STAGES.items()
                                          for s in names])
def test_planned_row_block_fits_its_budget(config, stage):
    """At the published sizes the planner's row block is at least 1, at
    most the output height, and keeps the int32 accumulator block inside
    the VMEM budget unless one row alone exceeds it."""
    g = config_stages(config)[stage].geom
    budget = inspect.signature(ops.plan_conv_blocks).parameters[
        "acc_budget_bytes"].default
    bh = ops.plan_conv_blocks(g.out_h, g.out_w, g.out_ch)
    assert 1 <= bh <= g.out_h
    acc_bytes = bh * g.out_w * g.out_ch * 4
    assert acc_bytes <= budget or bh == 1
    # the block is the largest under the row target: one more row would
    # pass the target, the height or the budget
    assert (bh == g.out_h or (bh + 1) * g.out_w > 256
            or (bh + 1) * g.out_w * g.out_ch * 4 > budget)
