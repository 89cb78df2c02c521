"""Fused direct-conv kernel: property-based bit-exactness.

The contract under test is the lowering-independence of the po2 export
contract: for any conv geometry, a ``Conv2D -> Relu -> Quant`` chain built
under the exporter's grid rules (po2 per-channel weight scales, bias on the
accumulator grid, po2 frozen activation scale) must produce the *same
integers* through

  * the unfused ``Graph.run`` float interpreter (half-up rounding),
  * the direct lowering's CPU fast path (XLA conv / shifted-window taps),
  * the im2col lowering (patch matrix + threshold matmul), and
  * the fused direct-conv Pallas kernel (interpret mode on CPU),

ties included. The property sweep covers strides, SAME/VALID padding,
K in {1, 3, 5}, odd H/W, channel counts that are not a multiple of any
block size, forced multi-block row grids, and tie-threshold inputs
(``s_out`` chosen so *every* step boundary lands exactly on the
accumulator grid — the half-up tie rule fires on every step).
"""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, strategies as st

from repro.core.qir import Graph, Node, QuantSpec
from repro.deploy.lower import FusedConvThresholdStage, lower_graph


def _conv_out_hw(h, w, k, stride, padding):
    if padding == "SAME":
        return -(-h // stride), -(-w // stride)
    return (h - k) // stride + 1, (w - k) // stride + 1


def _po2_conv_graph(rng, h, w, c, f, k, stride, padding, bits, ties):
    """One Conv2D -> Relu -> Quant chain under the po2 export contract.

    Mirrors ``core.qir._export_ic``: integer weight codes times a po2
    per-channel scale (recorded in ``attrs["w_scale"]``), bias snapped to
    the accumulator grid, po2 frozen activation scale. With ``ties`` the
    activation scale makes every threshold boundary an exact accumulator
    integer, so every step decision is a tie the half-up rule must break.
    """
    in_scale = 0.5                                   # po2 input step
    w_int = rng.integers(-7, 8, (k * k * c, f)).astype(np.float32)
    s_w = (2.0 ** rng.integers(-2, 1, (f,))).astype(np.float32)   # po2
    w_hat = (w_int * s_w).reshape(k, k, c, f)
    grid = s_w * in_scale                            # accumulator step
    b = (rng.integers(-5, 6, (f,)).astype(np.float32)) * grid
    if ties:
        # boundary (i - 0.5) * s_out on the grid: s_out = 2 * min(grid)
        s_out = float(2.0 * grid.min())
    else:
        s_out = float(2.0 ** rng.integers(-1, 3))
    oh, ow = _conv_out_hw(h, w, k, stride, padding)
    g = Graph(inputs=["x"], outputs=["y"], meta={"in_scale": in_scale},
              initializers={"w": w_hat, "b": b, "ws": s_w})
    g.nodes = [
        Node("Conv2D", "conv", ["x", "w", "b"], ["h0"],
             attrs={"kernel": k, "stride": stride, "padding": padding,
                    "weight_bits": 4, "w_scale": "ws",
                    "in_shape": [h, w, c], "out_shape": [oh, ow, f]}),
        Node("Relu", "relu", ["h0"], ["h1"]),
        Node("Quant", "quant", ["h1"], ["y"], attrs={"scale": s_out},
             quant=QuantSpec(bits=bits, signed=False)),
    ]
    return g, in_scale, (oh, ow)


def _check_all_paths(rng, h, w, c, f, k, stride, padding, bits, ties,
                     block_h=None):
    g, in_scale, (oh, ow) = _po2_conv_graph(
        rng, h, w, c, f, k, stride, padding, bits, ties)
    direct = lower_graph(g, in_scale=in_scale, conv_lowering="direct")
    i2c = lower_graph(g, in_scale=in_scale, conv_lowering="im2col")
    st_d, st_i = direct.stages[0], i2c.stages[0]
    assert isinstance(st_d, FusedConvThresholdStage)
    assert st_d.lowering == "direct" and st_i.lowering == "im2col"

    x_int = jnp.asarray(rng.integers(-15, 16, (2, h, w, c)), jnp.int32)

    # 1) unfused float interpreter (half-up reference), bit for bit
    run = g.run({"x": np.asarray(x_int, np.float32) * in_scale})["y"]
    y_d = np.asarray(st_d.apply_fast(x_int)).reshape(2, oh, ow, f)
    np.testing.assert_array_equal(y_d * st_d.stage.out_scale, run)
    # 2) the two lowerings agree exactly
    y_i = np.asarray(st_i.apply_fast(x_int)).reshape(2, oh, ow, f)
    np.testing.assert_array_equal(y_d, y_i)
    np.testing.assert_array_equal(np.asarray(st_d.apply_ref(x_int)), y_d)
    # 3) the fused Pallas kernel (interpret mode), incl. forced row blocks
    from repro.kernels import ops

    y_k = ops.conv_threshold(
        x_int, st_d.stage.w_int, st_d.stage.thresholds, kernel=k,
        stride=stride, padding=padding, out_h=oh, out_w=ow,
        block_h=block_h, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_k), y_d)
    # 4) the same kernel ending its rows in the stage's proven O(1) affine
    if st_d.affine is not None:
        y_a = ops.conv_threshold(
            x_int, st_d.stage.w_int, st_d.stage.thresholds, kernel=k,
            stride=stride, padding=padding, out_h=oh, out_w=ow,
            block_h=block_h, affine=st_d.affine, interpret=True)
        np.testing.assert_array_equal(np.asarray(y_a), y_d)


@settings(max_examples=12, deadline=None)
@given(
    st.sampled_from([1, 3, 5]),          # kernel
    st.sampled_from([1, 2]),             # stride
    st.sampled_from(["SAME", "VALID"]),  # padding
    st.sampled_from([5, 7, 9]),          # odd H
    st.sampled_from([5, 7, 9]),          # odd W
    st.sampled_from([1, 3, 5]),          # C: never a block-size multiple
    st.sampled_from([2, 4, 5]),          # F
    st.sampled_from([2, 3]),             # act bits
    st.booleans(),                       # tie-threshold inputs
    st.integers(0, 10_000),              # data seed
)
def test_direct_conv_bit_exact_property(k, stride, padding, h, w, c, f,
                                        bits, ties, seed):
    rng = np.random.default_rng(seed)
    _check_all_paths(rng, h, w, c, f, k, stride, padding, bits, ties)


def test_direct_conv_forced_multiblock_grid():
    """block_h=1/2 forces the padded multi-block row grid (OH % block_h
    handling) on odd output heights."""
    rng = np.random.default_rng(99)
    for bh in (1, 2):
        _check_all_paths(rng, 7, 5, 3, 4, 3, 2, "SAME", 3, False,
                         block_h=bh)


def test_direct_conv_every_boundary_is_a_tie():
    """Deterministic tie sweep: s_out = 2*grid makes every threshold an
    exact accumulator integer — half-up must count the boundary in."""
    rng = np.random.default_rng(7)
    _check_all_paths(rng, 6, 6, 2, 3, 3, 1, "SAME", 2, True)
    _check_all_paths(rng, 8, 6, 4, 3, 5, 1, "VALID", 3, True)


@pytest.mark.parametrize("k,stride,padding", [(4, 4, "SAME"),
                                               (3, 2, "SAME"),
                                               (2, 3, "VALID"),
                                               (5, 2, "VALID")])
@pytest.mark.parametrize("operand,x_offset", [("bf16", 0), ("int8", 128)])
def test_strided_conv_kernel_is_exact(k, stride, padding, operand, x_offset):
    """Strided convs run as stride-1 convs over pixel blocks
    (``space_to_depth``), on unsigned 8-bit codes in either exact MXU
    form; IC's stride-4 layer is the first case."""
    from repro.core.streamline import multi_threshold
    from repro.kernels import ops
    from repro.kernels.conv_threshold import direct_conv_acc, same_pads

    rng = np.random.default_rng(k * 10 + stride)
    h, c, f = 13, 3, 5
    oh = _conv_out_hw(h, h, k, stride, padding)[0]
    x = jnp.asarray(rng.integers(0, 256, (2, h, h, c)), jnp.int32)
    w2d = jnp.asarray(rng.integers(-127, 128, (k * k * c, f)), jnp.int8)
    thr = jnp.asarray(np.sort(rng.integers(-60000, 60000, (f, 7)), axis=1),
                      jnp.int32)
    pads = same_pads(h, h, oh, oh, stride, k) if padding == "SAME" \
        else ((0, 0), (0, 0))
    acc = direct_conv_acc(jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0))),
                          w2d, kernel=k, stride=stride, out_h=oh, out_w=oh)
    y = ops.conv_threshold(x, w2d, thr, kernel=k, stride=stride,
                           padding=padding, out_h=oh, out_w=oh,
                           operand=operand, x_offset=x_offset,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(multi_threshold(acc, thr)))


def test_plan_conv_blocks_shapes():
    """The kernel planner sizes row blocks from the output tile, within
    bounds."""
    from repro.kernels.ops import plan_conv_blocks

    assert plan_conv_blocks(32, 32, 16) == 8      # 256-row target
    assert plan_conv_blocks(1, 1024, 4) == 1      # never 0
    assert plan_conv_blocks(5, 3, 8) == 5         # capped at out_h
    # accumulator VMEM cap kicks in for huge channel counts
    assert plan_conv_blocks(64, 64, 8192, acc_budget_bytes=1 << 21) == 1


def test_conv_lowering_env_override(monkeypatch):
    """REPRO_CONV_LOWERING flips the default; explicit arg still wins;
    junk values fail loudly."""
    from repro.deploy.lower import default_conv_lowering

    monkeypatch.delenv("REPRO_CONV_LOWERING", raising=False)
    assert default_conv_lowering() == "direct"
    monkeypatch.setenv("REPRO_CONV_LOWERING", "im2col")
    assert default_conv_lowering() == "im2col"
    rng = np.random.default_rng(1)
    g, in_scale, _ = _po2_conv_graph(rng, 6, 6, 2, 3, 3, 1, "SAME", 2, False)
    assert lower_graph(g, in_scale=in_scale).stages[0].lowering == "im2col"
    assert lower_graph(g, in_scale=in_scale,
                       conv_lowering="direct").stages[0].lowering == "direct"
    monkeypatch.setenv("REPRO_CONV_LOWERING", "bogus")
    with pytest.raises(ValueError):
        lower_graph(g, in_scale=in_scale)
    with pytest.raises(ValueError):
        lower_graph(g, in_scale=in_scale, conv_lowering="also-bogus")


def test_conv_threshold_rejects_bad_geometry():
    from repro.kernels import conv_threshold as ct

    x = jnp.zeros((1, 4, 4, 2), jnp.int32)
    w2d = jnp.zeros((3 * 3 * 2, 4), jnp.int8)
    thr = jnp.zeros((4, 3), jnp.int32)
    with pytest.raises(AssertionError):
        ct.conv_threshold(x, w2d, thr, kernel=3, stride=1, out_h=4,
                          out_w=2, block_h=3, interpret=True)  # 4 % 3 != 0


# -- the O(1) affine activation on the kernel path ----------------------------

#: (operand, x_offset) forms ``deploy.lower.mxu_form`` picks between
FORMS = [("bf16", 0), ("int8", 128)]

#: per output channel: the bank's shift in ramp units (channel 0's
#: boundary i sits at ramp value i + shift), the centre tap's ramp weight
#: and its noise weight
CHANNELS = [(-20, 64, 0), (0, 64, 1), (-128, 32, -1), (-250, 64, 2)]


def boundary_case(rng, n, hw, stride):
    """A 3x3 SAME conv of 8-bit codes, and an 8-bit affine activation,
    such that channel 0's accumulators meet every threshold boundary
    exactly and fall below code 0 and past code 255.

    Input channels 0 and 1 carry, at the centre pixel of each output's
    window, a ramp d = x0 - x1 over [-255, 255]; channel 2 is noise.
    Output channel f weighs the centre tap (r, -r, e) (``CHANNELS``) and,
    past channel 0, every other tap with noise in [-2, 2]. On the po2
    accumulator grid 2^-6 with ``s_out`` 1, boundary i lies at accumulator
    ``64 * (i + shift)``: for channel 0, at ramp value i + shift. Returns
    ``(x, w2d, grid, bias, s_out)``."""
    from repro.kernels.conv_threshold import same_pads

    oh = -(-hw // stride)
    assert n * oh * oh >= 511, (n, hw, stride)
    lo = same_pads(hw, hw, oh, oh, stride, 3)[0][0]
    x = rng.integers(0, 256, (n, hw, hw, 3)).astype(np.int32)
    d = np.resize(np.arange(-255, 256), n * oh * oh).reshape(n, oh, oh)
    centre = np.arange(oh) * stride + 1 - lo
    at = np.ix_(np.arange(n), centre, centre)
    xs = x[at]
    xs[..., 0], xs[..., 1] = np.maximum(d, 0), np.maximum(-d, 0)
    x[at] = xs
    f = len(CHANNELS)
    w = rng.integers(-2, 3, (3, 3, 3, f))
    w[..., 0] = 0
    for c, (_, r, e) in enumerate(CHANNELS):
        w[1, 1, :, c] = (r, -r, e)
    grid = np.full(f, 2.0 ** -6)
    shift = np.array([c[0] for c in CHANNELS], np.float64)
    bias = grid * (-32 - 64 * shift)
    return x, w.reshape(9 * 3, f).astype(np.int8), grid, bias, 1.0


def affine_bank(grid, bias, s_out, steps=255):
    """The (F, S) int32 bank the affine form stands for: boundary i at
    ``ceil(((i - 0.5) * s_out - b) / g)`` (as ``deploy.lower`` builds a
    join's)."""
    i = np.arange(1, steps + 1, dtype=np.float64)
    return np.ceil(((i[None] - 0.5) * s_out - bias[:, None])
                   / grid[:, None]).astype(np.int32)


def proven_affine(grid, bias, s_out, reach):
    """The lowering's (mul, add) for this grid, which must exist."""
    from repro.deploy.lower import _affine_on_grid

    affine = _affine_on_grid(grid, bias, s_out, reach)
    assert affine is not None
    return affine


def assert_meets_every_boundary(total, grid, bias, s_out, bank, codes):
    """Channel 0's totals sit on each of its boundaries, and below code 0
    and past code S before the clip; its codes take every value."""
    steps = bank.shape[1]
    pre = np.floor(total * grid / s_out + bias / s_out + 0.5)[..., 0]
    assert np.isin(bank[0], total[..., 0]).all()
    assert (pre < 0).any() and (pre > steps).any()
    np.testing.assert_array_equal(np.unique(codes[..., 0]),
                                  np.arange(steps + 1))


def _stage(w2d, bank, affine, hw, stride):
    from repro.core.streamline import ThresholdDense
    from repro.deploy.lower import ConvGeom

    f = w2d.shape[1]
    oh = -(-hw // stride)
    td = ThresholdDense(w_int=jnp.asarray(w2d),
                        thresholds=jnp.asarray(bank), out_scale=1.0,
                        act_bits=8, weight_bits=8)
    return FusedConvThresholdStage(
        name="conv", stage=td,
        geom=ConvGeom(3, stride, "SAME", hw, hw, 3, oh, oh, f),
        in_scale=2.0 ** -6, affine=affine)


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("operand,x_offset", FORMS)
def test_affine_conv_kernel_is_bit_exact_on_every_boundary(
        stride, operand, x_offset):
    """The conv kernel ending its rows in the proven affine gives the
    codes of the bank count (the same kernel without it), of a NumPy
    count of the int64 accumulator, and of the stage's ``apply_ref``:
    every boundary a tie, codes clipped at 0 and at 255, stride 1 and 2,
    in both MXU forms."""
    from repro.core.streamline import multi_threshold
    from repro.kernels import ops
    from repro.kernels.conv_threshold import direct_conv_acc, same_pads

    rng = np.random.default_rng(16 + stride)
    hw = 16 * stride
    oh = hw // stride
    x, w2d, grid, bias, s_out = boundary_case(rng, 2, hw, stride)
    reach = np.sum(np.abs(w2d.astype(np.int64)), axis=0) * 255
    affine = proven_affine(grid, bias, s_out, reach)
    bank = affine_bank(grid, bias, s_out)
    pads = same_pads(hw, hw, oh, oh, stride, 3)
    acc = np.asarray(direct_conv_acc(
        jnp.pad(jnp.asarray(x), ((0, 0), pads[0], pads[1], (0, 0))),
        jnp.asarray(w2d), kernel=3, stride=stride, out_h=oh, out_w=oh))
    want = np.asarray(multi_threshold(jnp.asarray(acc), jnp.asarray(bank)))
    assert_meets_every_boundary(acc.astype(np.float64), grid, bias, s_out,
                                bank, want)
    kw = dict(kernel=3, stride=stride, padding="SAME", out_h=oh, out_w=oh,
              block_h=4, operand=operand, x_offset=x_offset,
              interpret=True)
    x, w2d, bank = jnp.asarray(x), jnp.asarray(w2d), jnp.asarray(bank)
    counted = ops.conv_threshold(x, w2d, bank, **kw)
    affined = ops.conv_threshold(x, w2d, bank, affine=affine,
                                 **kw)
    np.testing.assert_array_equal(np.asarray(counted), want)
    np.testing.assert_array_equal(np.asarray(affined), want)
    stage = _stage(w2d, bank, affine, hw, stride)
    assert stage.affine_on_kernel
    np.testing.assert_array_equal(np.asarray(stage.apply_ref(x)), want)
    np.testing.assert_array_equal(
        np.asarray(stage.apply_kernel(x, interpret=True)), want)


def _pallas_operands(fn, *args):
    """The operand avals of every ``pallas_call`` in ``fn``'s jaxpr."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append([v.aval for v in eqn.invars])
                continue
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_a_stage_without_affine_passes_the_bank_and_no_affine():
    """A CNV binary conv (S = 1, no proven affine) reaches the conv kernel
    with its (S, F) int32 bank and no float operand; an 8-bit stage with
    an affine reaches it with the (2, F) float32 affine and no bank."""
    import os

    from repro.core.qir import Graph

    golden = os.path.join(os.path.dirname(__file__), "golden")
    g = Graph.load(os.path.join(golden, "cnv.qir.json"))
    binary = next(s for s in lower_graph(g, in_scale=g.meta["in_scale"]
                                         ).stages
                  if isinstance(s, FusedConvThresholdStage))
    assert binary.affine is None and binary.stage.n_steps == 1
    assert not binary.affine_on_kernel
    gm = binary.geom
    x = jnp.zeros((2, gm.in_h, gm.in_w, gm.in_ch), jnp.int32)
    (avals,) = _pallas_operands(
        lambda v: binary.apply_kernel(v, interpret=True), x)
    assert [(a.shape, a.dtype) for a in avals[2:]] == \
        [((1, gm.out_ch), jnp.int32)]

    rng = np.random.default_rng(0)
    x8, w2d, grid, bias, s_out = boundary_case(rng, 2, 16, 1)
    reach = np.sum(np.abs(w2d.astype(np.int64)), axis=0) * 255
    stage = _stage(w2d, affine_bank(grid, bias, s_out),
                   proven_affine(grid, bias, s_out, reach), 16, 1)
    (avals,) = _pallas_operands(
        lambda v: stage.apply_kernel(v, interpret=True), jnp.asarray(x8))
    assert [(a.shape, a.dtype) for a in avals[2:]] == \
        [((2, 4), jnp.float32)]

