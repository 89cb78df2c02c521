"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode runs a kernel body through XLA, so it cannot see what the
TPU's kernel compiler (Mosaic) refuses: int32 matmuls, dynamic slices of
loaded values, strided value slices. These tests lower and compile each
kernel of the serving path at full Table-1 widths for one chip of a
``v5e:2x2`` topology that is described, not attached, and check that the
compiled program holds the kernel (``tpu_custom_call``). Nothing runs, so
they say nothing about results or speed; the interpret-mode tests and the
chip smoke test (``chip_smoke.py``) cover those.

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library, and the first test of
this file to run in a worker is the one that does.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 topology, with JAX's persistent
    compile cache off: a compile for a described chip is written to the
    cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(sharding, fn, *shapes):
    """Lower and compile ``fn`` for the described chip from arg shapes;
    assert the Pallas kernel made it into the program."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


I32, I8 = jnp.int32, jnp.int8

#: (operand, x_offset) forms ``deploy.lower.mxu_form`` picks between
FORMS = [("bf16", 0), ("int8", 128)]


@pytest.mark.parametrize("operand,x_offset", FORMS)
def test_kws_dense_stage_compiles(one_chip, operand, x_offset):
    """KWS's first stage: 490 -> 256 codes, 3-bit bank, one wave."""
    def fn(x, w, t):
        return ops.threshold_matmul(x, w, t, operand=operand,
                                    x_offset=x_offset)

    _compile(one_chip, fn, ((128, 490), I32), ((490, 256), I8),
             ((256, 7), I32))


def _megakernel(one_chip, dims, steps, forms, rows):
    n = len(dims) - 1

    def fn(x, *wb):
        return ops.mlp_megakernel(
            x, wb[:n], wb[n:], operands=tuple(f[0] for f in forms),
            offsets=tuple(f[1] for f in forms), block_m=128)

    shapes = [((rows, dims[0]), I32)]
    shapes += [((dims[i], dims[i + 1]), I8) for i in range(n)]
    shapes += [((dims[i + 1], steps), I32) for i in range(n)]
    _compile(one_chip, fn, *shapes)


@pytest.mark.parametrize("rows", [16, 512])
def test_kws_megakernel_compiles(one_chip, rows):
    """KWS's fused hidden run, 490 -> 256 x 3, on a small and a big wave."""
    _megakernel(one_chip, [490, 256, 256, 256], 7, [("bf16", 0)] * 3, rows)


@pytest.mark.parametrize("operand,x_offset", FORMS)
def test_ad_megakernel_compiles(one_chip, operand, x_offset):
    """AD's fused hidden run, 128 -> 72 -> 72 -> 8 -> 72 -> 72, 8-bit
    codes: the first stage sees signed input, the rest unsigned codes."""
    forms = [(operand, 0)] + [(operand, x_offset)] * 4
    _megakernel(one_chip, [128, 72, 72, 8, 72, 72], 255, forms, 128)


@pytest.mark.parametrize("steps", [7, 255])
def test_multi_threshold_compiles(one_chip, steps):
    """The standalone activation, whole bank (S=7) and slab path (S=255)."""
    _compile(one_chip, ops.multi_threshold, ((256, 256), I32),
             ((256, steps), I32))


def _conv(one_chip, n, hw, cin, cout, kernel, stride, padding, steps,
          operand, x_offset):
    if padding == "SAME":
        out = -(-hw // stride)
    else:
        out = (hw - kernel) // stride + 1

    def fn(x, w, t):
        return ops.conv_threshold(
            x, w, t, kernel=kernel, stride=stride, padding=padding,
            out_h=out, out_w=out, operand=operand, x_offset=x_offset)

    _compile(one_chip, fn, ((n, hw, hw, cin), I32),
             ((kernel * kernel * cin, cout), I8), ((cout, steps), I32))


#: IC's five convs (kernel, stride, in -> out channels, input width)
IC_CONVS = [(1, 1, 3, 32, 32), (4, 1, 32, 4, 32), (4, 1, 4, 32, 32),
            (4, 4, 32, 32, 32), (4, 1, 32, 4, 8)]


@pytest.mark.parametrize("kernel,stride,cin,cout,hw", IC_CONVS)
def test_ic_convs_compile(one_chip, kernel, stride, cin, cout, hw):
    """Every IC conv at full width, the stride-4 one included; the first
    sees signed input codes, the rest unsigned 8-bit ones."""
    offset = 0 if cin == 3 else 128
    _conv(one_chip, 16, hw, cin, cout, kernel, stride, "SAME", 255,
          "int8", offset)


@pytest.mark.parametrize("hw,cin,cout,operand", [(32, 3, 64, "bf16"),
                                                 (30, 64, 64, "bf16"),
                                                 (12, 128, 128, "int8")])
def test_cnv_valid_convs_compile(one_chip, hw, cin, cout, operand):
    """CNV's 3x3 VALID convs: the 8-bit input layer and binary layers."""
    _conv(one_chip, 16, hw, cin, cout, 3, 1, "VALID", 1, operand, 0)


def test_kernels_keep_the_names_the_trace_reducers_match(one_chip):
    """Each Pallas kernel's HLO instruction, and so its device op in a
    profiler trace, is ``<kernel>.N`` (``name=`` on the ``pallas_call``):
    the benchmark's per-kernel reducers match that stem."""
    import re

    def names(compiled):
        return set(re.findall(
            r"%([\w.-]+) = \S+ custom-call\([^\n]*tpu_custom_call",
            compiled.as_text()))

    conv = _compile(one_chip, lambda x, w, t: ops.conv_threshold(
        x, w, t, kernel=3, stride=1, padding="VALID", out_h=30, out_w=30,
        operand="bf16", x_offset=0),
        ((16, 32, 32, 3), I32), ((27, 64), I8), ((64, 1), I32))
    mega = _compile(one_chip, lambda x, w0, w1, t0, t1: ops.mlp_megakernel(
        x, (w0, w1), (t0, t1), operands=("bf16", "bf16"), offsets=(0, 0),
        block_m=128),
        ((128, 490), I32), ((490, 256), I8), ((256, 256), I8),
        ((256, 7), I32), ((256, 7), I32))
    for compiled, stem in ((conv, "conv_threshold"),
                           (mega, "mlp_megakernel")):
        got = names(compiled)
        assert got and all(re.fullmatch(rf"{stem}\.\d+", n) for n in got), \
            got


#: ResNet-8's three joins at full width: (input width, channels, the skip
#: branch's input channels and stride; 0 channels for the identity)
RESNET8_JOINS = [(32, 16, 0, 1), (16, 32, 16, 2), (8, 64, 32, 2)]


@pytest.mark.parametrize("hw,ch,skip_ch,skip_stride", RESNET8_JOINS)
@pytest.mark.parametrize("operand,x_offset", FORMS)
def test_resnet8_joins_compile(one_chip, hw, ch, skip_ch, skip_stride,
                               operand, x_offset):
    """Each residual join: the 3x3 main conv over 8-bit codes plus the
    identity codes or a 1x1 stride-2 projection of the block input, one
    wave of 256 rows; the kernel keeps the name ``conv_threshold_add``."""
    import re

    proj = skip_ch > 0

    def fn(x, w, skip, *rest):
        w_skip, mul, t = rest if proj else (None, *rest)
        return ops.conv_threshold_add(
            x, w, skip, w_skip, mul, t, kernel=3, stride=1, padding="SAME",
            out_h=hw, out_w=hw, skip_stride=skip_stride, operand=operand,
            x_offset=x_offset, skip_operand=operand, skip_offset=x_offset)

    skip_hw = hw * skip_stride
    shapes = [((256, hw, hw, ch), I32), ((9 * ch, ch), I8),
              ((256, skip_hw, skip_hw, skip_ch if proj else ch), I32)]
    if proj:
        shapes.append(((skip_ch, ch), I8))
    shapes += [((2, ch), I32), ((ch, 255), I32)]
    compiled = _compile(one_chip, fn, *shapes)
    got = set(re.findall(r"%([\w.-]+) = \S+ custom-call\([^\n]*"
                         r"tpu_custom_call", compiled.as_text()))
    assert got and all(re.fullmatch(r"conv_threshold_add\.\d+", n)
                       for n in got), got


F32 = jnp.float32


def _kernel_names(compiled):
    import re

    return set(re.findall(r"%([\w.-]+) = \S+ custom-call\([^\n]*"
                          r"tpu_custom_call", compiled.as_text()))


#: ResNet-8's four plain convs at full width: (input width, in -> out
#: channels, stride): the stem, then each stack's first conv
RESNET8_CONVS = [(32, 3, 16, 1), (32, 16, 16, 1), (32, 16, 32, 2),
                 (16, 32, 64, 2)]


@pytest.mark.parametrize("hw,cin,cout,stride", RESNET8_CONVS)
@pytest.mark.parametrize("operand,x_offset", FORMS)
def test_resnet8_convs_compile_on_the_affine_path(one_chip, hw, cin, cout,
                                                  stride, operand, x_offset):
    """Each plain conv ending its rows in the O(1) affine activation (a
    (2, F) float32 operand in place of the 255-step bank), one wave of 256
    rows; the stem sees signed input codes. The kernel keeps the name
    ``conv_threshold`` that the trace reducers and the roofline match."""
    import re

    offset = 0 if cin == 3 else x_offset
    out = -(-hw // stride)

    def fn(x, w, t, a):
        return ops.conv_threshold(
            x, w, t, kernel=3, stride=stride, padding="SAME", out_h=out,
            out_w=out, operand=operand, x_offset=offset, affine=a)

    got = _kernel_names(_compile(
        one_chip, fn, ((256, hw, hw, cin), I32), ((9 * cin, cout), I8),
        ((cout, 255), I32), ((2, cout), F32)))
    assert got and all(re.fullmatch(r"conv_threshold\.\d+", n)
                       for n in got), got


@pytest.mark.parametrize("hw,ch,skip_ch,skip_stride", RESNET8_JOINS)
@pytest.mark.parametrize("operand,x_offset", FORMS)
def test_resnet8_joins_compile_on_the_affine_path(one_chip, hw, ch, skip_ch,
                                                  skip_stride, operand,
                                                  x_offset):
    """Each residual join ending its rows in the O(1) affine activation,
    with the offsets of both branches added back first in the int8 form;
    the kernel keeps the name ``conv_threshold_add``."""
    import re

    proj = skip_ch > 0

    def fn(x, w, skip, *rest):
        w_skip, mul, t, a = rest if proj else (None, *rest)
        return ops.conv_threshold_add(
            x, w, skip, w_skip, mul, t, kernel=3, stride=1, padding="SAME",
            out_h=hw, out_w=hw, skip_stride=skip_stride, operand=operand,
            x_offset=x_offset, skip_operand=operand, skip_offset=x_offset,
            affine=a)

    skip_hw = hw * skip_stride
    shapes = [((256, hw, hw, ch), I32), ((9 * ch, ch), I8),
              ((256, skip_hw, skip_hw, skip_ch if proj else ch), I32)]
    if proj:
        shapes.append(((skip_ch, ch), I8))
    shapes += [((2, ch), I32), ((ch, 255), I32), ((2, ch), F32)]
    got = _kernel_names(_compile(one_chip, fn, *shapes))
    assert got and all(re.fullmatch(r"conv_threshold_add\.\d+", n)
                       for n in got), got
