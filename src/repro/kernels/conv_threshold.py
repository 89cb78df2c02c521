"""Fused direct-conv + multi-threshold Pallas kernel (no materialized im2col).

The paper's FPGA dataflow convs never materialize an im2col matrix: line
buffers stream shifted input windows straight into the MAC array and the
activation happens before anything leaves the chip. This kernel is that
design on the TPU: for one NHWC input tile it performs *implicit* im2col —
a static K x K tap loop where every tap contributes one shifted-window
(rows, C) x (C, F) matmul into an int32 accumulator held in VMEM/registers —
then applies the per-channel multi-threshold activation in-register and
writes back only the integer output codes. Versus the im2col lowering
(``deploy.lower`` building the (OH*OW, K*K*C) patch matrix and feeding
``threshold_matmul``) this removes the O(K^2*C) memory blow-up per conv
stage entirely: HBM sees the input once, the weights once, and the output
once.

Weight layout is shared with the im2col path: ``w2d`` is the
(kh*kw*cin, cout) matrix of ``core.streamline.ThresholdDense`` with feature
order (kh, kw, c) row-major, so tap (kh, kw) owns the contiguous row block
``[(kh*K + kw)*C, (kh*K + kw + 1)*C)``. One stage artifact serves both
lowerings, which is what makes the bit-exactness tests cheap.

Grid: ``(N, OH_padded // block_h)`` — one program per sample per block of
output rows. The host wrapper (``kernels.ops.conv_threshold``) zero-pads the
input spatially (SAME padding plus bottom rows so the row-block grid
divides; zero padding is exact on integer codes whenever code 0 means value
0 — the export contract) and picks ``block_h`` from the output-tile shape.
Channels ride whole in VMEM like ``multi_threshold`` does — tiny-model
channel counts are 3..512.

Inside a program every output row is a static K x K loop of (OW, C) window
loads taken straight from the input ref at static offsets, each feeding one
exact MXU matmul (``kernels.multi_threshold.mxu_dot``). Strided convs (IC's
stride-4 layer) never slice with a stride in the kernel: the host wrapper
rewrites them as stride-1 convs over s x s pixel blocks
(``space_to_depth``), which Mosaic compiles where strided value slices are
refused.

**Line-buffer DMA:** the input block spec carries only the rows a row block
of the stride-1 conv actually reads — ``block_h - 1 + kernel`` rows, halo
included — not the whole sample. The host wrapper restructures the padded
input into per-block row *bands* (``_row_bands``: band j = input rows
``[j * block_h, j * block_h + band_rows)``, overlapping rows duplicated
once), so the Pallas grid pipeline streams exactly one band
per program and its revolving block buffers double-buffer the fetch — the
next row block's band DMA overlaps the current block's tap matmuls, the TPU
analogue of the paper's line-buffer streaming. Before this the block spec
pinned the whole padded sample per program (index map ignored the row-block
index), so every row block refetched the full input.

**Residual join** (``conv_threshold_add``): a residual block's second conv
meets its skip branch before the activation. The sibling kernel reads,
besides the main input band, the skip operand of the same output rows:
the identity codes, or the (strided) block input of a fused 1x1
projection, which it multiplies in-kernel. The two int32 accumulators
sit on per-channel power-of-two grids; each is scaled by its integer
ratio to the finer grid (``mul``), they are added in int32, and the sum
is counted against the one threshold bank. Exact while the summed
accumulator and the bank stay inside int32 (``deploy.lower``'s
``_join_int32_safe``).

**Activation form.** Each output row ends in one of two exact forms,
chosen by what the caller passes. Given the stage's proven ``affine``
(a (2, F) float32 operand: ``mul``; ``add``, from ``deploy.lower``'s
``_affine_on_grid``), the row's int32 total becomes
``clip(floor(total * mul + add), 0, S)`` (``affine_codes``): a few vector
ops whatever S, and the (S, F) bank is not passed at all. Under the int8
form the kernel's total is shifted by the code offsets; the per-channel
int32 correction is added back before the affine, so the affine sees the
true total its proof bounds. Without an ``affine`` the row counts the
bank (``count_thresholds``): S sequential compare steps, with the offsets
folded into the bank: cheap for a binary or 3-bit stage's one to seven
steps, the bulk of an 8-bit stage's time at 255.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.multi_threshold import (affine_codes, count_thresholds,
                                          mxu_dot, shift_bank, to_operand)


def same_pads(h: int, w: int, out_h: int, out_w: int, stride: int,
              kernel: int):
    """XLA/TF SAME zero-pad widths: ((low_h, high_h), (low_w, high_w)).

    Low side gets floor(pad/2). Single source of truth for every conv path
    (im2col, direct CPU, Pallas host wrapper) — the bit-exactness contract
    between the lowerings depends on identical pad splits.
    """
    ph = max((out_h - 1) * stride + kernel - h, 0)
    pw = max((out_w - 1) * stride + kernel - w, 0)
    return (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2)


def band_rows(block_h: int, stride: int, kernel: int) -> int:
    """Input rows one output-row block reads: body rows plus the halo the
    K x K taps reach past the block boundary. The single source of truth for
    the band layout shared by the kernel, the host wrapper, and the traffic
    model (``core.bops.conv_input_band_bytes``). The kernel itself runs
    stride 1 (``space_to_depth``), so for a strided conv the model's count is
    a slight under-estimate of the ``(block_h - 1 + ceil(k/s)) * s`` rows
    actually fetched."""
    return (block_h - 1) * stride + kernel


def _row_bands(x_pad: jnp.ndarray, block_h: int, kernel: int,
               n_blocks: int) -> jnp.ndarray:
    """Restructure (N, HP, WP, C) into per-row-block bands
    (N, n_blocks, band_rows, WP, C) for a stride-1 conv: band j starts at
    input row ``j * block_h`` and carries exactly the rows that output-row
    block j reads (halo included, duplicated across adjacent bands). This is
    what lets the Pallas block spec fetch only the needed rows per program.
    """
    br = band_rows(block_h, 1, kernel)
    rows = jnp.arange(n_blocks)[:, None] * block_h + jnp.arange(br)[None, :]
    return jnp.take(x_pad, rows, axis=1)           # (N, nb, br, WP, C)


def space_to_depth(x_pad: jnp.ndarray, w2d: jnp.ndarray, *, kernel: int,
                   stride: int, out_h: int, out_w: int):
    """Rewrite a stride-s conv as a stride-1 conv over s x s pixel blocks.

    Output pixel (oh, ow) reads input rows ``oh*s + kh``; with
    ``kh = s*a + r`` that is row ``r`` of block row ``oh + a``. So the input
    (N, HP, WP, C) becomes (N, HP/s, WP/s, s*s*C) with channel order
    (r, q, c), and the (k*k*C, F) weights become a ``ceil(k/s)``-tap kernel
    over those channels, zero where ``s*a + r >= k``. Zero-padding the input
    to whole blocks is exact: the added pixels meet only zero taps. Returns
    ``(x_s2d, w2d_s2d, kernel_s2d)``; the conv is then stride 1.
    """
    n, hp, wp, c = x_pad.shape
    f = w2d.shape[1]
    ka = -(-kernel // stride)
    rows, cols = (out_h - 1 + ka) * stride, (out_w - 1 + ka) * stride
    x = jnp.pad(x_pad[:, :rows, :cols],
                ((0, 0), (0, max(rows - hp, 0)), (0, max(cols - wp, 0)),
                 (0, 0)))
    x = x.reshape(n, rows // stride, stride, cols // stride, stride, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(
        n, rows // stride, cols // stride, stride * stride * c)
    span = ka * stride
    w = jnp.pad(w2d.reshape(kernel, kernel, c, f),
                ((0, span - kernel), (0, span - kernel), (0, 0), (0, 0)))
    w = w.reshape(ka, stride, ka, stride, c, f).transpose(0, 2, 1, 3, 4, 5)
    return x, w.reshape(ka * ka * stride * stride * c, f), ka


def _main_operands(x_pad, w2d, *, kernel: int, stride: int, out_h: int,
                   out_w: int, block_h: int, operand: str):
    """The main input as per-row-block bands of a stride-1 conv (a strided
    conv rewritten by ``space_to_depth``) and its tap-major weights in the
    MXU operand type. Returns ``(x_band (N, nb, br, WP, C), w_taps (T, C,
    F), kernel of the stride-1 conv)``."""
    f = w2d.shape[1]
    x = x_pad.astype(jnp.int32)
    if stride > 1:
        x, w2d, kernel = space_to_depth(x, w2d, kernel=kernel, stride=stride,
                                        out_h=out_h, out_w=out_w)
    c = x.shape[-1]
    x_band = _row_bands(x, block_h, kernel, out_h // block_h)
    w_taps = to_operand(w2d, operand).reshape(kernel * kernel, c, f)
    return x_band, w_taps, kernel


def _act_operands(bank, affine, corr):
    """The activation's kernel operands: the (S, F) ``bank`` (offsets
    already folded in) where ``affine`` is None; else the (2, F) float32
    ``affine`` and, where the int8 offsets shifted the accumulator, the
    (1, F) int32 correction ``corr`` to add back. Each rides whole in
    VMEM."""
    if affine is None:
        arrs = [bank.T]                                 # (S, F)
    else:
        arrs = [affine.astype(jnp.float32)]
        if corr is not None:
            arrs.append(corr.astype(jnp.int32)[None])
    return arrs, [pl.BlockSpec(a.shape, lambda i, j: (0, 0)) for a in arrs]


def _activation(act_refs, n_steps: int, affine: bool):
    """The row activation as a function of the row's int32 total: the
    bank count, or the affine form (module docstring). The affine's rows
    load once per program, outside the row loop."""
    if not affine:
        thr_ref, = act_refs
        return lambda total: count_thresholds(total, thr_ref, n_steps)
    aff_ref, *corr_ref = act_refs
    mul, add = aff_ref[pl.ds(0, 1), :], aff_ref[pl.ds(1, 1), :]
    corr = corr_ref[0][...] if corr_ref else None

    def act(total):
        if corr is not None:
            total = total + corr
        return affine_codes(total, mul, add, n_steps)

    return act


def _conv_thr_kernel(x_ref, w_ref, *refs, kernel: int, block_h: int,
                     out_w: int, n_steps: int, operand: str, x_offset: int,
                     affine: bool, interpret: bool):
    """One (sample, output-row-block) program of a stride-1 conv.

    x_ref:   (1, 1, band_rows, WP, C) int32 — only this block's input rows
             (halo included); the grid pipeline double-buffers the band
             fetch against the previous program's tap matmuls
    w_ref:   (K*K, C, F)    MXU operand type — tap-major weights
    refs:    the activation's operands (``_act_operands``: the (S, F) int32
             bank, or the (2, F) affine [and (1, F) correction]), then
             o_ref (1, block_h, OW, F) int32 output codes

    Each output row is K*K tap matmuls of (OW, C) windows loaded straight
    from the ref at static offsets: no strided or reshaped values.
    """
    *act_refs, o_ref = refs
    activate = _activation(act_refs, n_steps, affine)

    def row(r, carry):
        acc = None
        for kh in range(kernel):                   # static K x K tap loop
            for kw in range(kernel):
                xs = x_ref[0, 0, r + kh, pl.ds(kw, out_w), :]   # (OW, C)
                part = mxu_dot(to_operand(xs, operand, x_offset),
                               w_ref[kh * kernel + kw], operand, interpret)
                acc = part if acc is None else acc + part
        o_ref[0, r] = activate(acc)
        return carry

    jax.lax.fori_loop(0, block_h, row, 0)


def conv_threshold(
    x_pad: jnp.ndarray,            # (N, HP, WP, C) int32, already zero-padded
    w2d: jnp.ndarray,              # (K*K*C, F) integer, (kh, kw, c)-major
    thresholds: jnp.ndarray,       # (F, S) int32, sorted along S
    *,
    kernel: int,
    stride: int,
    out_h: int,                    # unpadded output rows wanted
    out_w: int,
    block_h: int,
    operand: str = "int8",
    x_offset: int = 0,
    affine=None,                   # (2, F) float32 (mul; add), or None
    interpret: bool = False,
) -> jnp.ndarray:
    """One whole streamlined conv stage in a single kernel.

    Requires ``out_h % block_h == 0`` and the input padded tall enough for
    the last row block: ``HP >= (out_h - 1) * stride + kernel`` (the host
    wrapper guarantees both). Strided convs are first rewritten as stride-1
    convs by ``space_to_depth``. The input is restructured into per-row-block
    bands so every grid program fetches only the ``band_rows`` input rows it
    reads (halo included) — the Pallas pipeline then double-buffers the next
    band's fetch behind the current block's tap matmuls, instead of pinning
    the whole padded sample per program. ``operand``/``x_offset`` select the
    exact MXU form (``kernels.multi_threshold``). With ``affine`` the rows
    end in the O(1) activation and ``thresholds`` gives only S (module
    docstring). Returns (N, out_h, out_w, F) int32 codes.
    """
    n, hp, wp, c = x_pad.shape
    f = w2d.shape[1]
    s = thresholds.shape[1]
    assert w2d.shape[0] == kernel * kernel * c, (w2d.shape, kernel, c)
    assert thresholds.shape[0] == f
    assert out_h % block_h == 0, (out_h, block_h)
    assert hp >= (out_h - 1) * stride + kernel, (hp, out_h, stride, kernel)
    assert wp >= (out_w - 1) * stride + kernel, (wp, out_w, stride, kernel)
    if affine is None:
        act, act_specs = _act_operands(
            shift_bank(thresholds, w2d, x_offset), None, None)
    else:
        assert affine.shape == (2, f), affine.shape
        corr = (x_offset * jnp.sum(w2d.astype(jnp.int32), axis=0)
                if x_offset else None)
        act, act_specs = _act_operands(None, affine, corr)
    x_band, w_taps, kernel = _main_operands(
        x_pad, w2d, kernel=kernel, stride=stride, out_h=out_h, out_w=out_w,
        block_h=block_h, operand=operand)
    br, wp, c = x_band.shape[2:]
    n_blocks = out_h // block_h

    return pl.pallas_call(
        functools.partial(
            _conv_thr_kernel, kernel=kernel, block_h=block_h, out_w=out_w,
            n_steps=s, operand=operand, x_offset=x_offset,
            affine=affine is not None, interpret=interpret),
        grid=(n, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, br, wp, c), lambda i, j: (i, j, 0, 0, 0)),
            pl.BlockSpec((kernel * kernel, c, f), lambda i, j: (0, 0, 0)),
        ] + act_specs,
        out_specs=pl.BlockSpec((1, block_h, out_w, f),
                               lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, out_h, out_w, f), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the HLO instruction, and so the device op in a profiler trace,
        # is named ``conv_threshold.N`` after this, whatever wraps the call
        name="conv_threshold",
    )(x_band, w_taps, *act)


def _conv_thr_add_kernel(x_ref, w_ref, s_ref, *refs, kernel: int,
                         block_h: int, out_w: int, n_steps: int,
                         operand: str, x_offset: int, proj: bool,
                         skip_operand: str, skip_offset: int, affine: bool,
                         interpret: bool):
    """One (sample, output-row-block) program of a residual join.

    x_ref, w_ref: as ``_conv_thr_kernel`` (the main 3x3 conv)
    s_ref:   (1, block_h, OW, Cs) int32 — the skip operand of this block's
             output rows: identity codes (Cs = F), or the block input of a
             1x1 projection, already taken at the projection's stride
    refs:    [wp_ref (Cs, F) projection weights,] mul_ref (2, F) int32 —
             each branch's grid ratio to the finer grid —, the activation's
             operands (as ``_conv_thr_kernel``), o_ref (1, block_h, OW, F)
    """
    if proj:
        wp_ref, mul_ref, *act_refs, o_ref = refs
    else:
        mul_ref, *act_refs, o_ref = refs
    m_main = mul_ref[pl.ds(0, 1), :]                            # (1, F)
    m_skip = mul_ref[pl.ds(1, 1), :]
    activate = _activation(act_refs, n_steps, affine)

    def row(r, carry):
        acc = None
        for kh in range(kernel):                   # static K x K tap loop
            for kw in range(kernel):
                xs = x_ref[0, 0, r + kh, pl.ds(kw, out_w), :]   # (OW, C)
                part = mxu_dot(to_operand(xs, operand, x_offset),
                               w_ref[kh * kernel + kw], operand, interpret)
                acc = part if acc is None else acc + part
        skip = s_ref[0, r]                                      # (OW, Cs)
        if proj:
            skip = mxu_dot(to_operand(skip, skip_operand, skip_offset),
                           wp_ref[...], skip_operand, interpret)
        o_ref[0, r] = activate(acc * m_main + skip * m_skip)
        return carry

    jax.lax.fori_loop(0, block_h, row, 0)


def conv_threshold_add(
    x_pad: jnp.ndarray,            # (N, HP, WP, C) int32, already zero-padded
    w2d: jnp.ndarray,              # (K*K*C, F) integer, (kh, kw, c)-major
    skip: jnp.ndarray,             # (N, out_h, out_w, Cs) int32
    w_skip,                        # (Cs, F) integer, or None (identity)
    mul: jnp.ndarray,              # (2, F) int32 grid ratios: main, skip
    thresholds: jnp.ndarray,       # (F, S) int32, sorted along S
    *,
    kernel: int,
    stride: int,
    out_h: int,
    out_w: int,
    block_h: int,
    operand: str = "int8",
    x_offset: int = 0,
    skip_operand: str = "int8",
    skip_offset: int = 0,
    affine=None,                   # (2, F) float32 (mul; add), or None
    interpret: bool = False,
) -> jnp.ndarray:
    """One residual join in a single kernel: the main conv as
    ``conv_threshold`` computes it, plus the skip operand of the same output
    rows (identity codes, or a 1x1 projection of ``skip`` by ``w_skip``),
    each accumulator times its grid ratio ``mul``, summed in int32 and
    counted against ``thresholds``, or with ``affine`` put through the O(1)
    activation. ``skip`` is padded to ``out_h`` rows like the output. The
    int8 offsets of both branches shift the sum by ``mul[0] * x_offset *
    colsum(w2d) + mul[1] * skip_offset * colsum(w_skip)``: folded into the
    bank, or added back before the affine. Returns (N, out_h, out_w, F)
    int32 codes."""
    n, hp, wp, c = x_pad.shape
    f = w2d.shape[1]
    s = thresholds.shape[1]
    proj = w_skip is not None
    cs = skip.shape[-1]
    assert w2d.shape[0] == kernel * kernel * c, (w2d.shape, kernel, c)
    assert thresholds.shape[0] == f and mul.shape == (2, f), mul.shape
    assert out_h % block_h == 0, (out_h, block_h)
    assert skip.shape == (n, out_h, out_w, cs), (skip.shape, out_h, out_w)
    assert cs == (w_skip.shape[0] if proj else f), (cs, f)
    assert hp >= (out_h - 1) * stride + kernel, (hp, out_h, stride, kernel)
    assert wp >= (out_w - 1) * stride + kernel, (wp, out_w, stride, kernel)
    mul = mul.astype(jnp.int32)
    corr = mul[0] * x_offset * jnp.sum(w2d.astype(jnp.int32), axis=0)
    if proj:
        corr = corr + mul[1] * skip_offset * jnp.sum(
            w_skip.astype(jnp.int32), axis=0)
    if affine is None:
        act, act_specs = _act_operands(
            thresholds.astype(jnp.int32) - corr[:, None], None, None)
    else:
        assert affine.shape == (2, f), affine.shape
        act, act_specs = _act_operands(
            None, affine, corr if x_offset or (proj and skip_offset)
            else None)
    x_band, w_taps, kernel = _main_operands(
        x_pad, w2d, kernel=kernel, stride=stride, out_h=out_h, out_w=out_w,
        block_h=block_h, operand=operand)
    br, wp, c = x_band.shape[2:]
    n_blocks = out_h // block_h
    operands = [x_band, w_taps, skip.astype(jnp.int32)]
    in_specs = [
        pl.BlockSpec((1, 1, br, wp, c), lambda i, j: (i, j, 0, 0, 0)),
        pl.BlockSpec((kernel * kernel, c, f), lambda i, j: (0, 0, 0)),
        pl.BlockSpec((1, block_h, out_w, cs), lambda i, j: (i, j, 0, 0)),
    ]
    if proj:
        operands.append(to_operand(w_skip, skip_operand))
        in_specs.append(pl.BlockSpec((cs, f), lambda i, j: (0, 0)))
    operands += [mul] + act
    in_specs += [pl.BlockSpec((2, f), lambda i, j: (0, 0))] + act_specs

    return pl.pallas_call(
        functools.partial(
            _conv_thr_add_kernel, kernel=kernel, block_h=block_h,
            out_w=out_w, n_steps=s, operand=operand, x_offset=x_offset,
            proj=proj, skip_operand=skip_operand, skip_offset=skip_offset,
            affine=affine is not None, interpret=interpret),
        grid=(n, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_h, out_w, f),
                               lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, out_h, out_w, f), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the device op in a profiler trace is ``conv_threshold_add.N``
        name="conv_threshold_add",
    )(*operands)


def direct_conv_acc(x_pad: jnp.ndarray, w2d: jnp.ndarray, *, kernel: int,
                    stride: int, out_h: int, out_w: int,
                    as_float: bool = False) -> jnp.ndarray:
    """The kernel's accumulator as plain jnp — shifted-window tap sums, no
    materialized patch matrix. CPU/XLA fast path and the oracle the Pallas
    kernel is tested against.

    With ``as_float`` the taps accumulate in float32 (exact for integer
    values while partial sums stay below 2^24 — the ``_float_mm_safe``
    bound), which takes the SGEMM path on CPU. Returns (N, out_h, out_w, F)
    int32.
    """
    n, hp, wp, c = x_pad.shape
    rh = (out_h - 1) * stride + 1
    rw = (out_w - 1) * stride + 1
    dt = jnp.float32 if as_float else jnp.int32
    x = x_pad.astype(dt)
    acc = jnp.zeros((n, out_h, out_w, w2d.shape[1]), dt)
    for kh in range(kernel):
        for kw in range(kernel):
            xs = x[:, kh:kh + rh:stride, kw:kw + rw:stride, :]
            tap = (kh * kernel + kw) * c
            acc = acc + xs @ w2d[tap:tap + c, :].astype(dt)
    return acc.astype(jnp.int32)
