"""Jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples, the exact MXU operand form
(``kernels.multi_threshold``), and shape restoration. These are the entry
points models/benchmarks call; tests sweep them against ref.py. Every
wrapper compiles its kernel for the backend it runs on; only a caller that
passes ``interpret=True`` gets the Pallas interpreter (the CPU tests do).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import conv_threshold as _ct
from repro.kernels import flash_attention as _fa
from repro.kernels import megakernel as _mk
from repro.kernels import multi_threshold as _mt
from repro.kernels import qmatmul as _qm
from repro.kernels import ref
from repro.kernels.multi_threshold import shift_bank, to_operand


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(jax.jit, static_argnames=("relu", "out_scale", "block_m",
                                             "block_n", "block_k", "interpret"))
def qmatmul(x_int, w_int, scale, bias=None, *, relu=False,
            out_scale: Optional[float] = None, block_m=128, block_n=128,
            block_k=128, interpret: bool = False):
    """Fused int8 matmul stage; auto-pads to block multiples."""
    M0, K0 = x_int.shape
    N0 = w_int.shape[1]
    x_p, _ = _pad_to(x_int, block_m, 0)
    x_p, _ = _pad_to(x_p, block_k, 1)
    w_p, _ = _pad_to(w_int, block_k, 0)
    w_p, _ = _pad_to(w_p, block_n, 1)
    s_p, _ = _pad_to(jnp.reshape(scale, (-1,)).astype(jnp.float32), block_n, 0)
    b = (jnp.reshape(bias, (-1,)).astype(jnp.float32) if bias is not None
         else jnp.zeros((N0,), jnp.float32))
    b_p, _ = _pad_to(b, block_n, 0)
    y = _qm.qmatmul(x_p, w_p, s_p, b_p, relu=relu, out_scale=out_scale,
                    block_m=min(block_m, x_p.shape[0]),
                    block_n=min(block_n, w_p.shape[1]),
                    block_k=min(block_k, x_p.shape[1]),
                    interpret=interpret)
    return y[:M0, :N0]


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def multi_threshold(acc, thresholds, *, block_m=256, interpret: bool = False):
    """Multi-threshold activation; auto-pads rows."""
    M0 = acc.shape[0]
    bm = min(block_m, max(M0, 8))
    acc_p, _ = _pad_to(acc.astype(jnp.int32), bm, 0)
    y = _mt.multi_threshold(acc_p, thresholds.astype(jnp.int32),
                            block_m=bm, interpret=interpret)
    return y[:M0]


@functools.partial(jax.jit, static_argnames=("operand", "x_offset", "block_m",
                                             "block_n", "block_k",
                                             "interpret"))
def threshold_matmul(x_int, w_int, thresholds, *, operand="int8", x_offset=0,
                     block_m=128, block_n=128, block_k=128,
                     interpret: bool = False):
    """Fused integer dense stage (matmul + multi-threshold).

    ``operand``/``x_offset`` pick the exact MXU form: the default int8 form
    is exact for int8-range codes and weights; ``deploy.lower.mxu_form``
    chooses per stage."""
    M0, K0 = x_int.shape
    N0 = w_int.shape[1]
    # zero-pad the codes BEFORE the offset shift: a padded column then
    # carries -x_offset against an all-zero weight row, adding nothing
    x_p, _ = _pad_to(x_int.astype(jnp.int32), block_m, 0)
    x_p, _ = _pad_to(x_p, block_k, 1)
    w_p, _ = _pad_to(w_int, block_k, 0)
    w_p, _ = _pad_to(w_p, block_n, 1)
    # padded output channels need thresholds too; pad with INT32_MAX so the
    # padded channels output 0 (never reached)
    t_p = shift_bank(thresholds, w_int, x_offset)
    pad_n = (-N0) % block_n
    if pad_n:
        t_p = jnp.concatenate(
            [t_p, jnp.full((pad_n, t_p.shape[1]), jnp.iinfo(jnp.int32).max,
                           jnp.int32)], axis=0)
    y = _mt.threshold_matmul(to_operand(x_p, operand, x_offset),
                             to_operand(w_p, operand), t_p, operand=operand,
                             block_m=min(block_m, x_p.shape[0]),
                             block_n=min(block_n, w_p.shape[1]),
                             block_k=min(block_k, x_p.shape[1]),
                             interpret=interpret)
    return y[:M0, :N0]


@functools.partial(jax.jit, static_argnames=("operands", "offsets",
                                             "block_m", "interpret"))
def mlp_megakernel(x_int, weights, banks, *, operands=None, offsets=None,
                   block_m=128, interpret: bool = False):
    """Whole-MLP-segment megakernel (all stages in one Pallas program).

    ``weights``/``banks`` are the per-stage ``ThresholdDense`` artifacts in
    schedule order (tuples, so jit treats them as a pytree of operands);
    ``operands``/``offsets`` each stage's exact MXU form (default: int8,
    no offset — exact for int8-range codes and weights).
    Auto-pads the wave rows to the row block (a multiple of the 32-row int8
    tile); padded rows are inert (their codes are discarded). The whole
    chain runs on-chip: weights and banks resident in VMEM, inter-stage
    activations in scratch tiles — see ``kernels.megakernel`` and
    ``docs/megakernel.md``.
    """
    n = len(weights)
    M0 = x_int.shape[0]
    bm = min(block_m, -(-M0 // 32) * 32)
    x_p, _ = _pad_to(x_int.astype(jnp.int32), bm, 0)
    y = _mk.mlp_megakernel(x_p, tuple(weights), tuple(banks),
                           operands=operands or ("int8",) * n,
                           offsets=offsets or (0,) * n,
                           block_m=bm, interpret=interpret)
    return y[:M0]


def plan_conv_blocks(out_h: int, out_w: int, out_ch: int,
                     target_rows: int = 256,
                     acc_budget_bytes: int = 1 << 21) -> int:
    """Pick the output-row block for the fused direct-conv kernel.

    Sized from the *output tile* shape: enough rows that each program's
    flattened matmul M dimension (``block_h * out_w``) approaches
    ``target_rows`` (keeps the MXU busy), capped so the int32 accumulator
    block (``block_h * out_w * out_ch * 4`` bytes) stays inside a VMEM
    budget. Always at least 1 row; never more than ``out_h``.
    """
    block_h = max(1, min(out_h, target_rows // max(out_w, 1)))
    while (block_h > 1
           and block_h * out_w * max(out_ch, 1) * 4 > acc_budget_bytes):
        block_h -= 1
    return block_h


def _conv_pads(h, w, out_h, out_w, stride, kernel, padding, block_h,
               out_ch):
    """The host wrapper's zero padding of a direct conv's input: SAME pads,
    then extra bottom rows so the row-block grid's last block stays in
    bounds. Returns ``(pads, block_h, out_h padded to the block)``."""
    if padding == "SAME":
        pad_h, pad_w = _ct.same_pads(h, w, out_h, out_w, stride, kernel)
        pads = ((0, 0), pad_h, pad_w, (0, 0))
    else:
        pads = ((0, 0), (0, 0), (0, 0), (0, 0))
    bh = plan_conv_blocks(out_h, out_w, out_ch) \
        if block_h is None else min(block_h, out_h)
    oh_pad = -(-out_h // bh) * bh
    extra = ((oh_pad - 1) * stride + kernel) - (h + pads[1][0] + pads[1][1])
    if extra > 0:
        pads = (pads[0], (pads[1][0], pads[1][1] + extra), pads[2], pads[3])
    return pads, bh, oh_pad


@functools.partial(jax.jit, static_argnames=("kernel", "stride", "padding",
                                             "out_h", "out_w", "block_h",
                                             "operand", "x_offset",
                                             "interpret"))
def conv_threshold(x_int, w2d, thresholds, *, kernel: int, stride: int,
                   padding: str, out_h: int, out_w: int,
                   block_h: Optional[int] = None, operand: str = "int8",
                   x_offset: int = 0, affine=None, interpret: bool = False):
    """Fused direct-conv integer stage: NHWC codes -> threshold codes.

    Implicit im2col inside the Pallas kernel (shifted-window tap
    accumulation; see ``kernels.conv_threshold``) — the (OH*OW, K*K*C) patch
    matrix is never materialized. Handles SAME/VALID zero padding on the
    host (exact on integer codes whenever code 0 means value 0, the export
    contract), pads output rows so the row-block grid divides, and restores
    the unpadded shape. ``w2d`` is the (kh*kw*cin, cout) im2col weight
    matrix, ``thresholds`` the (cout, S) bank — the same stage artifact the
    im2col lowering feeds ``threshold_matmul``; ``operand``/``x_offset``
    its exact MXU form, as there. ``affine``, the stage's proven (2, F)
    float32 (mul; add) or None, picks the row activation: the O(1) affine
    form, or the count against ``thresholds``.
    """
    n, h, w, c = x_int.shape
    pads, bh, oh_pad = _conv_pads(h, w, out_h, out_w, stride, kernel,
                                  padding, block_h, w2d.shape[1])
    x_p = jnp.pad(x_int.astype(jnp.int32), pads)
    y = _ct.conv_threshold(x_p, w2d, thresholds, kernel=kernel,
                           stride=stride, out_h=oh_pad, out_w=out_w,
                           block_h=bh, operand=operand, x_offset=x_offset,
                           affine=affine, interpret=interpret)
    return y[:, :out_h]


@functools.partial(jax.jit, static_argnames=("kernel", "stride", "padding",
                                             "out_h", "out_w", "skip_stride",
                                             "block_h", "operand", "x_offset",
                                             "skip_operand", "skip_offset",
                                             "interpret"))
def conv_threshold_add(x_int, w2d, skip, w_skip, mul, thresholds, *,
                       kernel: int, stride: int, padding: str, out_h: int,
                       out_w: int, skip_stride: int = 1,
                       block_h: Optional[int] = None, operand: str = "int8",
                       x_offset: int = 0, skip_operand: str = "int8",
                       skip_offset: int = 0, affine=None,
                       interpret: bool = False):
    """Fused residual join: the main direct conv of ``x_int`` plus the skip
    branch, thresholded once (``kernels.conv_threshold.conv_threshold_add``).

    ``skip`` is the identity codes (N, out_h, out_w, F) with ``w_skip``
    None, or the block input of a 1x1 projection ``w_skip`` (Cs, F) at
    ``skip_stride`` (a 1x1 SAME conv reads pixels ``(s*i, s*j)`` and pads
    nothing, so the wrapper takes them with one strided slice). ``mul`` is
    the (2, F) grid ratios of the two accumulators; ``thresholds`` the
    (F, S) bank on the finer grid. Padding, the row-block plan and the
    operand forms and ``affine`` are as ``conv_threshold``'s; the skip
    operand is zero-padded to the padded output rows, whose codes are
    discarded.
    """
    n, h, w, c = x_int.shape
    pads, bh, oh_pad = _conv_pads(h, w, out_h, out_w, stride, kernel,
                                  padding, block_h, w2d.shape[1])
    x_p = jnp.pad(x_int.astype(jnp.int32), pads)
    skip = skip.astype(jnp.int32)
    if w_skip is not None and skip_stride > 1:
        skip = skip[:, ::skip_stride, ::skip_stride]
    skip = jnp.pad(skip[:, :out_h, :out_w],
                   ((0, 0), (0, oh_pad - out_h), (0, 0), (0, 0)))
    y = _ct.conv_threshold_add(
        x_p, w2d, skip, w_skip, mul, thresholds, kernel=kernel,
        stride=stride, out_h=oh_pad, out_w=out_w, block_h=bh,
        operand=operand, x_offset=x_offset, skip_operand=skip_operand,
        skip_offset=skip_offset, affine=affine, interpret=interpret)
    return y[:, :out_h]


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    block_q=128, block_k=128, interpret: bool = False):
    """Flash attention over (B, H, S, D) layout; pads S to block multiples.
    Padded KV rows are masked exactly inside the kernel via ``kv_len``."""
    B, H, Sq0, D = q.shape
    Sk0 = k.shape[2]
    bq = min(block_q, max(Sq0, 8))
    bk = min(block_k, max(Sk0, 8))
    q_p, _ = _pad_to(q, bq, 2)
    k_p, _ = _pad_to(k, bk, 2)
    v_p, _ = _pad_to(v, bk, 2)
    out = _fa.flash_attention(q_p, k_p, v_p, causal=causal, window=window,
                              q_offset=q_offset, kv_len=Sk0,
                              block_q=bq, block_k=bk, interpret=interpret)
    return out[:, :, :Sq0]


# re-export oracles for convenience
mlp_megakernel_ref = _mk.mlp_megakernel_ref
qmatmul_ref = ref.qmatmul_ref
multi_threshold_ref = ref.multi_threshold_ref
threshold_matmul_ref = ref.threshold_matmul_ref
flash_attention_ref = ref.flash_attention_ref
