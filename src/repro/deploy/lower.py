"""QIR graph lowering: from an exported interchange graph to a stage schedule.

This is the compiler half of the paper's deployment flow (FINN's
``Streamline -> to-HLS-layers`` stage, hls4ml's ``convert``): walk a
``core.qir.Graph``, greedily fuse every

    Dense|Conv2D -> [BatchNorm] -> [Relu] -> Quant

chain into a single integer dataflow stage (int8 matmul -> int32 accumulator
-> multi-threshold; convs go through im2col so they ride the same fused
kernel), and emit a static ``StageSchedule`` the executor turns into one jit
program. The matcher is op-generic: ``_match_chain`` produces a
``ChainMatch`` and ``stage_for`` dispatches on the head op, so adding a new
matmul-like op means one builder, not a new matcher.

Stage kinds covering every exported graph:

  * ``FusedThresholdStage``     — streamlined integer dense stage; runs on
    the fused Pallas kernel (``kernels.ops.threshold_matmul``) on TPU, or as
    the XLA-fused searchsorted reference inside the same jit program on CPU.
  * ``FusedConvThresholdStage`` — streamlined integer conv stage, with the
    bank built by ``core.streamline`` (BN folded into the kernel, exact
    half-up rounding; FINN-style bipolar sign banks for the binary CNV).
    Two lowerings share the one stage artifact, selected by ``lowering``:

      - ``"direct"`` (default) — the fused direct-conv Pallas kernel
        (``kernels.ops.conv_threshold``): implicit im2col via shifted-window
        tap accumulation inside the kernel, thresholds in-register, no
        materialized patch matrix. The CPU fast path is XLA's native conv
        (``mm_float``) or the same tap accumulation in int32.
      - ``"im2col"``  — fallback behind ``conv_lowering="im2col"`` /
        ``REPRO_CONV_LOWERING=im2col``: materialize the (OH*OW, K*K*C)
        patch matrix and ride the dense ``threshold_matmul``.

    Both produce identical integers (integer accumulation is order-free),
    so the bit-exactness contract is lowering-independent.
  * ``ConvAddThresholdStage``   — a residual join: ``Conv2D [-> 1x1
    projection Conv2D] -> Add -> Relu -> Quant``. A ``FusedConvThreshold
    Stage`` whose main conv's accumulator meets the skip branch's (identity
    codes or a fused 1x1 projection of an earlier stage's output, named by
    ``skip_from``) on one po2 grid before the one threshold bank; the join
    kernel is ``kernels.ops.conv_threshold_add``.
  * ``IntPoolStage``            — MaxPool on integer codes (max commutes
    with the monotone code -> value map, so pooling codes is exact).
  * ``IntSumPoolStage``         — AvgPool as an integer sum of codes; the
    1/(window^2) rides in the output scale, so the next stage (the float
    head) applies it exactly.
  * ``FlattenStage``            — NHWC -> flat reshape between conv and FC.
  * ``FloatHeadStage``          — the final Dense head: int codes -> float
    logits in one affine (the paper drops softmax; argmax suffices).
  * ``RefChainStage``           — fallback: a suffix of nodes from the
    first op with no lowering runs through a float JAX interpreter, so a
    chain graph is executable even where it is not fused. The schedule
    counts it (``StageSchedule.host_segments``, ``meta["host_segments"]``):
    every such stage is a host boundary in every wave.

The schedule is an ordered list: each stage reads its predecessor's output,
and a join stage besides reads the output of stage ``skip_from``, which
the executor keeps live until then. The schedule records value scales at
every boundary so integer and float stages compose exactly.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qir import Graph, Node
from repro.core.streamline import (
    ThresholdDense,
    apply_threshold_dense,
    make_threshold_stage,
    multi_threshold,
    multi_threshold_sorted,
    streamline_conv,
    streamline_dense,
)


# ---------------------------------------------------------------------------
# im2col
# ---------------------------------------------------------------------------

def im2col(x, kernel: int, stride: int, padding: str):
    """Extract conv patches: (N, H, W, C) -> (N, OH, OW, kernel*kernel*C).

    Feature order is (kh, kw, c) row-major — identical to reshaping an HWIO
    kernel to (kh*kw*cin, cout), so ``patches @ w2d`` is the convolution.
    SAME zero-pads like XLA/TF (low side gets floor(pad/2)); zero padding is
    exact on integer codes whenever code 0 means value 0 (signed inputs and
    unsigned half-up codes — the bipolar CNV uses VALID convs only).
    """
    from repro.kernels.conv_threshold import same_pads

    n, h, w, c = x.shape
    if padding == "SAME":
        oh, ow = -(-h // stride), -(-w // stride)
        pad_h, pad_w = same_pads(h, w, oh, ow, stride, kernel)
        x = jnp.pad(x, ((0, 0), pad_h, pad_w, (0, 0)))
    else:
        oh, ow = (h - kernel) // stride + 1, (w - kernel) // stride + 1
    cols = [x[:, i:i + stride * (oh - 1) + 1:stride,
              j:j + stride * (ow - 1) + 1:stride, :]
            for i in range(kernel) for j in range(kernel)]
    return jnp.concatenate(cols, axis=-1)


# ---------------------------------------------------------------------------
# stage kinds
# ---------------------------------------------------------------------------

def _float_mm_safe(w_int, in_bits: int) -> bool:
    """True when the stage's integer matmul can run *exactly* in float32.

    Integer arithmetic in float32 is exact while every partial sum stays
    below 2^24; any accumulation order then yields the same integers, so the
    accumulator can take the BLAS SGEMM path on CPU (int32 matmuls lower to
    scalar loops there) without giving up bit-exactness. The bound is the
    worst case over output channels: sum_k |w_int[k, c]| times the largest
    input code."""
    colsum = np.sum(np.abs(np.asarray(w_int, np.int64)), axis=0)
    worst = int(colsum.max()) if colsum.size else 0
    return worst * ((1 << in_bits) - 1) < (1 << 24)


def _join_int32_safe(acc_reach, thresholds) -> bool:
    """True when a residual join is exact in int32.

    ``acc_reach`` is, per output channel, the largest magnitude of the
    summed accumulator in units of the join's grid: ``colsum|w_main| *
    x_max * mul_main + colsum|w_skip| * u_max * mul_skip`` (an identity
    skip's ``w_skip`` is the identity). The kernel compares the sum, with
    the int8 offsets' corrections (each at most its branch's reach) moved
    into the bank, against the thresholds, or adds them back to the sum
    before the affine activation; every such number stays below 2^31 when
    the reach plus the largest threshold does."""
    t = np.abs(np.asarray(thresholds, np.float64))
    worst = float(np.max(acc_reach)) + (float(t.max()) if t.size else 0.0)
    return worst < 2.0 ** 31


def mxu_form(w_int, in_bits: int, in_signed: bool) -> Tuple[str, int]:
    """The exact operand form of one fused stage's matmul on the Pallas
    kernels (Mosaic has no int32 matmul; ``kernels.multi_threshold``).

    ``("bf16", 0)`` — bf16 operands, f32 accumulation — wherever that is
    exact: every code and weight an integer of magnitude <= 256 (exact in
    bf16) and every partial sum below 2^24 (``_float_mm_safe``). Otherwise
    ``("int8", x_offset)``: int8 x int8 -> int32, with unsigned codes that
    overflow int8 (8-bit activations) shifted down by ``x_offset`` and the
    correction folded into the threshold bank. Raises where neither form
    is exact (codes or weights wider than 8 bits past the bf16 bound).
    """
    w = np.asarray(w_int, np.int64)
    w_lo, w_hi = (int(w.min()), int(w.max())) if w.size else (0, 0)
    if (in_bits <= 8 and max(-w_lo, w_hi) <= 256
            and _float_mm_safe(w, in_bits)):
        return "bf16", 0
    if in_signed:
        x_lo, x_hi = -(1 << (in_bits - 1)), (1 << (in_bits - 1)) - 1
    else:
        x_lo, x_hi = 0, (1 << in_bits) - 1
    offset = 0 if x_hi <= 127 else 1 << (in_bits - 1)
    if x_lo - offset < -128 or x_hi - offset > 127 or w_lo < -128 \
            or w_hi > 127:
        raise ValueError(
            f"no exact MXU operand form: {in_bits}-bit "
            f"{'signed' if in_signed else 'unsigned'} codes, weights in "
            f"[{w_lo}, {w_hi}], past the bf16 bound")
    return "int8", offset


def _apply_act(stage: ThresholdDense, affine, acc):
    """Integer activation on the accumulator, fastest exact form available.

    ``affine`` is the O(1) arithmetic short-cut, a (2, F) ``mul; add``:
    when every scale in the stage is a power of two and the bias sits on the
    accumulator grid (the conv exporter's contract), the half-up quant
    q = clip(floor(acc*mul + add), 0, S) is exact in float32 and therefore
    bit-identical to counting thresholds — without the O(log S) gather loop.
    Otherwise fall back to the sorted-bank searchsorted (or, for single-step
    sign banks, one broadcast compare). The conv kernels end their rows
    with the same ``affine_codes`` (``affine_on_kernel``)."""
    if affine is not None:
        from repro.kernels.multi_threshold import affine_codes

        mul, add = affine
        return affine_codes(acc, mul, add, stage.n_steps)
    return multi_threshold_sorted(acc, stage.thresholds)


@dataclasses.dataclass
class FusedThresholdStage:
    """One streamlined integer dense stage (see core/streamline.py)."""

    name: str
    stage: ThresholdDense
    in_dim: int
    out_dim: int
    in_scale: float
    in_bits: int = 8
    in_signed: bool = False  # input codes signed (the graph input) or not
    mm_float: bool = False   # exact float32 GEMM path (see _float_mm_safe)
    affine: Optional[jnp.ndarray] = None   # exact O(1) act. (_apply_act)

    @property
    def out_scale(self) -> float:
        return self.stage.out_scale

    @functools.cached_property
    def mxu(self) -> Tuple[str, int]:
        """(operand, x_offset) of the kernel path (``mxu_form``)."""
        return mxu_form(self.stage.w_int, self.in_bits, self.in_signed)

    @property
    def macs(self) -> int:
        return self.in_dim * self.out_dim

    def _acc(self, x_int):
        if self.mm_float:
            return jnp.matmul(x_int.astype(jnp.float32),
                              self.stage.w_int.astype(jnp.float32)
                              ).astype(jnp.int32)
        return jnp.matmul(x_int.astype(jnp.int32),
                          self.stage.w_int.astype(jnp.int32))

    def apply_ref(self, x_int):
        return apply_threshold_dense(self.stage, x_int)

    def apply_fast(self, x_int):
        """CPU/XLA path: (exact-float or int32) matmul + exact activation
        — bit-identical to ``apply_ref``, SGEMM-backed when the bound
        allows, O(1) or O(log S) in the step count."""
        return _apply_act(self.stage, self.affine, self._acc(x_int))

    def apply_kernel(self, x_int, *, interpret: bool = False):
        from repro.kernels import ops

        operand, x_offset = self.mxu
        return ops.threshold_matmul(
            x_int, self.stage.w_int, self.stage.thresholds,
            operand=operand, x_offset=x_offset, interpret=interpret)


@dataclasses.dataclass
class ConvGeom:
    """Static conv geometry a fused conv stage needs at trace time."""

    kernel: int
    stride: int
    padding: str
    in_h: int
    in_w: int
    in_ch: int
    out_h: int
    out_w: int
    out_ch: int


CONV_LOWERINGS = ("direct", "im2col")


def default_conv_lowering() -> str:
    """The preferred conv lowering, overridable via REPRO_CONV_LOWERING."""
    kind = os.environ.get("REPRO_CONV_LOWERING", "direct").strip() or "direct"
    if kind not in CONV_LOWERINGS:
        raise ValueError(
            f"REPRO_CONV_LOWERING={kind!r}; expected one of {CONV_LOWERINGS}")
    return kind


@dataclasses.dataclass
class FusedConvThresholdStage:
    """One streamlined integer conv stage (direct or im2col lowering).

    ``stage.w_int`` holds the (kernel*kernel*in_ch, out_ch) im2col weight
    matrix; the integer accumulator and threshold bank are identical to the
    dense case, so both lowerings — the fused direct-conv kernel and the
    im2col + ``threshold_matmul`` fallback — consume one stage artifact and
    produce identical integers.
    """

    name: str
    stage: ThresholdDense
    geom: ConvGeom
    in_scale: float
    in_bits: int = 8
    in_signed: bool = False  # input codes signed (the graph input) or not
    mm_float: bool = False   # exact float32 GEMM path (see _float_mm_safe)
    affine: Optional[jnp.ndarray] = None   # exact O(1) act. (_apply_act)
    lowering: str = "direct"         # "direct" | "im2col"

    @property
    def out_scale(self) -> float:
        return self.stage.out_scale

    @functools.cached_property
    def mxu(self) -> Tuple[str, int]:
        """(operand, x_offset) of the kernel path (``mxu_form``)."""
        return mxu_form(self.stage.w_int, self.in_bits, self.in_signed)

    @property
    def affine_on_kernel(self) -> bool:
        """Whether the kernel path ends each row with the proven O(1)
        ``affine`` (the direct-conv kernel does; the im2col lowering's
        ``threshold_matmul`` counts the bank)."""
        return self.affine is not None and self.lowering == "direct"

    @property
    def in_dim(self) -> int:
        return self.geom.in_h * self.geom.in_w * self.geom.in_ch

    @property
    def out_dim(self) -> int:
        return self.geom.out_h * self.geom.out_w * self.geom.out_ch

    @property
    def macs(self) -> int:
        g = self.geom
        return g.out_h * g.out_w * g.kernel * g.kernel * g.in_ch * g.out_ch

    @property
    def fifo_work(self) -> int:
        """Per-token work driving the FIFO-depth simulation.

        The im2col lowering materializes (OH*OW, K*K*C) patch tiles, so its
        pipeline work scales with the patch traffic (= ``macs``). The fused
        direct kernel streams shifted windows in-register and emits only
        output tiles, so its FIFO pressure scales with the output tile
        count — sizing fused-stage FIFOs from im2col tile counts would
        over-buffer them (paper §3.1.2: depth follows observed occupancy).
        """
        g = self.geom
        if self.lowering == "direct":
            return g.out_h * g.out_w * g.out_ch
        return self.macs

    def _pad_same(self, x):
        """SAME zero padding on integer codes (exact: code 0 is value 0)."""
        from repro.kernels.conv_threshold import same_pads

        g = self.geom
        if g.padding != "SAME":
            return x
        pad_h, pad_w = same_pads(g.in_h, g.in_w, g.out_h, g.out_w,
                                 g.stride, g.kernel)
        return jnp.pad(x, ((0, 0), pad_h, pad_w, (0, 0)))

    def _cols2d(self, x_int):
        g = self.geom
        x = x_int.reshape(-1, g.in_h, g.in_w, g.in_ch)
        cols = im2col(x, g.kernel, g.stride, g.padding)
        return cols.reshape(-1, g.kernel * g.kernel * g.in_ch)

    def _shape_out(self, y2d, n):
        g = self.geom
        return y2d.reshape(n, g.out_h, g.out_w, g.out_ch)

    def apply_ref(self, x_int):
        acc = jnp.matmul(self._cols2d(x_int).astype(jnp.int32),
                         self.stage.w_int.astype(jnp.int32))
        return self._shape_out(multi_threshold(acc, self.stage.thresholds),
                               x_int.shape[0])

    def apply_fast(self, x_int):
        """CPU/XLA path, algorithm selected by ``lowering``.

        * ``direct``  — no patch matrix ever: with the exactness bound
          satisfied the accumulator comes from XLA's native float32
          convolution (integer-valued, so bit-identical to the int32 path
          but Eigen-optimized); otherwise the kernel's shifted-window tap
          accumulation runs in int32.
        * ``im2col``  — materialize the patch matrix and matmul (float32
          SGEMM when the bound allows, int32 otherwise) — the baseline the
          fused kernel is benchmarked against.
        """
        return _apply_act(self.stage, self.affine, self._acc_fast(x_int))

    def _acc_fast(self, x_int):
        """The (N, OH, OW, F) int32 accumulator of the CPU/XLA path."""
        g = self.geom
        if self.lowering == "direct":
            x = x_int.reshape(-1, g.in_h, g.in_w, g.in_ch)
            if self.mm_float:
                w4 = self.stage.w_int.astype(jnp.float32).reshape(
                    g.kernel, g.kernel, g.in_ch, g.out_ch)
                return jax.lax.conv_general_dilated(
                    x.astype(jnp.float32), w4, (g.stride, g.stride),
                    g.padding,
                    dimension_numbers=("NHWC", "HWIO", "NHWC")
                ).astype(jnp.int32)
            from repro.kernels.conv_threshold import direct_conv_acc

            return direct_conv_acc(
                self._pad_same(x), self.stage.w_int, kernel=g.kernel,
                stride=g.stride, out_h=g.out_h, out_w=g.out_w)
        cols = self._cols2d(x_int)
        if self.mm_float:
            acc = jnp.matmul(cols.astype(jnp.float32),
                             self.stage.w_int.astype(jnp.float32)
                             ).astype(jnp.int32)
        else:
            acc = jnp.matmul(cols.astype(jnp.int32),
                             self.stage.w_int.astype(jnp.int32))
        return self._shape_out(acc, x_int.shape[0])

    def apply_kernel(self, x_int, *, interpret: bool = False):
        from repro.kernels import ops

        g = self.geom
        operand, x_offset = self.mxu
        if self.lowering == "direct":
            x = x_int.reshape(-1, g.in_h, g.in_w, g.in_ch)
            return ops.conv_threshold(
                x, self.stage.w_int, self.stage.thresholds,
                kernel=g.kernel, stride=g.stride, padding=g.padding,
                out_h=g.out_h, out_w=g.out_w, operand=operand,
                x_offset=x_offset,
                affine=self.affine if self.affine_on_kernel else None,
                interpret=interpret)
        y = ops.threshold_matmul(
            self._cols2d(x_int), self.stage.w_int, self.stage.thresholds,
            operand=operand, x_offset=x_offset, interpret=interpret)
        return self._shape_out(y, x_int.shape[0])


@dataclasses.dataclass
class ConvAddThresholdStage(FusedConvThresholdStage):
    """One residual join: the main conv (``stage.w_int``, ``geom``) of the
    predecessor's output plus the skip branch on the output of stage
    ``skip_from``, thresholded once.

    Each branch's accumulator is on a per-channel po2 grid (``s_w * s_in``;
    ``s_in`` for identity codes); ``mul`` (2, F) holds each grid's integer
    ratio to the finer one, on which ``stage.thresholds`` is built, so the
    block output is ``#(acc_main * mul[0] + acc_skip * mul[1] >= T)``.
    ``w_skip`` (Cs, F) is a 1x1 projection's integer weights, applied at
    ``skip_stride``; None adds the skip codes as they are (identity).
    """

    skip_from: int = -1
    w_skip: Optional[jnp.ndarray] = None
    skip_stride: int = 1
    skip_bits: int = 8
    mul: Optional[np.ndarray] = None      # (2, F) int32 grid ratios
    skip_float: bool = False  # exact float32 projection (_float_mm_safe)

    @functools.cached_property
    def skip_mxu(self) -> Tuple[str, int]:
        """(operand, offset) of the projection on the kernel path."""
        if self.w_skip is None:
            return "int8", 0
        return mxu_form(self.w_skip, self.skip_bits, False)

    @property
    def affine_on_kernel(self) -> bool:
        """Both lowerings run the join kernel, which takes the affine."""
        return self.affine is not None

    @property
    def macs(self) -> int:
        g = self.geom
        proj = 0 if self.w_skip is None else int(np.size(self.w_skip))
        return super().macs + g.out_h * g.out_w * proj

    def _skip_acc(self, skip):
        """The skip branch's (N, OH, OW, F) int32 accumulator."""
        if self.w_skip is None:
            return skip.astype(jnp.int32)
        g, st = self.geom, self.skip_stride
        u = skip[:, ::st, ::st][:, :g.out_h, :g.out_w]
        dt = jnp.float32 if self.skip_float else jnp.int32
        return jnp.matmul(u.astype(dt), self.w_skip.astype(dt)
                          ).astype(jnp.int32)

    def _total(self, acc, skip):
        mul = jnp.asarray(self.mul)
        return acc * mul[0] + self._skip_acc(skip) * mul[1]

    def apply_ref(self, x_int, skip):
        acc = jnp.matmul(self._cols2d(x_int).astype(jnp.int32),
                         self.stage.w_int.astype(jnp.int32))
        acc = self._shape_out(acc, x_int.shape[0])
        return multi_threshold(self._total(acc, skip), self.stage.thresholds)

    def apply_fast(self, x_int, skip):
        return _apply_act(self.stage, self.affine,
                          self._total(self._acc_fast(x_int), skip))

    def apply_kernel(self, x_int, skip, *, interpret: bool = False):
        """The join kernel. The ``im2col`` lowering materializes the patch
        matrix and runs it through the same kernel as a 1x1 conv."""
        from repro.kernels import ops

        g = self.geom
        operand, x_offset = self.mxu
        skip_operand, skip_offset = self.skip_mxu
        x = x_int.reshape(-1, g.in_h, g.in_w, g.in_ch)
        kernel, stride, padding = g.kernel, g.stride, g.padding
        if self.lowering != "direct":
            x = im2col(x, kernel, stride, padding)
            kernel, stride, padding = 1, 1, "VALID"
        return ops.conv_threshold_add(
            x, self.stage.w_int, skip, self.w_skip, jnp.asarray(self.mul),
            self.stage.thresholds, kernel=kernel, stride=stride,
            padding=padding, out_h=g.out_h, out_w=g.out_w,
            skip_stride=self.skip_stride, operand=operand, x_offset=x_offset,
            skip_operand=skip_operand, skip_offset=skip_offset,
            affine=self.affine, interpret=interpret)


@dataclasses.dataclass
class IntPoolStage:
    """MaxPool executed directly on integer codes.

    Exact because code -> value is monotone (value = code * scale for the
    half-up banks; value = 2*code - 1 for bipolar), so max commutes with the
    decoding either way. Scale passes through unchanged.
    """

    name: str
    window: int
    stride: int
    padding: str
    in_h: int
    in_w: int
    ch: int
    out_h: int
    out_w: int
    in_scale: float
    in_bits: int = 8

    @property
    def out_scale(self) -> float:
        return self.in_scale

    @property
    def in_dim(self) -> int:
        return self.in_h * self.in_w * self.ch

    @property
    def out_dim(self) -> int:
        return self.out_h * self.out_w * self.ch

    @property
    def macs(self) -> int:
        return self.out_h * self.out_w * self.ch * self.window * self.window

    def apply_ref(self, x):
        x = x.reshape(-1, self.in_h, self.in_w, self.ch)
        init = (jnp.iinfo(x.dtype).min
                if jnp.issubdtype(x.dtype, jnp.integer) else -jnp.inf)
        return jax.lax.reduce_window(
            x, init, jax.lax.max, (1, self.window, self.window, 1),
            (1, self.stride, self.stride, 1), self.padding)


@dataclasses.dataclass
class IntSumPoolStage:
    """AvgPool executed as an integer sum of codes over each window.

    The mean's 1/(window^2) moves into the output scale (a power of two
    for 8x8), so the codes stay integers and the next stage (the float
    head) applies the division exactly; the codes widen by
    log2(window^2) bits."""

    name: str
    window: int
    stride: int
    padding: str
    in_h: int
    in_w: int
    ch: int
    out_h: int
    out_w: int
    in_scale: float
    in_bits: int = 8

    @property
    def out_scale(self) -> float:
        return self.in_scale / (self.window * self.window)

    @property
    def out_bits(self) -> int:
        return self.in_bits + int(np.ceil(np.log2(self.window * self.window)))

    @property
    def in_dim(self) -> int:
        return self.in_h * self.in_w * self.ch

    @property
    def out_dim(self) -> int:
        return self.out_h * self.out_w * self.ch

    @property
    def macs(self) -> int:
        return self.out_h * self.out_w * self.ch * self.window * self.window

    def apply_ref(self, x):
        x = x.reshape(-1, self.in_h, self.in_w, self.ch).astype(jnp.int32)
        return jax.lax.reduce_window(
            x, jnp.int32(0), jax.lax.add, (1, self.window, self.window, 1),
            (1, self.stride, self.stride, 1), self.padding)


@dataclasses.dataclass
class FlattenStage:
    """NHWC -> (N, H*W*C) reshape between the conv stack and the FC head."""

    name: str
    in_dim: int
    in_scale: float
    in_bits: int = 8

    @property
    def out_dim(self) -> int:
        return self.in_dim

    @property
    def out_scale(self) -> float:
        return self.in_scale

    @property
    def macs(self) -> int:
        return self.in_dim  # pure data movement

    def apply_ref(self, x):
        return x.reshape(x.shape[0], -1)


def head_limbs(w, in_dim: int, in_bits: int):
    """Split a float head matrix into small integer limbs on a per-column
    fixed-point grid, so that ``codes @ w`` becomes exact integer matmuls.

    Column c gets the power-of-two step ``2^(e - 24)`` that keeps 24
    significant bits of its largest weight (``2^(e-1) <= max|w| < 2^e``), so
    ``W = round(w / step)`` has ``|W| < 2^24`` and reproduces every weight
    within half a unit of the column's last float32 bit. ``W`` is cut into
    balanced base-``2^L`` digits with ``L`` the widest limb for which every
    digit is exact in bf16 (``|d| <= 128``) and every partial sum of
    ``codes @ digits`` stays below 2^24 — so one f32 matmul per limb is
    exact on any backend, the TPU's one-pass bf16 default included.
    Returns ``(limbs (n, K, N) float32, L, step (N,) float64)``, low limb
    first.
    """
    w = np.asarray(w, np.float64)
    x_max = (1 << in_bits) - 1
    bits = 8
    while bits > 1 and in_dim * x_max * (1 << (bits - 1)) >= (1 << 24):
        bits -= 1
    if in_dim * x_max * (1 << (bits - 1)) >= (1 << 24):
        raise ValueError(f"head {in_dim}x{w.shape[1]} over {in_bits}-bit "
                         "codes is past the exact f32 accumulation bound")
    _, e = np.frexp(np.max(np.abs(w), axis=0))
    step = np.ldexp(1.0, e - 24)
    rest = np.round(w / step).astype(np.int64)
    base, limbs = 1 << bits, []
    while np.any(rest) or not limbs:
        d = (rest + base // 2) % base - base // 2
        limbs.append(d)
        rest = (rest - d) // base
    return np.stack(limbs).astype(np.float32), bits, step


@dataclasses.dataclass
class FloatHeadStage:
    """Final affine head: logits = x_int * in_scale @ w + b (float out).

    Computed exactly, so every execution path and backend produces the same
    float32 logits bit for bit: the codes meet ``w``'s integer limbs
    (``head_limbs``) in exact f32 matmuls, and one fixed sequence of
    elementwise float32 ops forms the logits — Horner over the limbs
    (multiplies by powers of two are exact), then ``(acc + b / scale) *
    scale``, an order no backend can fuse into a multiply-add. Within
    float32 rounding of ``x @ w * in_scale + b``; bit-identical to it where
    the weights are integers, as in CNV's sign head.

    Codes wider than 8 bits (a sum pool's) are not exact as the TPU's
    one-pass bf16 matmul operand: they meet the limbs as base-256 digits,
    each in [0, 255], combined by Horner too.
    """

    name: str
    w: jnp.ndarray
    b: jnp.ndarray
    in_dim: int
    out_dim: int
    in_scale: float
    in_bits: int = 8

    def __post_init__(self):
        #: base-256 digits of the codes (unsigned where more than one)
        self.code_digits = -(-self.in_bits // 8)
        limbs, self.limb_bits, step = head_limbs(self.w, self.in_dim,
                                                 min(self.in_bits, 8))
        self.limbs = tuple(jnp.asarray(d) for d in limbs)
        scale = step * self.in_scale
        self.scale = jnp.asarray(scale.astype(np.float32))
        self.b_scaled = jnp.asarray(
            (np.asarray(self.b, np.float64) / scale).astype(np.float32))

    @property
    def macs(self) -> int:
        return self.in_dim * self.out_dim

    def apply_ref(self, x_int):
        base = float(1 << self.limb_bits)
        acc = None
        for j in reversed(range(self.code_digits)):  # top digit first
            x = x_int if self.code_digits == 1 else (x_int >> (8 * j)) & 255
            x = x.astype(jnp.float32)
            h = None
            for d in self.limbs[::-1]:             # top limb first (Horner)
                part = jnp.matmul(x, d, preferred_element_type=jnp.float32)
                h = part if h is None else h * base + part
            acc = h if acc is None else acc * 256.0 + h
        return (acc + self.b_scaled) * self.scale


@dataclasses.dataclass
class RefChainStage:
    """Fallback float interpreter over a run of QIR nodes.

    Consumes the float value of its input (the executor multiplies integer
    codes by ``in_scale`` first) and emits float; exact QIR.run semantics.
    """

    name: str
    nodes: List[Node]
    initializers: Dict[str, np.ndarray]
    in_name: str
    out_name: str
    in_dim: int
    out_dim: int
    in_scale: float
    in_bits: int = 8

    def apply_ref(self, x_float):
        from repro.core.qir import eval_node

        env: Dict[str, jnp.ndarray] = {
            k: jnp.asarray(v) for k, v in self.initializers.items()
        }
        env[self.in_name] = x_float
        for node in self.nodes:
            env[node.outputs[0]] = eval_node(node, [env[i] for i in node.inputs])
        return env[self.out_name]


Stage = Union[FusedThresholdStage, FusedConvThresholdStage,
              ConvAddThresholdStage, IntPoolStage, IntSumPoolStage,
              FlattenStage, FloatHeadStage, RefChainStage]


@dataclasses.dataclass
class StageSchedule:
    """The static compilation artifact: an ordered list of stages plus the
    input quantization contract (integer codes with ``in_scale`` step)."""

    stages: List[Stage]
    in_scale: float
    meta: Dict = dataclasses.field(default_factory=dict)

    @property
    def n_fused(self) -> int:
        return sum(isinstance(s, (FusedThresholdStage,
                                  FusedConvThresholdStage))
                   for s in self.stages)

    @property
    def n_fused_conv(self) -> int:
        return sum(isinstance(s, FusedConvThresholdStage)
                   for s in self.stages)

    @property
    def n_join(self) -> int:
        return sum(isinstance(s, ConvAddThresholdStage) for s in self.stages)

    @property
    def affine_stages(self) -> int:
        """Threshold stages whose kernel ends each row with the O(1)
        affine activation instead of counting the bank
        (``affine_on_kernel``)."""
        return sum(getattr(s, "affine_on_kernel", False)
                   for s in self.stages)

    @property
    def host_segments(self) -> int:
        """Non-compiled segments (``group_segments``): host boundaries
        every wave crosses; 0 when the whole schedule compiles."""
        return sum(not seg.compiled for seg in group_segments(self.stages))

    def layer_dims(self) -> List[int]:
        dims = [self.stages[0].in_dim]
        for s in self.stages:
            dims.append(s.out_dim)
        return dims

    def describe(self) -> str:
        rows = [f"schedule: {len(self.stages)} stages "
                f"({self.n_fused} fused int, {self.n_fused_conv} conv, "
                f"{self.n_join} join, {self.host_segments} host segments, "
                f"in_scale={self.in_scale:g})"]
        for s in self.stages:
            kind = type(s).__name__
            if isinstance(s, FusedConvThresholdStage):
                kind += f"[{s.lowering}]"
            skip = (f"  + skip of stage {s.skip_from}"
                    if isinstance(s, ConvAddThresholdStage) else "")
            rows.append(f"  {s.name:16s} {kind:24s} {s.in_dim:>6d} -> "
                        f"{s.out_dim}{skip}")
        return "\n".join(rows)


# ---------------------------------------------------------------------------
# segments (compiled streaming)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Segment:
    """A contiguous run of stages the executor treats as one unit.

    ``compiled`` segments are runs of fused/integer stages (everything except
    the fallback float interpreter) that the streaming executor compiles into
    a *single* jit program per micro-batch wave — micro-batches advance
    through all of the segment's stages inside ``jax.lax`` control flow, so
    Python is crossed once per segment instead of once per stage per
    micro-batch. A ``RefChainStage`` is a *host boundary*: it interprets
    arbitrary leftover QIR nodes, so it gets its own non-compiled segment and
    the wave returns to the host around it.
    """

    start: int   # first stage index (inclusive)
    stop: int    # last stage index (exclusive)
    compiled: bool

    @property
    def n_stages(self) -> int:
        return self.stop - self.start


def group_segments(stages: Sequence[Stage]) -> List[Segment]:
    """Group a stage schedule into maximal compiled segments split at host
    boundaries (``RefChainStage``). Every stage lands in exactly one segment
    and segment order is schedule order."""
    segments: List[Segment] = []
    run_start = 0
    for i, s in enumerate(stages):
        if isinstance(s, RefChainStage):
            if i > run_start:
                segments.append(Segment(run_start, i, compiled=True))
            segments.append(Segment(i, i + 1, compiled=False))
            run_start = i + 1
    if run_start < len(stages):
        segments.append(Segment(run_start, len(stages), compiled=True))
    return segments


# ---------------------------------------------------------------------------
# megakernel residency planner (the whole-network-resident fused path)
# ---------------------------------------------------------------------------

#: Fusing one stage is what ``threshold_matmul`` already does — the
#: megakernel only pays off once there is an inter-stage boundary to delete.
MEGAKERNEL_MIN_STAGES = 2


@dataclasses.dataclass(frozen=True)
class MegakernelSegment:
    """A planned whole-network-resident kernel covering stages
    ``[start, stop)`` — a run of consecutive ``FusedThresholdStage``s whose
    entire working set (weights + threshold banks + inter-stage FIFO tiles)
    fits the VMEM cap, so the executor dispatches the run as ONE program
    (``kernels.megakernel``) instead of one program per stage. Carries the
    planner's byte accounting as the audit trail (``docs/megakernel.md``).
    """

    start: int          # first fused stage index (inclusive)
    stop: int           # last fused stage index (exclusive)
    block_m: int        # wave row block the tile accounting assumed
    weight_bytes: int   # resident int8 weight matrices, all stages
    bank_bytes: int     # resident int32 threshold banks, all stages
    tile_bytes: int     # in/out row blocks + two revolving FIFO tiles
    budget_bytes: int   # the VMEM cap the plan was admitted under

    @property
    def n_stages(self) -> int:
        return self.stop - self.start

    @property
    def total_bytes(self) -> int:
        return self.weight_bytes + self.bank_bytes + self.tile_bytes


def plan_megakernel(stages: Sequence[Stage], segment: Segment, *,
                    block_m: int = 128,
                    budget_bytes: Optional[int] = None
                    ) -> Optional[MegakernelSegment]:
    """Walk one compiled ``Segment`` and plan its resident megakernel.

    Finds the longest run of consecutive ``FusedThresholdStage``s inside the
    segment (the MLP models are one segment that is entirely such a run,
    plus the float head) and admits it when the residency byte accounting
    (``core.bops.megakernel_residency_bytes``: every weight matrix, every
    threshold bank, the inter-stage FIFO tiles) fits the VMEM cap. Returns
    ``None`` when no run is long enough or the working set exceeds the
    budget — the executor then falls back to the per-stage path, which
    stays the bit-exactness reference.
    """
    from repro.core.bops import (MEGAKERNEL_VMEM_BYTES,
                                 megakernel_residency_bytes)

    budget = MEGAKERNEL_VMEM_BYTES if budget_bytes is None else budget_bytes
    if not segment.compiled:
        return None
    best = None          # longest run wins; earlier run breaks length ties
    i = segment.start
    while i < segment.stop:
        if isinstance(stages[i], FusedThresholdStage):
            j = i
            while j < segment.stop and isinstance(stages[j],
                                                  FusedThresholdStage):
                j += 1
            if best is None or (j - i) > (best[1] - best[0]):
                best = (i, j)
            i = j
        else:
            i += 1
    if best is None or best[1] - best[0] < MEGAKERNEL_MIN_STAGES:
        return None
    run = stages[best[0]:best[1]]
    res = megakernel_residency_bytes(run, block_m=block_m)
    if res["total_bytes"] > budget:
        return None      # does not fit resident: staged path
    return MegakernelSegment(start=best[0], stop=best[1], block_m=block_m,
                             weight_bytes=res["weight_bytes"],
                             bank_bytes=res["bank_bytes"],
                             tile_bytes=res["tile_bytes"],
                             budget_bytes=budget)


# ---------------------------------------------------------------------------
# pattern matcher
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ChainMatch:
    """One fusable Dense|Conv2D -> [BatchNorm] -> [Relu] -> Quant run."""

    kind: str                         # "dense" | "conv"
    head: Node
    params: Dict[str, np.ndarray]     # w, b (+ BN stats when present)
    act: str                          # "halfup" | "bipolar"
    act_bits: int
    weight_bits: int
    s_out: Optional[float]            # export-frozen activation scale
    w_scale: Optional[np.ndarray]     # per-channel scale: weights pre-quantized
    n_consumed: int


def _head_params(graph: Graph, node: Node) -> Optional[Dict[str, np.ndarray]]:
    """Pull (w, b) for a Dense/Conv2D node; None unless w is an initializer."""
    if len(node.inputs) < 2 or node.inputs[1] not in graph.initializers:
        return None
    w = graph.initializers[node.inputs[1]]
    b = (graph.initializers.get(node.inputs[2])
         if len(node.inputs) > 2 else None)
    if b is None:
        b = np.zeros((w.shape[-1],), np.float32)
    return {"w": w, "b": b}


def _is_linear_value(graph: Graph, name: str) -> bool:
    """True iff ``name`` has exactly one consumer and is not a graph output
    — the condition for fusing it away without dropping a reader."""
    if name in graph.outputs:
        return False
    return sum(name in n.inputs for n in graph.nodes) == 1


def _is_passthrough_value(graph: Graph, name: str) -> bool:
    """Weaker check for values that survive as stage outputs (pool/flatten):
    at most one consumer, so the stage pipeline stays a chain."""
    return sum(name in n.inputs for n in graph.nodes) <= 1


def _match_chain(graph: Graph, nodes: List[Node], i: int
                 ) -> Optional[ChainMatch]:
    """Try to match Dense|Conv2D -> [BatchNorm] -> [Relu] -> Quant at i.

    The chain must be linear: each intermediate value feeds exactly the next
    node and nothing else (fusion erases it from the runtime environment).
    A Relu is required for the half-up quant flavor (it is what makes the
    output codes unsigned); bipolar sign quants fuse without one.
    """
    head = nodes[i]
    if head.op not in ("Dense", "Conv2D"):
        return None
    if head.op == "Conv2D" and "in_shape" not in head.attrs:
        return None  # no static geometry: leave for the fallback interpreter
    params = _head_params(graph, head)
    if params is None:
        return None
    j = i + 1
    prev_out = head.outputs[0]
    if not _is_linear_value(graph, prev_out):
        return None
    if j < len(nodes) and nodes[j].op == "BatchNorm" and nodes[j].inputs[0] == prev_out:
        bn = nodes[j]
        try:
            stats = [graph.initializers[n] for n in bn.inputs[1:5]]
        except KeyError:
            return None
        params.update(gamma=stats[0], beta=stats[1], mu=stats[2], sigma2=stats[3])
        prev_out = bn.outputs[0]
        j += 1
        if not _is_linear_value(graph, prev_out):
            return None
    relu = False
    if j < len(nodes) and nodes[j].op == "Relu" and nodes[j].inputs[0] == prev_out:
        relu = True
        prev_out = nodes[j].outputs[0]
        j += 1
        if not _is_linear_value(graph, prev_out):
            return None
    if not (j < len(nodes) and nodes[j].op == "Quant"
            and nodes[j].inputs[0] == prev_out and nodes[j].quant is not None):
        return None
    quant = nodes[j]
    bipolar = bool(quant.attrs.get("bipolar"))
    if bipolar == relu:
        # half-up needs the ReLU; a sign bank after ReLU would be constant
        return None
    act_bits = quant.quant.bits
    weight_bits = head.attrs.get("weight_bits", act_bits)
    w_scale = None
    ws_name = head.attrs.get("w_scale")
    if ws_name is not None and ws_name in graph.initializers and "gamma" not in params:
        # pre-quantized weights; unusable under BN (folding rescales them)
        w_scale = graph.initializers[ws_name]
    s_out = quant.attrs.get("scale")
    return ChainMatch(
        kind="dense" if head.op == "Dense" else "conv",
        head=head, params=params,
        act="bipolar" if bipolar else "halfup",
        act_bits=act_bits, weight_bits=weight_bits,
        s_out=None if s_out is None else float(s_out),
        w_scale=w_scale, n_consumed=j + 1 - i)


def _threshold_for_chain(m: ChainMatch, scale: float,
                         bn_eps: float) -> ThresholdDense:
    """Streamline one matched chain into a ThresholdDense bank."""
    w = np.asarray(m.params["w"], np.float32)
    w2d = w.reshape(-1, w.shape[-1])
    if m.w_scale is not None:
        # weights already carry integer codes times a per-channel scale;
        # divide it back out (exact: the exporter used po2 / unit scales)
        s_w = jnp.reshape(jnp.asarray(m.w_scale, jnp.float32), (-1,))
        w_int = jnp.round(jnp.asarray(w2d) / s_w[None, :])
        return make_threshold_stage(
            w_int, s_w, m.params["b"], in_scale=scale, act_bits=m.act_bits,
            s_out=m.s_out, bipolar=m.act == "bipolar",
            weight_bits=m.weight_bits)
    if m.kind == "conv":
        return streamline_conv(
            m.params, weight_bits=m.weight_bits, act_bits=m.act_bits,
            in_scale=scale, bn_eps=bn_eps, s_out=m.s_out,
            bipolar=m.act == "bipolar")
    if m.act == "bipolar":
        from repro.core.quantizers import IntQuantizer

        wq = IntQuantizer(bits=m.weight_bits, signed=True, narrow=True, axis=0)
        w_int, s_w = wq.quantize_int(jnp.asarray(w2d))
        return make_threshold_stage(
            w_int, jnp.squeeze(s_w, axis=0), m.params["b"], in_scale=scale,
            act_bits=m.act_bits, bipolar=True, weight_bits=m.weight_bits)
    return streamline_dense(
        m.params, weight_bits=m.weight_bits, act_bits=m.act_bits,
        in_scale=scale, bn_eps=bn_eps, s_out=m.s_out)


def _conv_geom(node: Node, w) -> ConvGeom:
    """A Conv2D node's static geometry (``w``: its HWIO weights)."""
    a = node.attrs
    ih, iw, ic = a["in_shape"]
    oh, ow, oc = a["out_shape"]
    return ConvGeom(kernel=int(a.get("kernel", np.shape(w)[0])),
                    stride=int(a.get("stride", 1)),
                    padding=a.get("padding", "SAME"), in_h=int(ih),
                    in_w=int(iw), in_ch=int(ic), out_h=int(oh), out_w=int(ow),
                    out_ch=int(oc))


def _lowering(conv_lowering: Optional[str]) -> str:
    kind = conv_lowering or default_conv_lowering()
    if kind not in CONV_LOWERINGS:
        raise ValueError(f"conv_lowering={kind!r}; "
                         f"expected one of {CONV_LOWERINGS}")
    return kind


def _is_po2(x) -> bool:
    x = np.asarray(x, np.float64)
    return bool(np.all(x > 0) and np.all(np.log2(x) == np.round(np.log2(x))))


def _exact_affine(m: ChainMatch, td: ThresholdDense, scale: float,
                  mm_safe: bool, in_bits: int):
    """(mul; add) for the O(1) activation, or None when not provably exact.

    Requires: half-up flavor with an export-frozen s_out, pre-quantized
    weights whose per-channel scales (and in_scale/s_out) are powers of two,
    bias on the accumulator grid, and the 2^24 accumulator bound — i.e. the
    ``export_qcnn`` contract. Under those conditions every term of
    acc*mul + add is an exact float32 multiple of g/s_out, so floor/clip
    reproduce the threshold counts bit for bit.
    """
    if (m.act != "halfup" or m.s_out is None or m.w_scale is None
            or not mm_safe):
        return None
    s_w = np.asarray(m.w_scale, np.float64).reshape(-1)
    if not _is_po2(np.concatenate([s_w, [scale, td.out_scale]])):
        return None
    colsum = np.sum(np.abs(np.asarray(td.w_int, np.int64)), axis=0)
    return _affine_on_grid(s_w * scale,
                           np.asarray(m.params["b"], np.float64).reshape(-1),
                           td.out_scale, colsum * ((1 << in_bits) - 1))


def _affine_on_grid(g, b, s_out: float, acc_max):
    """The (2, F) float32 ``mul; add`` of the O(1) activation for an
    accumulator on the po2 grid ``g`` (per channel) whose magnitude is at
    most ``acc_max`` grid steps, or None where float32 cannot evaluate it
    exactly. The XLA path and the conv kernels take it as it is."""
    r1 = g / s_out                         # activation grid in code units
    if not (np.all(b / g == np.round(b / g)) and np.all(r1 <= 0.5)):
        return None                        # bias off-grid / 0.5 off-grid
    # every term of acc*mul + add is k*r1; exactness needs max|k| < 2^24
    k_max = acc_max + np.abs(b / g) + 0.5 / r1
    if not np.all(k_max < (1 << 24)):
        return None
    return jnp.asarray(np.stack([g / s_out, b / s_out + 0.5]
                                ).astype(np.float32))


def stage_for(m: ChainMatch, scale: float, in_bits: int = 8,
              bn_eps: float = 1e-3,
              conv_lowering: Optional[str] = None,
              in_signed: bool = False) -> Stage:
    """Build the fused stage for one matched chain — the op dispatch point."""
    td = _threshold_for_chain(m, scale, bn_eps)
    mm_float = _float_mm_safe(td.w_int, in_bits)
    affine = _exact_affine(m, td, scale, mm_float, in_bits)
    if m.kind == "conv":
        return FusedConvThresholdStage(name=m.head.name, stage=td,
                                       geom=_conv_geom(m.head, m.params["w"]),
                                       in_scale=scale, in_bits=in_bits,
                                       in_signed=in_signed,
                                       mm_float=mm_float, affine=affine,
                                       lowering=_lowering(conv_lowering))
    w = m.params["w"]
    return FusedThresholdStage(name=m.head.name, stage=td,
                               in_dim=int(w.shape[0]),
                               out_dim=int(w.shape[1]),
                               in_scale=scale, in_bits=in_bits,
                               in_signed=in_signed,
                               mm_float=mm_float, affine=affine)


@dataclasses.dataclass
class JoinMatch:
    """One residual join ``Conv2D [-> Conv2D(1x1 proj)] -> Add -> Relu ->
    Quant``: ``head`` reads the predecessor's output, the skip branch reads
    ``skip_value`` (through ``proj`` when there is one)."""

    head: Node
    proj: Optional[Node]
    skip_value: str
    quant: Node
    n_consumed: int


def _conv_with_scale(graph: Graph, node: Node) -> bool:
    """A Conv2D with static geometry, initializer weights and the export's
    per-channel weight scale: what a join branch needs to be exact."""
    return (node.op == "Conv2D" and "in_shape" in node.attrs
            and node.attrs.get("w_scale") in graph.initializers
            and _head_params(graph, node) is not None)


def _match_join(graph: Graph, nodes: List[Node], i: int
                ) -> Optional[JoinMatch]:
    """Try to match a residual join at i; every intermediate value must be
    linear, as in ``_match_chain``."""
    head = nodes[i]
    if not _conv_with_scale(graph, head):
        return None
    j, proj = i + 1, None
    if (j < len(nodes) and nodes[j].op == "Conv2D"
            and _conv_with_scale(graph, nodes[j])
            and int(nodes[j].attrs.get("kernel", 0)) == 1):
        proj, j = nodes[j], j + 1
    if j + 2 >= len(nodes):
        return None
    add, relu, quant = nodes[j:j + 3]
    main = head.outputs[0]
    if add.op != "Add" or len(add.inputs) != 2 or main not in add.inputs:
        return None
    other = add.inputs[1] if add.inputs[0] == main else add.inputs[0]
    if proj is not None and other != proj.outputs[0]:
        return None
    if not (relu.op == "Relu" and relu.inputs[0] == add.outputs[0]
            and quant.op == "Quant" and quant.inputs[0] == relu.outputs[0]
            and quant.quant is not None and not quant.attrs.get("bipolar")
            and quant.attrs.get("scale") is not None):
        return None
    inner = [main, add.outputs[0], relu.outputs[0]]
    if proj is not None:
        inner.append(proj.outputs[0])
    if not all(_is_linear_value(graph, v) for v in inner):
        return None
    return JoinMatch(head=head, proj=proj,
                     skip_value=other if proj is None else proj.inputs[0],
                     quant=quant, n_consumed=j + 3 - i)


def _int_weights(graph: Graph, node: Node):
    """(integer weight codes (K, F), po2 scale (F,), bias (F,)) of an
    exported conv: the weights divided back by their per-channel scale."""
    p = _head_params(graph, node)
    s_w = np.asarray(graph.initializers[node.attrs["w_scale"]],
                     np.float64).reshape(-1)
    w = np.asarray(p["w"], np.float64).reshape(-1, s_w.size)
    return (np.round(w / s_w).astype(np.int64), s_w,
            np.asarray(p["b"], np.float64).reshape(-1))


def join_stage_for(jm: JoinMatch, graph: Graph, scale: float, in_bits: int,
                   in_signed: bool, skip_from: int, skip_scale: float,
                   skip_bits: int, conv_lowering: Optional[str] = None
                   ) -> Optional[ConvAddThresholdStage]:
    """Build the join stage for one matched residual join, or None where
    the export's exactness contract does not hold: po2 weight and
    activation scales, biases on the finer branch grid, the sum inside
    int32 (``_join_int32_safe``)."""
    w_main, s_main, b = _int_weights(graph, jm.head)
    g_main = s_main * scale
    w_skip, stride = None, 1
    if jm.proj is None:
        g_skip = np.full_like(g_main, skip_scale)
        skip_reach = np.full(g_main.shape, float((1 << skip_bits) - 1))
    else:
        w_skip, s_skip, b_skip = _int_weights(graph, jm.proj)
        g_skip = s_skip * skip_scale
        b = b + b_skip
        skip_reach = (np.sum(np.abs(w_skip), axis=0)
                      * ((1 << skip_bits) - 1))
        stride = int(jm.proj.attrs.get("stride", 1))
    s_out = float(jm.quant.attrs["scale"])
    if not (_is_po2(g_main) and _is_po2(g_skip) and _is_po2(s_out)):
        return None
    grid = np.minimum(g_main, g_skip)
    if not np.all(b / grid == np.round(b / grid)):
        return None
    mul = np.stack([g_main / grid, g_skip / grid]).astype(np.int64)
    act_bits = jm.quant.quant.bits
    steps = np.arange(1, 2 ** act_bits, dtype=np.float64)
    thr = np.ceil(((steps[None, :] - 0.5) * s_out - b[:, None])
                  / grid[:, None])
    reach = (np.sum(np.abs(w_main), axis=0) * ((1 << in_bits) - 1) * mul[0]
             + skip_reach * mul[1])
    if not _join_int32_safe(reach, thr):
        return None
    td = ThresholdDense(
        w_int=jnp.asarray(w_main, jnp.int8),
        thresholds=jnp.asarray(thr.astype(np.int32)), out_scale=s_out,
        act_bits=act_bits,
        weight_bits=int(jm.head.attrs.get("weight_bits", 8)))
    return ConvAddThresholdStage(
        name=jm.head.name, stage=td,
        geom=_conv_geom(jm.head, graph.initializers[jm.head.inputs[1]]),
        in_scale=scale, in_bits=in_bits, in_signed=in_signed,
        mm_float=_float_mm_safe(w_main, in_bits),
        affine=_affine_on_grid(grid, b, s_out, reach),
        lowering=_lowering(conv_lowering),
        skip_from=skip_from,
        w_skip=None if w_skip is None else jnp.asarray(w_skip, jnp.int8),
        skip_stride=stride, skip_bits=skip_bits,
        mul=mul.astype(np.int32),
        skip_float=w_skip is not None and _float_mm_safe(w_skip, skip_bits))


def lower_graph(graph: Graph, in_scale: float = 1.0 / 127.0,
                bn_eps: float = 1e-3,
                conv_lowering: Optional[str] = None) -> StageSchedule:
    """Compile a QIR graph to a stage schedule.

    ``in_scale`` is the float value of one integer step of the (already
    quantized) network input — the paper's 8-bit input layer contract.
    Conv exporters record their contract in ``graph.meta["in_scale"]``.
    ``conv_lowering`` selects the conv stage algorithm ("direct" fused
    kernel by default, "im2col" fallback); None defers to the
    REPRO_CONV_LOWERING environment override.

    Every stage reads its predecessor's output; a residual join besides
    reads an earlier stage's output (``skip_from``), so a value with two
    consumers does not end the match. From the first op with no lowering,
    the rest of the graph becomes one ``RefChainStage``, a host boundary
    that ``meta["host_segments"]`` counts; where that rest reads a value of
    an earlier stage other than its input, the interpreter could not see
    it, and lowering raises ``NotImplementedError``.
    """
    stages: List[Stage] = []
    nodes = graph.nodes
    scale = in_scale
    in_bits = 8   # MLPerf-Tiny 8-bit input layer contract
    in_signed = True   # signed input codes; every stage emits unsigned ones
    running = graph.inputs[0] if graph.inputs else None
    # value -> (index of the stage that produced it, -1 for the graph
    # input; its scale; its code bits)
    vals: Dict[str, Tuple[int, float, int]] = {}

    def emit(st, out_name: str, bits: int, out_scale: float):
        nonlocal running, scale, in_bits, in_signed
        stages.append(st)
        running, scale, in_bits, in_signed = out_name, out_scale, bits, False
        vals[out_name] = (len(stages) - 1, scale, in_bits)

    if running is not None:
        vals[running] = (-1, scale, in_bits)
    i = 0
    while i < len(nodes):
        node = nodes[i]
        # an op reading an earlier stage's value, not the predecessor's,
        # does not continue the schedule
        follows = not (node.inputs and node.inputs[0] in vals
                       and node.inputs[0] != running)
        m = _match_chain(graph, nodes, i) if follows else None
        if m is not None:
            st = stage_for(m, scale, in_bits, bn_eps,
                           conv_lowering=conv_lowering, in_signed=in_signed)
            i += m.n_consumed
            emit(st, nodes[i - 1].outputs[0], st.stage.act_bits,
                 st.out_scale)
            continue
        jm = _match_join(graph, nodes, i) if follows else None
        if jm is not None and vals.get(jm.skip_value, (-1,))[0] >= 0:
            skip_from, skip_scale, skip_bits = vals[jm.skip_value]
            st = join_stage_for(jm, graph, scale, in_bits, in_signed,
                                skip_from, skip_scale, skip_bits,
                                conv_lowering=conv_lowering)
            if st is not None:
                i += jm.n_consumed
                emit(st, jm.quant.outputs[0], st.stage.act_bits,
                     st.out_scale)
                continue
        if (follows and node.op == "MaxPool" and "in_shape" in node.attrs
                and _is_passthrough_value(graph, node.outputs[0])):
            ih, iw, ch = (int(v) for v in node.attrs["in_shape"])
            win = int(node.attrs.get("window", 2))
            stride = int(node.attrs.get("stride", win))
            if "out_shape" in node.attrs:
                oh, ow = int(node.attrs["out_shape"][0]), int(node.attrs["out_shape"][1])
            elif node.attrs.get("padding", "VALID") == "SAME":
                oh, ow = -(-ih // stride), -(-iw // stride)
            else:
                oh, ow = (ih - win) // stride + 1, (iw - win) // stride + 1
            emit(IntPoolStage(
                name=node.name, window=win, stride=stride,
                padding=node.attrs.get("padding", "VALID"),
                in_h=ih, in_w=iw, ch=ch, out_h=oh, out_w=ow,
                in_scale=scale, in_bits=in_bits),
                node.outputs[0], in_bits, scale)
            i += 1
            continue
        if (follows and node.op == "AvgPool"
                and "out_shape" in node.attrs
                and node.attrs.get("padding", "VALID") == "VALID"
                and _is_passthrough_value(graph, node.outputs[0])):
            ih, iw, ch = (int(v) for v in node.attrs["in_shape"])
            oh, ow = (int(v) for v in node.attrs["out_shape"][:2])
            win = int(node.attrs.get("window", 2))
            st = IntSumPoolStage(
                name=node.name, window=win,
                stride=int(node.attrs.get("stride", win)), padding="VALID",
                in_h=ih, in_w=iw, ch=ch, out_h=oh, out_w=ow,
                in_scale=scale, in_bits=in_bits)
            emit(st, node.outputs[0], st.out_bits, st.out_scale)
            i += 1
            continue
        if (follows and node.op == "Flatten"
                and _is_passthrough_value(graph, node.outputs[0])):
            if "in_shape" in node.attrs:
                in_dim = int(np.prod(node.attrs["in_shape"]))
            else:
                in_dim = stages[-1].out_dim if stages else 1
            emit(FlattenStage(name=node.name, in_dim=in_dim,
                              in_scale=scale, in_bits=in_bits),
                 node.outputs[0], in_bits, scale)
            i += 1
            continue
        if follows and node.op == "Dense" and i == len(nodes) - 1:
            params = _head_params(graph, node)
            if params is not None:
                stages.append(FloatHeadStage(
                    name=node.name,
                    w=jnp.asarray(params["w"], jnp.float32),
                    b=jnp.asarray(params["b"], jnp.float32),
                    in_dim=int(params["w"].shape[0]),
                    out_dim=int(params["w"].shape[1]),
                    in_scale=scale, in_bits=in_bits))
                i += 1
                continue
        # fallback: sweep the rest of the graph into one reference chain
        rest = nodes[i:]
        in_name = rest[0].inputs[0]
        unseen = sorted({v for n in rest for v in n.inputs
                         if v in vals and v != in_name})
        if unseen:
            raise NotImplementedError(
                f"no lowering for {node.op} node {node.name!r}, and the "
                f"fallback interpreter from there on would need {unseen}, "
                "values of earlier stages besides its input")
        out_name = graph.outputs[0] if graph.outputs else rest[-1].outputs[0]
        in_dim = stages[-1].out_dim if stages else _guess_dim(graph, in_name)
        out_dim = _guess_dim(graph, out_name, default=in_dim)
        stages.append(RefChainStage(
            name=f"ref[{rest[0].name}..{rest[-1].name}]",
            nodes=list(rest),
            initializers=dict(graph.initializers),
            in_name=in_name,
            out_name=out_name,
            in_dim=in_dim,
            out_dim=out_dim,
            in_scale=scale, in_bits=in_bits))
        i = len(nodes)
    schedule = StageSchedule(stages=stages, in_scale=in_scale,
                             meta=dict(graph.meta))
    schedule.meta["host_segments"] = schedule.host_segments
    return schedule


def _guess_dim(graph: Graph, name: str, default: int = 1) -> int:
    """Best-effort feature dim for fallback bookkeeping (FIFO sizing only)."""
    for node in graph.nodes:
        if name in node.outputs and node.op in ("Dense",):
            wname = node.inputs[1]
            if wname in graph.initializers:
                return int(graph.initializers[wname].shape[1])
    if name in graph.initializers:
        return int(graph.initializers[name].shape[-1])
    return default
