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
  * ``IntPoolStage``            — MaxPool on integer codes (max commutes
    with the monotone code -> value map, so pooling codes is exact).
  * ``FlattenStage``            — NHWC -> flat reshape between conv and FC.
  * ``FloatHeadStage``          — the final Dense head: int codes -> float
    logits in one affine (the paper drops softmax; argmax suffices).
  * ``RefChainStage``           — fallback: any suffix of nodes the matcher
    does not recognize runs through a float JAX interpreter, so *any*
    exported graph is executable (just not fused).

The schedule records value scales at every boundary so integer and float
stages compose exactly.
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

    ``affine`` is the O(1) arithmetic short-cut (mul, add) per channel:
    when every scale in the stage is a power of two and the bias sits on the
    accumulator grid (the conv exporter's contract), the half-up quant
    q = clip(floor(acc*mul + add), 0, S) is exact in float32 and therefore
    bit-identical to counting thresholds — without the O(log S) gather loop.
    Otherwise fall back to the sorted-bank searchsorted (or, for single-step
    sign banks, one broadcast compare)."""
    if affine is not None:
        mul, add = affine
        q = jnp.floor(acc.astype(jnp.float32) * mul + add)
        return jnp.clip(q, 0, stage.n_steps).astype(jnp.int32)
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
    affine: Optional[tuple] = None   # exact O(1) activation (see _apply_act)
    block_m: Optional[int] = None    # tuned kernel row block (None = default)
    block_n: Optional[int] = None    # tuned kernel col block (None = default)

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
            operand=operand, x_offset=x_offset,
            block_m=self.block_m or 128, block_n=self.block_n or 128,
            interpret=interpret)


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
    affine: Optional[tuple] = None   # exact O(1) activation (see _apply_act)
    lowering: str = "direct"         # "direct" | "im2col"
    block_h: Optional[int] = None    # tuned output-row block (None = planner)

    @property
    def out_scale(self) -> float:
        return self.stage.out_scale

    @functools.cached_property
    def mxu(self) -> Tuple[str, int]:
        """(operand, x_offset) of the kernel path (``mxu_form``)."""
        return mxu_form(self.stage.w_int, self.in_bits, self.in_signed)

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
        g = self.geom
        if self.lowering == "direct":
            x = x_int.reshape(-1, g.in_h, g.in_w, g.in_ch)
            if self.mm_float:
                w4 = self.stage.w_int.astype(jnp.float32).reshape(
                    g.kernel, g.kernel, g.in_ch, g.out_ch)
                acc = jax.lax.conv_general_dilated(
                    x.astype(jnp.float32), w4, (g.stride, g.stride),
                    g.padding,
                    dimension_numbers=("NHWC", "HWIO", "NHWC")
                ).astype(jnp.int32)
            else:
                from repro.kernels.conv_threshold import direct_conv_acc

                acc = direct_conv_acc(
                    self._pad_same(x), self.stage.w_int, kernel=g.kernel,
                    stride=g.stride, out_h=g.out_h, out_w=g.out_w)
            return _apply_act(self.stage, self.affine, acc)
        cols = self._cols2d(x_int)
        if self.mm_float:
            acc = jnp.matmul(cols.astype(jnp.float32),
                             self.stage.w_int.astype(jnp.float32)
                             ).astype(jnp.int32)
        else:
            acc = jnp.matmul(cols.astype(jnp.int32),
                             self.stage.w_int.astype(jnp.int32))
        return self._shape_out(
            _apply_act(self.stage, self.affine, acc), x_int.shape[0])

    def apply_kernel(self, x_int, *, interpret: bool = False):
        from repro.kernels import ops

        g = self.geom
        operand, x_offset = self.mxu
        if self.lowering == "direct":
            x = x_int.reshape(-1, g.in_h, g.in_w, g.in_ch)
            return ops.conv_threshold(
                x, self.stage.w_int, self.stage.thresholds,
                kernel=g.kernel, stride=g.stride, padding=g.padding,
                out_h=g.out_h, out_w=g.out_w, block_h=self.block_h,
                operand=operand, x_offset=x_offset, interpret=interpret)
        y = ops.threshold_matmul(
            self._cols2d(x_int), self.stage.w_int, self.stage.thresholds,
            operand=operand, x_offset=x_offset, interpret=interpret)
        return self._shape_out(y, x_int.shape[0])


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
    """

    name: str
    w: jnp.ndarray
    b: jnp.ndarray
    in_dim: int
    out_dim: int
    in_scale: float
    in_bits: int = 8

    def __post_init__(self):
        limbs, self.limb_bits, step = head_limbs(self.w, self.in_dim,
                                                 self.in_bits)
        self.limbs = tuple(jnp.asarray(d) for d in limbs)
        scale = step * self.in_scale
        self.scale = jnp.asarray(scale.astype(np.float32))
        self.b_scaled = jnp.asarray(
            (np.asarray(self.b, np.float64) / scale).astype(np.float32))

    @property
    def macs(self) -> int:
        return self.in_dim * self.out_dim

    def apply_ref(self, x_int):
        x = x_int.astype(jnp.float32)
        base = float(1 << self.limb_bits)
        acc = None
        for d in self.limbs[::-1]:                 # top limb first (Horner)
            part = jnp.matmul(x, d, preferred_element_type=jnp.float32)
            acc = part if acc is None else acc * base + part
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


Stage = Union[FusedThresholdStage, FusedConvThresholdStage, IntPoolStage,
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

    def layer_dims(self) -> List[int]:
        dims = [self.stages[0].in_dim]
        for s in self.stages:
            dims.append(s.out_dim)
        return dims

    def describe(self) -> str:
        rows = [f"schedule: {len(self.stages)} stages "
                f"({self.n_fused} fused int, {self.n_fused_conv} conv, "
                f"in_scale={self.in_scale:g})"]
        for s in self.stages:
            kind = type(s).__name__
            if isinstance(s, FusedConvThresholdStage):
                kind += f"[{s.lowering}]"
            rows.append(f"  {s.name:16s} {kind:24s} {s.in_dim:>6d} -> {s.out_dim}")
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


def _exact_affine(m: ChainMatch, td: ThresholdDense, scale: float,
                  mm_safe: bool, in_bits: int) -> Optional[tuple]:
    """(mul, add) for the O(1) activation, or None when not provably exact.

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
    grids = np.concatenate([s_w, [scale, td.out_scale]])
    if not np.all(grids > 0):
        return None
    logs = np.log2(grids)
    if not np.all(logs == np.round(logs)):
        return None
    g = s_w * scale                        # accumulator grid per channel
    r1 = g / td.out_scale                  # activation grid in code units
    b = np.asarray(m.params["b"], np.float64).reshape(-1)
    if not (np.all(b / g == np.round(b / g)) and np.all(r1 <= 0.5)):
        return None                        # bias off-grid / 0.5 off-grid
    # every term of acc*mul + add is k*r1; exactness needs max|k| < 2^24
    colsum = np.sum(np.abs(np.asarray(td.w_int, np.int64)), axis=0)
    k_max = (colsum * ((1 << in_bits) - 1) + np.abs(b / g) + 0.5 / r1)
    if not np.all(k_max < (1 << 24)):
        return None
    mul = jnp.asarray((g / td.out_scale).astype(np.float32))
    add = jnp.asarray((b / td.out_scale + 0.5).astype(np.float32))
    return (mul, add)


def stage_for(m: ChainMatch, scale: float, in_bits: int = 8,
              bn_eps: float = 1e-3,
              conv_lowering: Optional[str] = None,
              in_signed: bool = False) -> Stage:
    """Build the fused stage for one matched chain — the op dispatch point."""
    td = _threshold_for_chain(m, scale, bn_eps)
    mm_float = _float_mm_safe(td.w_int, in_bits)
    affine = _exact_affine(m, td, scale, mm_float, in_bits)
    if m.kind == "conv":
        a = m.head.attrs
        ih, iw, ic = a["in_shape"]
        oh, ow, oc = a["out_shape"]
        geom = ConvGeom(kernel=int(a.get("kernel", m.params["w"].shape[0])),
                        stride=int(a.get("stride", 1)),
                        padding=a.get("padding", "SAME"),
                        in_h=int(ih), in_w=int(iw), in_ch=int(ic),
                        out_h=int(oh), out_w=int(ow), out_ch=int(oc))
        kind = conv_lowering or default_conv_lowering()
        if kind not in CONV_LOWERINGS:
            raise ValueError(f"conv_lowering={kind!r}; "
                             f"expected one of {CONV_LOWERINGS}")
        return FusedConvThresholdStage(name=m.head.name, stage=td, geom=geom,
                                       in_scale=scale, in_bits=in_bits,
                                       in_signed=in_signed,
                                       mm_float=mm_float, affine=affine,
                                       lowering=kind)
    w = m.params["w"]
    return FusedThresholdStage(name=m.head.name, stage=td,
                               in_dim=int(w.shape[0]),
                               out_dim=int(w.shape[1]),
                               in_scale=scale, in_bits=in_bits,
                               in_signed=in_signed,
                               mm_float=mm_float, affine=affine)


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
    """
    stages: List[Stage] = []
    nodes = graph.nodes
    scale = in_scale
    in_bits = 8   # MLPerf-Tiny 8-bit input layer contract
    in_signed = True   # signed input codes; every stage emits unsigned ones
    i = 0
    while i < len(nodes):
        m = _match_chain(graph, nodes, i)
        if m is not None:
            st = stage_for(m, scale, in_bits, bn_eps,
                           conv_lowering=conv_lowering, in_signed=in_signed)
            stages.append(st)
            scale = st.out_scale
            in_bits = st.stage.act_bits
            in_signed = False
            i += m.n_consumed
            continue
        node = nodes[i]
        if (node.op == "MaxPool" and "in_shape" in node.attrs
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
            stages.append(IntPoolStage(
                name=node.name, window=win, stride=stride,
                padding=node.attrs.get("padding", "VALID"),
                in_h=ih, in_w=iw, ch=ch, out_h=oh, out_w=ow,
                in_scale=scale, in_bits=in_bits))
            i += 1
            continue
        if (node.op == "Flatten"
                and _is_passthrough_value(graph, node.outputs[0])):
            if "in_shape" in node.attrs:
                in_dim = int(np.prod(node.attrs["in_shape"]))
            else:
                in_dim = stages[-1].out_dim if stages else 1
            stages.append(FlattenStage(name=node.name, in_dim=in_dim,
                                       in_scale=scale, in_bits=in_bits))
            i += 1
            continue
        if node.op == "Dense" and i == len(nodes) - 1:
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
        scale = 1.0  # float domain from here on
        i = len(nodes)
    return StageSchedule(stages=stages, in_scale=in_scale,
                        meta=dict(graph.meta))


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
