"""Multi-threshold activation kernel (FINN streamlining, paper C2) and the
fully fused integer stage: integer matmul -> int32 accum -> multi-threshold.

The multi-threshold op is the deployed form of (dequant -> BN -> ReLU ->
requant): for act_bits output bits it compares the integer accumulator
against S = 2^bits - 1 per-channel thresholds and outputs the count — a pure
integer op, executed on the VPU with the thresholds resident in VMEM.

Threshold layout: (C, S) is transposed to (S, C) before the kernel so the
channel axis is the 128-lane minor axis — each of the S compare steps is a
full-width (bm, C) vector op that loads one bank row straight from the ref,
and S (= 7 for 3-bit KWS, 255 worst-case) is the sequential loop.

**MXU operands.** Mosaic has no int32 x int32 matmul, so the integer
matmul runs in one of two exact forms, chosen per stage by the lowering
(``deploy.lower.mxu_form``) and named by ``operand``:

  * ``"bf16"`` — codes and weights as bf16, f32 accumulation. Exact while
    every code and weight is an integer of magnitude <= 256 (bf16 holds
    those exactly) and every partial sum stays below 2^24 (the
    ``_float_mm_safe`` bound);
  * ``"int8"`` — int8 x int8 -> int32. Unsigned codes that overflow int8
    (8-bit activations, 128..255) are shifted down by ``x_offset`` first;
    ``shift_bank`` folds the matching ``x_offset * colsum(w)`` correction
    into the threshold bank, so the count is unchanged.

Deep banks (S >= ``DOUBLE_BUFFER_STEPS``) stream in slabs instead of
pinning the whole (S, C) bank per program: the slab rides a second
(sequential) grid dimension, so the Pallas pipeline's revolving block
buffers prefetch the next slab's DMA behind the current slab's compare
loop — the same grid-pipeline double-buffering the direct-conv kernel uses
for its input bands — and only two slabs ever occupy VMEM. Banks are
padded to a slab multiple with INT32_MAX rows (never reached by any
accumulator inside the 2^24 exactness bound, the same trick
``ops.threshold_matmul`` uses for padded channels), so the count is exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: Banks at least this deep stream in double-buffered slabs instead of
#: riding whole in VMEM (carried-over ROADMAP item: S >= 256).
DOUBLE_BUFFER_STEPS = 256

#: Slab height for the streamed bank (rows of the (S, C) transposed bank
#: per grid step; multiple of the 8-row f32/int32 sublane tile).
BANK_SLAB = 64

#: The exact integer-matmul operand forms (module docstring).
MXU_OPERANDS = ("bf16", "int8")

_INT32_MAX = jnp.iinfo(jnp.int32).max
_INT32_MIN = jnp.iinfo(jnp.int32).min


def to_operand(x, operand: str, x_offset: int = 0):
    """Integer codes -> the MXU operand type (host side or in-kernel)."""
    if operand == "bf16":
        return x.astype(jnp.float32).astype(jnp.bfloat16)
    if operand == "int8":
        x = x.astype(jnp.int32)
        if x_offset:
            x = x - x_offset
        return x.astype(jnp.int8)
    raise ValueError(f"operand={operand!r}; expected one of {MXU_OPERANDS}")


def mxu_dot(x, w, operand: str, interpret: bool = False):
    """Exact (M, K) x (K, N) integer matmul on ``to_operand`` inputs ->
    int32. bf16 accumulates in f32 (integral and below 2^24, so the int32
    conversion is exact); int8 accumulates in int32. Under the Pallas
    interpreter the int8 operands are widened to int32 first — the same
    integers; XLA's CPU backend mis-emits small int8 x int8 -> int32 dots
    inside loops."""
    acc_t = jnp.float32 if operand == "bf16" else jnp.int32
    if operand == "int8" and interpret:
        x, w = x.astype(jnp.int32), w.astype(jnp.int32)
    acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=acc_t)
    return acc.astype(jnp.int32)


def shift_bank(thresholds, w, x_offset: int):
    """Fold the int8 code offset into a (C, S) bank: the kernel accumulates
    ``(x - x_offset) @ w``, which is ``x @ w - x_offset * colsum(w)``, so
    counting it against ``T - x_offset * colsum(w)`` gives the same codes.
    Saturating: a shifted threshold past the int32 range stays out of reach
    of every accumulator, as the unshifted one was."""
    t = thresholds.astype(jnp.int32)
    if not x_offset:
        return t
    c = (x_offset * jnp.sum(w.astype(jnp.int32), axis=0))[:, None]
    s = t - c
    s = jnp.where((c > 0) & (s > t), _INT32_MIN, s)
    return jnp.where((c < 0) & (s < t), _INT32_MAX, s)


def count_thresholds(acc, thr_ref, n_steps: int, out=None):
    """out + #(acc >= T) over the first ``n_steps`` rows of an (S, C) bank
    ref; each step loads one bank row from the ref."""
    def body(s, out):
        t = thr_ref[pl.ds(s, 1), :]                      # (1, C)
        return out + (acc >= t).astype(jnp.int32)

    init = jnp.zeros_like(acc) if out is None else out
    return jax.lax.fori_loop(0, n_steps, body, init)


def _mt_kernel(acc_ref, thr_ref, o_ref, *, n_steps: int):
    o_ref[...] = count_thresholds(acc_ref[...], thr_ref, n_steps)


def _mt_slab_kernel(acc_ref, thr_ref, o_ref, *, slab: int):
    """One bank slab's compares, accumulated into the revisited out block.

    The slab grid dimension is sequential and the out block's index does
    not depend on it, so the output stays resident across slab steps while
    the pipeline prefetches slab s+1 behind slab s's compare loop.
    """
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] = count_thresholds(acc_ref[...], thr_ref, slab, o_ref[...])


def multi_threshold(acc: jnp.ndarray, thresholds: jnp.ndarray, *,
                    block_m: int = 256, interpret: bool = False) -> jnp.ndarray:
    """acc (M, C) int32, thresholds (C, S) int32 -> (M, C) int32 in [0, S].

    M must divide block_m (ops.multi_threshold pads); C rides whole in VMEM
    (tiny-model channel counts: 12-512). Banks with S < DOUBLE_BUFFER_STEPS
    ride whole too; deeper banks stream in double-buffered BANK_SLAB slabs
    (module docstring)."""
    M, C = acc.shape
    S = thresholds.shape[1]
    assert thresholds.shape[0] == C
    block_m = min(block_m, M)
    assert M % block_m == 0, (M, block_m)
    thr_t = thresholds.T.astype(jnp.int32)   # (S, C): lanes = channels

    if S >= DOUBLE_BUFFER_STEPS:
        pad = (-S) % BANK_SLAB
        if pad:
            # INT32_MAX rows count nothing: no in-bound accumulator reaches
            # them (same padding contract as ops.threshold_matmul channels)
            thr_t = jnp.concatenate(
                [thr_t, jnp.full((pad, C), _INT32_MAX, jnp.int32)], axis=0)
        n_slabs = thr_t.shape[0] // BANK_SLAB
        return pl.pallas_call(
            functools.partial(_mt_slab_kernel, slab=BANK_SLAB),
            grid=(M // block_m, n_slabs),
            in_specs=[
                pl.BlockSpec((block_m, C), lambda i, s: (i, 0)),
                pl.BlockSpec((BANK_SLAB, C), lambda i, s: (s, 0)),
            ],
            out_specs=pl.BlockSpec((block_m, C), lambda i, s: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((M, C), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                # slab dim sequential: the revolving buffers double-buffer
                # the next slab fetch behind the current compare loop
                dimension_semantics=("parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(acc, thr_t)

    return pl.pallas_call(
        functools.partial(_mt_kernel, n_steps=S),
        grid=(M // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, C), lambda i: (i, 0)),
            pl.BlockSpec((S, C), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, C), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(acc, thr_t)


def _tmm_kernel(x_ref, w_ref, thr_ref, o_ref, acc_ref, *, n_k: int,
                n_steps: int, operand: str, interpret: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(x_ref[...], w_ref[...], operand, interpret)

    @pl.when(k == n_k - 1)
    def _threshold():
        o_ref[...] = count_thresholds(acc_ref[...], thr_ref, n_steps)


def threshold_matmul(
    x_op: jnp.ndarray,             # (M, K) codes as the MXU operand type
    w_op: jnp.ndarray,             # (K, N) weights as the MXU operand type
    thresholds: jnp.ndarray,       # (N, S) int32, offset already folded in
    *,
    operand: str = "int8",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """One whole streamlined dense stage in a single kernel: the int32
    accumulator never leaves VMEM between the matmul and the activation.
    Operands come from ``to_operand`` (and the bank from ``shift_bank``);
    ``ops.threshold_matmul`` does both."""
    M, K = x_op.shape
    K2, N = w_op.shape
    S = thresholds.shape[1]
    assert K == K2 and thresholds.shape[0] == N
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    n_k = K // block_k
    thr_t = thresholds.T.astype(jnp.int32)   # (S, N)

    return pl.pallas_call(
        functools.partial(_tmm_kernel, n_k=n_k, n_steps=S, operand=operand,
                          interpret=interpret),
        grid=(M // block_m, N // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((S, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x_op, w_op, thr_t)
