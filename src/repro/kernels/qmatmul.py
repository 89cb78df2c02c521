"""Fused int8 dataflow-stage kernel: matmul -> dequant -> bias -> ReLU -> requant.

This is the TPU form of the paper's merged dataflow stage (DESIGN.md C3):
on the FPGA one pipeline stage computes the quantized matmul, folded-BN
affine, and merged ReLU back-to-back without leaving the fabric; here one
Pallas kernel keeps the int32 accumulator in VMEM scratch across the K loop
and applies the epilogue in-register before a single write to HBM — the
activation tensor never round-trips at float width.

Reuse factor (paper C6): ``n_k = K // block_k`` is the number of times each
output tile's multiplier path is revisited. block_k = K (RF=1) maximizes
parallel use of the MXU at max VMEM footprint; smaller block_k trades
latency for working set, exactly the FPGA RF trade.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary" — sequential accumulate);
M/N parallel. All block dims MXU-aligned (multiples of 128 for f32/int8 lanes;
int8 sublane packing prefers bm % 32 == 0).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _qmatmul_kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, acc_ref, *,
                    n_k: int, relu: bool, out_scale: Optional[float]):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        y = acc_ref[...].astype(jnp.float32) * scale_ref[...]      # (bm,bn)*(1,bn)
        y = y + bias_ref[...]
        if relu:
            y = jnp.maximum(y, 0.0)
        if out_scale is None:
            o_ref[...] = y.astype(o_ref.dtype)
        else:
            q = jnp.round(y * (1.0 / out_scale))
            o_ref[...] = jnp.clip(q, -128, 127).astype(jnp.int8)


def qmatmul(
    x_int: jnp.ndarray,            # (M, K) int8
    w_int: jnp.ndarray,            # (K, N) int8
    scale: jnp.ndarray,            # (N,) f32 per-out-channel dequant scale
    bias: Optional[jnp.ndarray] = None,
    *,
    relu: bool = False,
    out_scale: Optional[float] = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused quantized matmul. Shapes must be divisible by the block sizes
    (ops.qmatmul pads). Returns (M, N) f32, or int8 when out_scale is set."""
    M, K = x_int.shape
    K2, N = w_int.shape
    assert K == K2, (K, K2)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        (M, N, K), (block_m, block_n, block_k))
    n_k = K // block_k
    scale2d = jnp.reshape(scale.astype(jnp.float32), (1, N))
    bias2d = (jnp.reshape(bias.astype(jnp.float32), (1, N)) if bias is not None
              else jnp.zeros((1, N), jnp.float32))
    out_dtype = jnp.int8 if out_scale is not None else jnp.float32

    kernel = functools.partial(_qmatmul_kernel, n_k=n_k, relu=relu,
                               out_scale=out_scale)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, N // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x_int, w_int, scale2d, bias2d)
