"""Whole-network-resident megakernel: an entire MLP segment in ONE kernel.

The paper's FPGA dataflow architectures (and the FINN/hls4ml designs they
build on) win latency because *every* layer is on-fabric simultaneously —
weights resident, activations flowing layer to layer through on-chip FIFOs,
zero per-layer program dispatch. This kernel is the software analogue for
the KWS/AD-class MLP schedules, whose weights and threshold banks total
well under VMEM:

  * every stage's weight matrix and threshold bank is fetched ONCE per wave
    (constant block-index maps over a sequential grid — the Pallas pipeline
    never refetches a block whose index is unchanged) and stays resident
    in VMEM for all row blocks;
  * the inter-stage "FIFOs" are two revolving VMEM scratch tiles: each
    stage's int32 accumulator is thresholded into integer codes and written
    straight into the tile the next stage reads — activations never leave
    the chip between layers;
  * the grid iterates over the micro-batch wave's row blocks, so one
    ``pallas_call`` replaces the whole per-stage program sequence.

The per-stage path (``threshold_matmul`` / ``apply_fast``) stays as the
bit-exactness reference — integer accumulation and threshold counting are
order-free, so both paths produce identical integers (asserted on the
golden fixtures). The residency planner (``deploy.lower.plan_megakernel``)
decides when a segment fits; see ``docs/megakernel.md``.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.multi_threshold import (count_thresholds, mxu_dot,
                                          shift_bank, to_operand)


def _mega_kernel(x_ref, *refs, n_stages: int, n_steps: Sequence[int],
                 out_dims: Sequence[int], operands: Sequence[str],
                 offsets: Sequence[int], interpret: bool):
    """One row block of the wave through ALL stages, entirely on-chip.

    ``refs`` layout (pallas_call order): the n_stages resident weight refs,
    the n_stages resident transposed-bank refs, the output ref, then the two
    revolving inter-stage FIFO tiles (absent when n_stages == 1).
    """
    w_refs = refs[:n_stages]
    t_refs = refs[n_stages:2 * n_stages]
    o_ref = refs[2 * n_stages]
    fifo = refs[2 * n_stages + 1:]

    h = x_ref[...]                        # (bm, K0), stage 0's operand type
    for d in range(n_stages):
        if d:                             # int32 codes from the FIFO tile
            h = to_operand(h, operands[d], offsets[d])
        # int32 accumulator, never leaves VMEM
        acc = mxu_dot(h, w_refs[d][...], operands[d], interpret)
        codes = count_thresholds(acc, t_refs[d], int(n_steps[d]))
        if d == n_stages - 1:
            o_ref[...] = codes
        else:
            buf = fifo[d % 2]                           # inter-stage FIFO tile
            buf[:, :out_dims[d]] = codes
            h = buf[:, :out_dims[d]]


def mlp_megakernel(x_int: jnp.ndarray,
                   weights: Sequence[jnp.ndarray],
                   banks: Sequence[jnp.ndarray], *,
                   operands: Sequence[str],
                   offsets: Sequence[int],
                   block_m: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    """Run a whole FusedThresholdStage chain as one Pallas program.

    ``x_int`` is the flattened wave ``(M, K0)`` of int32 codes; ``weights``
    the per-stage ``(K_d, N_d)`` integer matrices (``K_{d+1} == N_d``);
    ``banks`` the per-stage ``(N_d, S_d)`` int32 sorted threshold banks;
    ``operands``/``offsets`` each stage's exact MXU form
    (``kernels.multi_threshold``). Returns the LAST stage's ``(M, N_last)``
    int32 codes; intermediate activations exist only in the kernel's VMEM
    scratch. M must divide ``block_m`` (``ops.mlp_megakernel`` pads).
    """
    assert len(weights) == len(banks) == len(operands) == len(offsets)
    assert weights
    M, K0 = x_int.shape
    n_stages = len(weights)
    assert M % block_m == 0, (M, block_m)
    dims = []
    k_prev = K0
    for w, b in zip(weights, banks):
        assert w.shape[0] == k_prev, (w.shape, k_prev)
        assert b.shape[0] == w.shape[1], (b.shape, w.shape)
        k_prev = int(w.shape[1])
        dims.append(k_prev)
    # (S, N) banks, lanes = channels, each stage's code offset folded in
    thr_t = [shift_bank(b, w, off).T
             for b, w, off in zip(banks, weights, offsets)]
    w_ops = [to_operand(w, op) for w, op in zip(weights, operands)]

    # constant index maps: weights/banks are fetched once and stay resident
    # across the (sequential) row-block grid — the VMEM residency the
    # planner budgets for
    in_specs = [pl.BlockSpec((block_m, K0), lambda i: (i, 0))]
    for w in w_ops:
        in_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0)))
    for t in thr_t:
        in_specs.append(pl.BlockSpec(t.shape, lambda i: (0, 0)))

    scratch = []
    if n_stages > 1:
        fifo_width = max(dims[:-1])
        scratch = [pltpu.VMEM((block_m, fifo_width), jnp.int32),
                   pltpu.VMEM((block_m, fifo_width), jnp.int32)]

    return pl.pallas_call(
        functools.partial(_mega_kernel, n_stages=n_stages,
                          n_steps=tuple(int(t.shape[0]) for t in thr_t),
                          out_dims=tuple(dims), operands=tuple(operands),
                          offsets=tuple(offsets), interpret=interpret),
        grid=(M // block_m,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, dims[-1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, dims[-1]), jnp.int32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            # sequential grid: consecutive row blocks reuse the resident
            # weight/bank blocks instead of refetching them
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(to_operand(x_int, operands[0], offsets[0]), *w_ops, *thr_t)


def mlp_megakernel_ref(x_int, weights, banks) -> jnp.ndarray:
    """Pure-jnp oracle: the same chain, stage by stage (order-free ints)."""
    from repro.core.streamline import multi_threshold

    h = jnp.asarray(x_int, jnp.int32)
    for w, b in zip(weights, banks):
        acc = jnp.matmul(h, jnp.asarray(w, jnp.int32))
        h = multi_threshold(acc, jnp.asarray(b, jnp.int32))
    return h
