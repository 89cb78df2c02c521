"""Hardware-cost metrics: BOPs, weight memory, inference cost C, FLOPs.

Implements the paper's Eq. 1 / Eq. 2 verbatim:

  BOPs ~= m*n*k^2 * (b_a*b_w + b_a + b_w + log2(n*k^2))          (Eq. 1)
  C     = 0.5 * (BOPs/BOPs_ref + WM/WM_ref)                      (Eq. 2)

plus FLOPs counting for float models (the Fig. 2 x-axis) and the
6*N*D model-FLOPs rule used by the LM-scale roofline (§Roofline).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Iterable, List, Optional


def conv_bops(m: int, n: int, k: int, b_a: int, b_w: int, out_hw: int = 1) -> float:
    """Eq. 1 for one conv layer, times the number of output positions.

    m: out channels, n: in channels, k: kernel size, out_hw: H_out*W_out.
    The paper's Eq. 1 counts MACs per output position; multiply by positions
    for total BOPs of the layer.
    """
    per_pos = m * n * k * k * (b_a * b_w + b_a + b_w + math.log2(max(n * k * k, 2)))
    return per_pos * out_hw


def dense_bops(m: int, n: int, b_a: int, b_w: int) -> float:
    """Eq. 1 with k=1 (fully connected)."""
    return conv_bops(m, n, 1, b_a, b_w, out_hw=1)


def weight_memory_bits(n_weights: int, b_w: int) -> int:
    """WM: total bits to store the weights."""
    return n_weights * b_w


def inference_cost(bops: float, wm: float, bops_ref: float, wm_ref: float) -> float:
    """Eq. 2 relative inference cost."""
    return 0.5 * (bops / bops_ref + wm / wm_ref)


@dataclass
class LayerCost:
    name: str
    bops: float
    wm_bits: int
    flops: float
    n_params: int
    traffic_bytes: float = 0.0   # per-query memory traffic (schedule costing)


def dense_cost(name, in_dim, out_dim, b_a=8, b_w=8, bias=True) -> LayerCost:
    n_w = in_dim * out_dim + (out_dim if bias else 0)
    return LayerCost(
        name=name,
        bops=dense_bops(out_dim, in_dim, b_a, b_w),
        wm_bits=weight_memory_bits(n_w, b_w),
        flops=2.0 * in_dim * out_dim,
        n_params=n_w,
    )


def conv_cost(name, in_ch, out_ch, k, out_h, out_w, b_a=8, b_w=8, bias=True) -> LayerCost:
    n_w = k * k * in_ch * out_ch + (out_ch if bias else 0)
    return LayerCost(
        name=name,
        bops=conv_bops(out_ch, in_ch, k, b_a, b_w, out_hw=out_h * out_w),
        wm_bits=weight_memory_bits(n_w, b_w),
        flops=2.0 * k * k * in_ch * out_ch * out_h * out_w,
        n_params=n_w,
    )


@dataclass
class ModelCost:
    layers: List[LayerCost]

    @property
    def bops(self) -> float:
        return sum(l.bops for l in self.layers)

    @property
    def wm_bits(self) -> int:
        return sum(l.wm_bits for l in self.layers)

    @property
    def flops(self) -> float:
        return sum(l.flops for l in self.layers)

    @property
    def n_params(self) -> int:
        return sum(l.n_params for l in self.layers)

    @property
    def traffic_bytes(self) -> float:
        return sum(l.traffic_bytes for l in self.layers)

    def cost_vs(self, ref: "ModelCost") -> float:
        return inference_cost(self.bops, self.wm_bits, ref.bops, ref.wm_bits)

    def table(self) -> str:
        rows = [f"{'layer':24s} {'params':>10s} {'BOPs':>14s} {'WM[bits]':>12s} {'FLOPs':>14s}"]
        for l in self.layers:
            rows.append(
                f"{l.name:24s} {l.n_params:>10d} {l.bops:>14.3e} {l.wm_bits:>12d} {l.flops:>14.3e}"
            )
        rows.append(
            f"{'TOTAL':24s} {self.n_params:>10d} {self.bops:>14.3e} {self.wm_bits:>12d} {self.flops:>14.3e}"
        )
        return "\n".join(rows)


# ---------------------------------------------------------------------------
# compiled-schedule costing (deploy.lower stage lists)
# ---------------------------------------------------------------------------

def conv_input_band_bytes(geom, block_h: int) -> float:
    """Input bytes one query streams through the fused direct-conv kernel at
    a given output-row block size.

    The kernel fetches per-row-block *bands* of
    ``(block_h - 1) * stride + kernel`` padded input rows (halo rows
    duplicated across adjacent bands — the line-buffer overlap), so smaller
    blocks re-fetch more halo rows while bigger blocks need a bigger VMEM
    accumulator. This is what ``stage_traffic_bytes`` charges a direct
    stage at the kernel planner's row block.
    """
    from repro.kernels.conv_threshold import band_rows, same_pads

    bh = max(1, min(int(block_h), geom.out_h))
    n_blocks = -(-geom.out_h // bh)
    if geom.padding == "SAME":
        (_, _), (pw_lo, pw_hi) = same_pads(geom.in_h, geom.in_w, geom.out_h,
                                           geom.out_w, geom.stride,
                                           geom.kernel)
        wp = geom.in_w + pw_lo + pw_hi
    else:
        wp = geom.in_w
    return 4.0 * n_blocks * band_rows(bh, geom.stride, geom.kernel) \
        * wp * geom.in_ch


def stage_traffic_bytes(stage) -> float:
    """Memory-traffic model of one lowered deploy stage, for a single
    batch-1 query (the MLPerf SingleStream unit; batched execution
    amortizes the parameter term, which this model deliberately does not).

    The stage reads its input codes and writes its output codes (int32,
    4 bytes) and reads its parameters (int8 weight codes, int32
    thresholds). Conv stages are lowering-aware — the point of the
    fused direct-conv kernel: an ``im2col``-lowered stage additionally
    writes *and* re-reads the materialized (OH*OW, K*K*C) patch matrix,
    the O(K^2*C) blow-up the ``direct`` kernel keeps in-register. This is
    the byte term the kernel benchmark and the scenario energy proxy chart
    next to Eq. 1's BOPs.
    """
    io = 4.0 * (int(getattr(stage, "in_dim", 0))
                + int(getattr(stage, "out_dim", 0)))
    bank = getattr(stage, "stage", None)        # ThresholdDense, if fused
    params = 0.0
    if bank is not None:
        params = (float(math.prod(bank.w_int.shape))          # int8 codes
                  + 4.0 * float(math.prod(bank.thresholds.shape)))
    w = getattr(stage, "w", None)               # FloatHeadStage
    if w is not None:
        params = 4.0 * float(math.prod(w.shape))
    geom = getattr(stage, "geom", None)
    if geom is not None and getattr(stage, "lowering", "direct") == "im2col":
        patch = (geom.out_h * geom.out_w
                 * geom.kernel * geom.kernel * geom.in_ch)
        io += 2.0 * 4.0 * patch                 # write + read the im2col mat
    elif geom is not None:
        # direct lowering streams per-row-block bands (halo rows
        # duplicated per block) at the kernel planner's row block, so the
        # banded fetch replaces the flat input term
        from repro.kernels.ops import plan_conv_blocks

        bh = plan_conv_blocks(geom.out_h, geom.out_w, geom.out_ch)
        io += conv_input_band_bytes(geom, bh) \
            - 4.0 * int(getattr(stage, "in_dim", 0))
    return io + params


def stage_cost(stage) -> LayerCost:
    """Eq. 1/2 cost of one lowered deploy stage, by duck type.

    Works on any ``deploy.lower`` stage: conv stages carry a ``geom``
    (kernel/out-tile geometry -> conv_bops), matmul-like stages carry
    in_dim/out_dim, and data-movement stages (pool/flatten) cost zero BOPs.
    ``in_bits``/``stage.weight_bits`` feed Eq. 1's b_a/b_w, so the energy
    proxy of a compiled conv schedule is precision-aware end to end, and
    ``traffic_bytes`` carries the lowering-aware memory term (im2col
    patch-matrix bytes vs none for the fused direct kernel).
    """
    name = getattr(stage, "name", type(stage).__name__)
    b_a = int(getattr(stage, "in_bits", 8))
    bank = getattr(stage, "stage", None)        # ThresholdDense, if fused
    b_w = int(getattr(bank, "weight_bits", 8))
    geom = getattr(stage, "geom", None)
    traffic = stage_traffic_bytes(stage)
    if geom is not None:                        # FusedConvThresholdStage
        c = conv_cost(name, geom.in_ch, geom.out_ch, geom.kernel,
                      geom.out_h, geom.out_w, b_a, b_w, bias=False)
        c.traffic_bytes = traffic
        return c
    w = getattr(stage, "w", None)               # FloatHeadStage
    if bank is not None or w is not None:
        c = dense_cost(name, int(stage.in_dim), int(stage.out_dim),
                       b_a, b_w, bias=w is not None)
        c.traffic_bytes = traffic
        return c
    # pool / flatten / fallback chains: no MACs, just movement
    return LayerCost(name=name, bops=0.0, wm_bits=0, flops=0.0, n_params=0,
                     traffic_bytes=traffic)


def schedule_cost(stages: Iterable) -> ModelCost:
    """ModelCost of a compiled ``StageSchedule.stages`` list — the energy
    proxy the MLPerf-Tiny scenario runtime attaches to conv deployments."""
    return ModelCost([stage_cost(s) for s in stages])


# ---------------------------------------------------------------------------
# megakernel residency accounting + residency-aware traffic model
# ---------------------------------------------------------------------------

#: VMEM cap the megakernel residency planner budgets against — the same
#: conservative per-program working-set budget as the conv kernel's
#: accumulator cap (``kernels.ops.plan_conv_blocks``; real cores have
#: ~16 MB, the margin covers compiler padding to (8, 128) tiles and the
#: revolving input/output row blocks).
MEGAKERNEL_VMEM_BYTES = 1 << 21


def megakernel_residency_bytes(stages, block_m: int = 128) -> dict:
    """VMEM working set of an entire FusedThresholdStage run fused into one
    resident megakernel (``kernels.megakernel``): every stage's int8 weight
    matrix and int32 threshold bank live in VMEM for the whole wave, plus
    the two revolving inter-stage FIFO tiles (int32, ``block_m`` rows by
    the widest intermediate dim) and the input/output row blocks. This is
    the byte accounting ``deploy.lower.plan_megakernel`` sums against
    ``MEGAKERNEL_VMEM_BYTES`` — all components reported so the audit trail
    (and ``scripts/check_megakernel_residency.py``) can re-add them.
    """
    stages = list(stages)
    weight = sum(int(math.prod(s.stage.w_int.shape)) for s in stages)
    bank = sum(4 * int(math.prod(s.stage.thresholds.shape)) for s in stages)
    dims = [int(stages[0].in_dim)] + [int(s.out_dim) for s in stages]
    inter = max(dims[1:-1], default=0)
    tile = (4 * block_m * (dims[0] + dims[-1])    # input + output row blocks
            + 2 * 4 * block_m * inter)            # two revolving FIFO tiles
    return {"weight_bytes": int(weight), "bank_bytes": int(bank),
            "tile_bytes": int(tile),
            "total_bytes": int(weight + bank + tile)}


def megakernel_traffic_bytes(stages, wave_rows: int) -> float:
    """Residency-aware HBM traffic of one fused wave: parameters are
    fetched ONCE (they stay resident across the whole wave), activations
    cross HBM only at the segment boundary — the wave input is read and the
    final codes written; every inter-stage tensor lives in VMEM scratch."""
    stages = list(stages)
    res = megakernel_residency_bytes(stages)
    io = 4.0 * wave_rows * (int(stages[0].in_dim) + int(stages[-1].out_dim))
    return io + res["weight_bytes"] + res["bank_bytes"]


def staged_traffic_bytes(stages, wave_rows: int) -> float:
    """The per-stage dispatch baseline the megakernel deletes: every stage
    program re-reads its parameters and round-trips its input and output
    activations through HBM (the inter-stage write+read the fused kernel
    keeps on-chip). The difference vs ``megakernel_traffic_bytes`` is the
    modeled saving of the megakernel."""
    total = 0.0
    for s in stages:
        total += 4.0 * wave_rows * (int(s.in_dim) + int(s.out_dim))
        total += float(math.prod(s.stage.w_int.shape))
        total += 4.0 * float(math.prod(s.stage.thresholds.shape))
    return total


# ---------------------------------------------------------------------------
# LM-scale model FLOPs (used by launch/roofline.py)
# ---------------------------------------------------------------------------

def lm_model_flops(n_active_params: int, n_tokens: int, training: bool = True) -> float:
    """6*N*D for training (fwd+bwd), 2*N*D for inference forward."""
    mult = 6.0 if training else 2.0
    return mult * n_active_params * n_tokens
