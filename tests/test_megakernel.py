"""Megakernel unit tests: the whole-segment Pallas kernel against its
pure-jnp oracle, the residency planner's admit/reject logic, and the byte
accounting of the residency model (``docs/megakernel.md``).

Golden-fixture bit-exactness across executor entry points lives in
``tests/test_golden.py``; this file covers the pieces in isolation.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.bops import (
    MEGAKERNEL_VMEM_BYTES,
    megakernel_residency_bytes,
    megakernel_traffic_bytes,
    staged_traffic_bytes,
)
from repro.core.streamline import ThresholdDense
from repro.deploy import (
    FusedThresholdStage,
    MegakernelSegment,
    Segment,
    plan_megakernel,
)
from repro.kernels import ops

RNG = np.random.default_rng(7)


def _chain(in_dim, out_dims, steps):
    """Random (weights, banks) for a chained stage run; codes stay tiny so
    every accumulator is exact int32."""
    weights, banks = [], []
    k = in_dim
    for n, s in zip(out_dims, steps):
        weights.append(jnp.asarray(
            RNG.integers(-8, 9, (k, n)).astype(np.int8)))
        banks.append(jnp.asarray(
            np.sort(RNG.integers(-60, 60, (n, s)), axis=1).astype(np.int32)))
        k = n
    return weights, banks


def _fts(name, in_dim, out_dim, steps=7):
    w, b = _chain(in_dim, [out_dim], [steps])
    td = ThresholdDense(w_int=w[0], thresholds=b[0], out_scale=0.25,
                        act_bits=3)
    return FusedThresholdStage(name=name, stage=td, in_dim=in_dim,
                               out_dim=out_dim, in_scale=1.0)


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,in_dim,out_dims,steps", [
    (16, 12, [24, 16], [7, 7]),          # two stages, even dims
    (12, 10, [18, 30, 6], [3, 15, 7]),   # three stages, ragged dims + pad
    (8, 20, [16], [255]),                # single stage: no FIFO scratch
    (33, 7, [9, 5, 11, 4], [7, 3, 3, 1]),  # deep chain, odd everything
])
def test_mlp_megakernel_matches_ref(m, in_dim, out_dims, steps):
    weights, banks = _chain(in_dim, out_dims, steps)
    x = jnp.asarray(RNG.integers(0, 8, (m, in_dim)).astype(np.int32))
    y = ops.mlp_megakernel(x, weights, banks, block_m=16, interpret=True)
    yr = ops.mlp_megakernel_ref(x, weights, banks)
    assert y.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


def test_mlp_megakernel_output_range():
    """Codes are threshold counts in [0, S_last]."""
    weights, banks = _chain(10, [12, 8], [7, 3])
    x = jnp.asarray(RNG.integers(0, 8, (24, 10)).astype(np.int32))
    y = np.asarray(ops.mlp_megakernel(x, weights, banks, interpret=True))
    assert y.min() >= 0 and y.max() <= 3


# ---------------------------------------------------------------------------
# deep-bank double buffering (multi_threshold slab path, S >= 256)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,c,steps", [(16, 12, 256),   # exact slab multiple
                                       (24, 8, 300),    # INT32_MAX row pad
                                       (8, 40, 511)])   # 8-bit act worst case
def test_multi_threshold_deep_bank_slab_path_matches_ref(m, c, steps):
    from repro.kernels.multi_threshold import DOUBLE_BUFFER_STEPS
    assert steps >= DOUBLE_BUFFER_STEPS   # these hit the slab-grid kernel
    acc = jnp.asarray(RNG.integers(-5000, 5000, (m, c)).astype(np.int32))
    thr = jnp.asarray(np.sort(RNG.integers(-4000, 4000, (c, steps)), axis=1)
                      .astype(np.int32))
    y = ops.multi_threshold(acc, thr, block_m=16, interpret=True)
    yr = ops.multi_threshold_ref(acc, thr)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


# ---------------------------------------------------------------------------
# residency planner
# ---------------------------------------------------------------------------

def test_plan_admits_fused_run_and_accounts_bytes():
    stages = [_fts("d0", 16, 32), _fts("d1", 32, 24), _fts("d2", 24, 8)]
    plan = plan_megakernel(stages, Segment(0, 3, compiled=True))
    assert isinstance(plan, MegakernelSegment)
    assert (plan.start, plan.stop, plan.n_stages) == (0, 3, 3)
    res = megakernel_residency_bytes(stages, block_m=plan.block_m)
    assert plan.weight_bytes == res["weight_bytes"]
    assert plan.bank_bytes == res["bank_bytes"]
    assert plan.tile_bytes == res["tile_bytes"]
    assert plan.total_bytes == res["total_bytes"] <= plan.budget_bytes


def test_plan_rejects_short_run_budget_and_uncompiled():
    stages = [_fts("d0", 16, 32), _fts("d1", 32, 8)]
    # a single fused stage is not worth a megakernel
    assert plan_megakernel(stages[:1], Segment(0, 1, compiled=True)) is None
    # the working set must fit the cap
    assert plan_megakernel(stages, Segment(0, 2, compiled=True),
                           budget_bytes=64) is None
    # host-boundary segments never fuse
    assert plan_megakernel(stages, Segment(0, 2, compiled=False)) is None


def test_plan_picks_longest_fused_run():
    """A non-fusable stage splits the segment; the longer run wins."""
    stages = [_fts("a0", 8, 8), _fts("a1", 8, 8),
              object(),                              # break in the chain
              _fts("b0", 8, 8), _fts("b1", 8, 8), _fts("b2", 8, 8)]
    plan = plan_megakernel(stages, Segment(0, 6, compiled=True))
    assert (plan.start, plan.stop) == (3, 6)


def test_residency_components_readd_and_default_budget():
    stages = [_fts("d0", 490, 32), _fts("d1", 32, 32)]
    res = megakernel_residency_bytes(stages)
    assert res["total_bytes"] == (res["weight_bytes"] + res["bank_bytes"]
                                  + res["tile_bytes"])
    assert res["weight_bytes"] == 490 * 32 + 32 * 32          # int8: 1 B/elem
    assert res["bank_bytes"] == 4 * 7 * (32 + 32)             # int32 banks
    assert MEGAKERNEL_VMEM_BYTES == 1 << 21


def test_traffic_model_megakernel_beats_staged():
    """The residency traffic model: the fused wave
    skips every inter-stage HBM round-trip and re-fetch, so it can only
    save bytes — and the saving grows with chain depth."""
    stages = [_fts("d0", 64, 48), _fts("d1", 48, 48), _fts("d2", 48, 12)]
    for rows in (1, 16, 256):
        mega = megakernel_traffic_bytes(stages, rows)
        staged = staged_traffic_bytes(stages, rows)
        assert mega < staged
    # boundary io is identical; the delta is exactly the inter-stage
    # activations plus nothing else for a 1-deep "chain"
    one = [stages[0]]
    assert (staged_traffic_bytes(one, 8)
            == megakernel_traffic_bytes(one, 8))
