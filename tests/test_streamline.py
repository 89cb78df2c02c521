"""Streamlining exactness: the integer multi-threshold deployment graph must
agree with the float QAT reference everywhere (paper C2 — FINN's streamlining
is exact, not approximate)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core.qlayers import QDense, QDenseBatchNorm
from repro.core.quantizers import IntQuantizer
from repro.core.streamline import (
    StreamlinedMLP,
    apply_threshold_dense,
    float_ref_dense,
    multi_threshold,
    quant_act_ref,
    streamline_dense,
    streamline_mlp,
)


def _random_bn_params(key, in_dim, out_dim):
    ks = jax.random.split(key, 6)
    return {
        "w": jax.random.normal(ks[0], (in_dim, out_dim)) * (in_dim ** -0.5),
        "b": jax.random.normal(ks[1], (out_dim,)) * 0.1,
        "gamma": jax.random.normal(ks[2], (out_dim,)) * 0.2 + 1.0,
        "beta": jax.random.normal(ks[3], (out_dim,)) * 0.1,
        "mu": jax.random.normal(ks[4], (out_dim,)) * 0.1,
        "sigma2": jax.nn.softplus(jax.random.normal(ks[5], (out_dim,))) + 0.5,
    }


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("bits", [3, 4, 8])
def test_threshold_stage_matches_float_reference(seed, bits):
    """Integer thresholds reproduce fold->quantW->matmul->ReLU->quantA exactly."""
    in_dim, out_dim = 24, 16
    params = _random_bn_params(jax.random.PRNGKey(seed), in_dim, out_dim)
    in_scale = 0.05
    stage = streamline_dense(params, weight_bits=bits, act_bits=bits,
                             in_scale=in_scale)

    in_qmax = 2 ** (bits - 1) - 1
    x_int = jax.random.randint(jax.random.PRNGKey(seed + 100), (64, in_dim),
                               -in_qmax, in_qmax + 1)
    y_int = apply_threshold_dense(stage, x_int)
    y_ref = float_ref_dense(params, x_int.astype(jnp.float32) * in_scale,
                            weight_bits=bits, act_bits=bits,
                            s_out=stage.out_scale)
    np.testing.assert_array_equal(np.asarray(y_int), np.asarray(y_ref))


def test_thresholds_sorted_and_output_in_range():
    params = _random_bn_params(jax.random.PRNGKey(0), 16, 8)
    stage = streamline_dense(params, weight_bits=4, act_bits=4, in_scale=0.1)
    t = np.asarray(stage.thresholds)
    assert np.all(np.diff(t, axis=1) >= 0)          # monotone banks
    x_int = jax.random.randint(jax.random.PRNGKey(1), (32, 16), -7, 8)
    y = np.asarray(apply_threshold_dense(stage, x_int))
    assert y.min() >= 0 and y.max() <= stage.n_steps


def test_multi_threshold_reference_count_semantics():
    acc = jnp.asarray([[-5, 0, 10]]).astype(jnp.int32).T   # (3,1)
    thr = jnp.asarray([[-3, 2], [-3, 2], [-3, 2]]).astype(jnp.int32)
    out = np.asarray(multi_threshold(acc, thr))
    np.testing.assert_array_equal(out[:, 0], [0, 1, 2])


def test_quant_act_ref_half_up():
    # boundary 0.5 rounds UP (FINN convention), unlike jnp.round's half-even
    y = quant_act_ref(jnp.asarray([0.5, 1.5, 2.5]), 1.0, 7)
    np.testing.assert_array_equal(np.asarray(y), [1, 2, 3])


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([2, 3, 4, 6]))
def test_streamline_property_exact_for_random_stages(seed, bits):
    """Property: for any BN params and int inputs, thresholds == float ref."""
    params = _random_bn_params(jax.random.PRNGKey(seed), 8, 5)
    stage = streamline_dense(params, weight_bits=bits, act_bits=bits,
                             in_scale=0.07)
    in_qmax = 2 ** (bits - 1) - 1
    x_int = jax.random.randint(jax.random.PRNGKey(seed ^ 1234), (16, 8),
                               -in_qmax, in_qmax + 1)
    y_int = apply_threshold_dense(stage, x_int)
    y_ref = float_ref_dense(params, x_int.astype(jnp.float32) * 0.07,
                            weight_bits=bits, act_bits=bits,
                            s_out=stage.out_scale)
    np.testing.assert_array_equal(np.asarray(y_int), np.asarray(y_ref))


def test_streamlined_mlp_end_to_end_prediction_parity():
    """Full pipeline: streamlined integer MLP predicts the same classes as
    the float QAT forward for a trained-ish stack."""
    key = jax.random.PRNGKey(0)
    dims = [12, 10, 8]
    bits = 4
    layer_defs = [QDenseBatchNorm(dims[i], dims[i + 1], weight_bits=bits,
                                  act_bits=bits) for i in range(2)]
    params_list = [_random_bn_params(jax.random.fold_in(key, i), dims[i], dims[i + 1])
                   for i in range(2)]
    head = QDense(dims[-1], 4, weight_bits=32, act_bits=32)
    head_params = head.init(jax.random.PRNGKey(9))

    smlp = streamline_mlp(layer_defs, params_list, in_scale=0.05,
                          head_params=head_params)

    x_int = jax.random.randint(jax.random.PRNGKey(2), (32, 12), -7, 8)
    pred_int = np.asarray(smlp.predict(x_int))

    # float reference: stage-by-stage quantized forward
    h = x_int
    scale = 0.05
    for ld, p, st_ in zip(layer_defs, params_list, smlp.stages):
        h = float_ref_dense(p, h.astype(jnp.float32) * scale,
                            weight_bits=bits, act_bits=bits, s_out=st_.out_scale)
        scale = st_.out_scale
    logits = h.astype(jnp.float32) @ head_params["w"] * scale + head_params["b"]
    pred_ref = np.asarray(jnp.argmax(logits, axis=-1))
    np.testing.assert_array_equal(pred_int, pred_ref)


def test_streamline_plain_dense_no_bn():
    """QDense (no BN) also streamlines (fold is identity)."""
    key = jax.random.PRNGKey(3)
    params = {"w": jax.random.normal(key, (10, 6)) * 0.3,
              "b": jnp.zeros((6,))}
    stage = streamline_dense(params, weight_bits=4, act_bits=4, in_scale=0.1)
    x_int = jax.random.randint(jax.random.PRNGKey(4), (8, 10), -7, 8)
    y_int = apply_threshold_dense(stage, x_int)
    y_ref = float_ref_dense(params, x_int.astype(jnp.float32) * 0.1,
                            weight_bits=4, act_bits=4, s_out=stage.out_scale)
    np.testing.assert_array_equal(np.asarray(y_int), np.asarray(y_ref))


def test_streamlined_stage_runs_on_pallas_kernel():
    """The deployment stage executes on kernels.ops.threshold_matmul with
    identical integer outputs — QIR -> kernel parity."""
    from repro.kernels import ops

    params = _random_bn_params(jax.random.PRNGKey(5), 16, 8)
    stage = streamline_dense(params, weight_bits=4, act_bits=4, in_scale=0.05)
    x_int = jax.random.randint(jax.random.PRNGKey(6), (24, 16), -7, 8)
    y_graph = apply_threshold_dense(stage, x_int)
    y_kernel = ops.threshold_matmul(
        x_int.astype(jnp.int8), stage.w_int, stage.thresholds,
        block_m=8, block_n=8, block_k=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_kernel), np.asarray(y_graph))
