"""Hypothesis property tests for the Pallas kernels: random shapes, dtypes,
block sizes — every draw must match the ref.py oracle exactly (integer
kernels) or to fp tolerance (attention)."""

import numpy as np
import jax.numpy as jnp
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels.ref import (
    flash_attention_ref,
    multi_threshold_ref,
    qmatmul_ref,
    threshold_matmul_ref,
)


@settings(max_examples=12, deadline=None)
@given(
    st.integers(1, 70),            # M
    st.integers(1, 70),            # K
    st.integers(1, 70),            # N
    st.sampled_from([8, 16, 32]),  # block
    st.booleans(),                 # relu
    st.integers(0, 2 ** 31 - 1),
)
def test_qmatmul_property(m, k, n, block, relu, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-127, 128, (m, k)).astype(np.int8))
    w = jnp.asarray(rng.integers(-127, 128, (k, n)).astype(np.int8))
    s = jnp.asarray(rng.uniform(1e-3, 1e-2, n).astype(np.float32))
    y = ops.qmatmul(x, w, s, None, relu=relu,
                    block_m=block, block_n=block, block_k=block, interpret=True)
    yr = qmatmul_ref(x, w, s, None, relu=relu)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=12, deadline=None)
@given(
    st.integers(1, 60), st.integers(1, 40), st.integers(1, 31),
    st.integers(0, 2 ** 31 - 1),
)
def test_multi_threshold_property(m, c, steps, seed):
    rng = np.random.default_rng(seed)
    acc = jnp.asarray(rng.integers(-10_000, 10_000, (m, c)).astype(np.int32))
    thr = jnp.asarray(np.sort(rng.integers(-9_000, 9_000, (c, steps)), axis=1)
                      .astype(np.int32))
    y = ops.multi_threshold(acc, thr, block_m=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(multi_threshold_ref(acc, thr)))


@settings(max_examples=10, deadline=None)
@given(
    st.integers(1, 40), st.integers(1, 50), st.integers(1, 24),
    st.integers(1, 15), st.integers(0, 2 ** 31 - 1),
)
def test_threshold_matmul_property(m, k, n, steps, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-127, 128, (m, k)).astype(np.int8))
    w = jnp.asarray(rng.integers(-127, 128, (k, n)).astype(np.int8))
    thr = jnp.asarray(
        np.sort(rng.integers(-40_000, 40_000, (n, steps)), axis=1)
        .astype(np.int32))
    y = ops.threshold_matmul(x, w, thr, block_m=16, block_n=16, block_k=16,
                             interpret=True)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(threshold_matmul_ref(x, w, thr)))


@settings(max_examples=8, deadline=None)
@given(
    st.integers(1, 2),                       # batch
    st.sampled_from([(2, 1), (4, 2), (4, 4)]),  # (H, Hkv)
    st.integers(3, 80),                      # Sq = Sk
    st.sampled_from([8, 16, 32]),            # D
    st.booleans(),                           # causal
    st.integers(0, 2 ** 31 - 1),
)
def test_flash_attention_property(b, heads, s, d, causal, seed):
    h, hkv = heads
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, h, s, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32))
    o = ops.flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                            interpret=True)
    orf = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=3e-5, atol=3e-5)
