"""Per-kernel validation: sweep shapes/dtypes and assert_allclose against the
ref.py pure-jnp oracles (interpret=True executes the Pallas kernel body on
CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.ref import (
    flash_attention_ref,
    multi_threshold_ref,
    qmatmul_ref,
    threshold_matmul_ref,
)


RNG = np.random.default_rng(42)


def _int8(shape):
    return jnp.asarray(RNG.integers(-127, 128, shape).astype(np.int8))


# ---------------------------------------------------------------------------
# qmatmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (100, 70, 50), (128, 128, 128),
                                   (33, 200, 17), (256, 64, 192)])
@pytest.mark.parametrize("relu", [False, True])
def test_qmatmul_matches_ref(m, k, n, relu):
    x = _int8((m, k))
    w = _int8((k, n))
    s = jnp.asarray(RNG.uniform(1e-3, 1e-2, n).astype(np.float32))
    b = jnp.asarray(RNG.standard_normal(n).astype(np.float32))
    y = ops.qmatmul(x, w, s, b, relu=relu, block_m=32, block_n=32, block_k=32,
                    interpret=True)
    yr = qmatmul_ref(x, w, s, b, relu=relu)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("out_scale", [0.125, 0.5, 0.03])
def test_qmatmul_requant_int8_exact(out_scale):
    x = _int8((64, 48))
    w = _int8((48, 40))
    s = jnp.asarray(RNG.uniform(1e-3, 5e-3, 40).astype(np.float32))
    b = jnp.asarray(RNG.standard_normal(40).astype(np.float32))
    y = ops.qmatmul(x, w, s, b, relu=True, out_scale=out_scale,
                    block_m=32, block_n=32, block_k=16, interpret=True)
    yr = qmatmul_ref(x, w, s, b, relu=True, out_scale=out_scale)
    assert y.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


def test_qmatmul_no_bias():
    x = _int8((32, 32))
    w = _int8((32, 32))
    s = jnp.ones((32,), jnp.float32) * 0.01
    y = ops.qmatmul(x, w, s, None, block_m=16, block_n=16, block_k=16,
                    interpret=True)
    yr = qmatmul_ref(x, w, s, None)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-6)


def test_qmatmul_reuse_factor_block_k_invariance():
    """Paper C6: the reuse factor (block_k = K/RF) must not change results."""
    x = _int8((64, 128))
    w = _int8((128, 64))
    s = jnp.full((64,), 0.005, jnp.float32)
    outs = [
        np.asarray(ops.qmatmul(x, w, s, None, block_m=32, block_n=32,
                               block_k=bk, interpret=True))
        for bk in (128, 64, 32, 16)   # RF = 1, 2, 4, 8
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-6)


# ---------------------------------------------------------------------------
# multi_threshold
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,c,steps", [(16, 8, 3), (37, 20, 7), (64, 12, 15),
                                       (128, 72, 255), (5, 3, 1)])
def test_multi_threshold_matches_ref(m, c, steps):
    acc = jnp.asarray(RNG.integers(-5000, 5000, (m, c)).astype(np.int32))
    thr = jnp.asarray(np.sort(RNG.integers(-4000, 4000, (c, steps)), axis=1)
                      .astype(np.int32))
    y = ops.multi_threshold(acc, thr, block_m=16, interpret=True)
    yr = multi_threshold_ref(acc, thr)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


def test_multi_threshold_range():
    """Output is a count in [0, S] — the act_bits integer code range."""
    acc = jnp.asarray(RNG.integers(-100, 100, (40, 10)).astype(np.int32))
    thr = jnp.asarray(np.sort(RNG.integers(-90, 90, (10, 7)), axis=1).astype(np.int32))
    y = np.asarray(ops.multi_threshold(acc, thr, interpret=True))
    assert y.min() >= 0 and y.max() <= 7


@pytest.mark.parametrize("m,k,n,steps", [(32, 64, 32, 7), (100, 70, 50, 15),
                                         (64, 128, 40, 3)])
def test_threshold_matmul_matches_ref(m, k, n, steps):
    x = _int8((m, k))
    w = _int8((k, n))
    thr = jnp.asarray(np.sort(RNG.integers(-30000, 30000, (n, steps)), axis=1)
                      .astype(np.int32))
    y = ops.threshold_matmul(x, w, thr, block_m=32, block_n=32, block_k=32,
                             interpret=True)
    yr = threshold_matmul_ref(x, w, thr)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


@pytest.mark.parametrize("operand,x_offset", [("bf16", 0), ("int8", 128)])
def test_threshold_matmul_exact_forms_on_unsigned_8bit_codes(operand,
                                                             x_offset):
    """Both exact MXU forms on codes in [0, 255]: bf16 inside the 2^24
    bound, int8 with the offset folded into the bank (which must also
    survive INT32_MAX/MIN entries without wrapping)."""
    x = jnp.asarray(RNG.integers(0, 256, (40, 200)), jnp.int32)
    w = _int8((200, 24))
    thr = np.sort(RNG.integers(-2_000_000, 2_000_000, (24, 15)), axis=1)
    thr[0, -1], thr[1, 0] = np.iinfo(np.int32).max, np.iinfo(np.int32).min
    thr = jnp.asarray(thr.astype(np.int32))
    y = ops.threshold_matmul(x, w, thr, operand=operand, x_offset=x_offset,
                             block_m=32, block_n=32, block_k=32,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(threshold_matmul_ref(x, w, thr)))


def test_megakernel_mixes_exact_forms_per_stage():
    """A bf16 stage feeding 8-bit codes to an int8 stage with offset."""
    weights = [_int8((20, 16)), _int8((16, 12))]
    banks = [jnp.asarray(np.sort(RNG.integers(-20000, 20000, (16, 255)),
                                 axis=1).astype(np.int32)),
             jnp.asarray(np.sort(RNG.integers(-300000, 300000, (12, 7)),
                                 axis=1).astype(np.int32))]
    x = jnp.asarray(RNG.integers(-127, 128, (24, 20)), jnp.int32)
    y = ops.mlp_megakernel(x, weights, banks, operands=("bf16", "int8"),
                           offsets=(0, 128), block_m=16, interpret=True)
    yr = ops.mlp_megakernel_ref(x, weights, banks)
    assert int(np.asarray(ops.mlp_megakernel_ref(x, weights[:1],
                                                 banks[:1])).max()) > 127
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("sq,sk", [(64, 64), (65, 65), (32, 96)])
def test_flash_attention_matches_ref(h, hkv, sq, sk):
    q = jnp.asarray(RNG.standard_normal((2, h, sq, 16)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((2, hkv, sk, 16)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((2, hkv, sk, 16)).astype(np.float32))
    o = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                            interpret=True)
    orf = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    q = jnp.asarray(RNG.standard_normal((1, 2, 64, 16)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((1, 2, 64, 16)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((1, 2, 64, 16)).astype(np.float32))
    o = ops.flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                            interpret=True)
    orf = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [16, 33])
def test_flash_attention_sliding_window(window):
    q = jnp.asarray(RNG.standard_normal((1, 2, 96, 16)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((1, 2, 96, 16)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((1, 2, 96, 16)).astype(np.float32))
    o = ops.flash_attention(q, k, v, causal=True, window=window,
                            block_q=32, block_k=32, interpret=True)
    orf = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), rtol=2e-5, atol=2e-5)


def test_flash_attention_q_offset_decode_chunk():
    """Continuation chunk: q holds positions [32, 48) of a 48-long stream."""
    S = 48
    q_all = jnp.asarray(RNG.standard_normal((1, 2, S, 16)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((1, 2, S, 16)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((1, 2, S, 16)).astype(np.float32))
    full = flash_attention_ref(q_all, k, v, causal=True)
    tail = ops.flash_attention(q_all[:, :, 32:], k, v, causal=True,
                               q_offset=32, block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(full[:, :, 32:]),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.standard_normal((1, 2, 64, 32))).astype(jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((1, 2, 64, 32))).astype(jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((1, 2, 64, 32))).astype(jnp.bfloat16)
    o = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                            interpret=True)
    orf = flash_attention_ref(q, k, v, causal=True)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(orf, np.float32),
        rtol=2e-2, atol=2e-2)


def test_flash_attention_matches_model_attention():
    """Kernel agrees with the model-side chunked_attention oracle (which the
    LM stack lowers) — ties the kernel layer to the model layer."""
    from repro.configs import get_config
    from repro.models.attention import chunked_attention

    cfg = get_config("llama3-8b").reduced()
    B, S = 2, 64
    q = jnp.asarray(RNG.standard_normal((B, S, cfg.n_heads, cfg.hd)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((B, S, cfg.n_kv_heads, cfg.hd)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((B, S, cfg.n_kv_heads, cfg.hd)).astype(np.float32))
    pos = jnp.arange(S)
    out_model = chunked_attention(cfg, q, k, v, pos, pos)      # (B,S,H,hd)
    out_kernel = ops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True, block_q=32, block_k=32, interpret=True,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_model),
                               rtol=3e-5, atol=3e-5)
