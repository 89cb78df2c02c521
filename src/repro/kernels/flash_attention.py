"""Flash attention Pallas kernel (GQA + causal + sliding window).

TPU adaptation of the paper's C4 insight — keep the hot loop's working set
on-chip: the (bq, bk) score tile, the online-softmax stats, and the output
accumulator all live in VMEM/VREGs across the KV sweep; only q/k/v block
streams and one final output write touch HBM. The (Sq x Sk) score matrix is
never materialized.

Grid: (B, H, Sq/bq, Sk/bk) with the KV dimension innermost (sequential —
the online-softmax carry lives in VMEM scratch). GQA is handled in the k/v
BlockSpec index_map: query head h reads kv head h // (H / Hkv), so no
k/v replication tensor is ever built.

Fully-masked (future) KV blocks are skipped with pl.when — for causal
attention that halves the executed grid, same FLOPs saving as the paper's
layer-merging removed stages.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_kv: int, block_q: int, block_k: int, scale: float,
                  causal: bool, window: int, q_offset: int, kv_len: int):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level causal/window skip: any (qpos, kpos) pair valid?
    q_lo = i * block_q + q_offset
    q_hi = q_lo + block_q - 1
    k_lo = j * block_k
    k_hi = k_lo + block_k - 1
    live = k_lo < kv_len                      # padded KV blocks never run
    if causal:
        live = jnp.logical_and(live, k_lo <= q_hi)
    if window > 0:
        live = jnp.logical_and(live, k_hi > q_lo - window)

    @pl.when(live)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                        # (bq, bk)

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = k_pos < kv_len                   # mask padded keys exactly
        if causal:
            ok &= k_pos <= q_pos
        if window > 0:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[:, :1]                            # (bq, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,               # (B, H, Sq, D)
    k: jnp.ndarray,               # (B, Hkv, Sk, D)
    v: jnp.ndarray,               # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    kv_len: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash attention. Sq % block_q == 0, Sk % block_k == 0 (ops pads;
    ``kv_len`` masks the KV padding exactly).

    D should be lane-aligned (128) for MXU efficiency on real hardware."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    kv_len = Sk if kv_len is None else kv_len
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    n_kv = Sk // block_k

    kernel = functools.partial(
        _flash_kernel, n_kv=n_kv, block_q=block_q, block_k=block_k,
        scale=D ** -0.5, causal=causal, window=window, q_offset=q_offset,
        kv_len=kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, Sq // block_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running denom
            pltpu.VMEM((block_q, D), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
