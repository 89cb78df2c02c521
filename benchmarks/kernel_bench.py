"""Kernel-level benchmark: the fused Pallas dataflow stage vs the unfused
XLA op chain, plus roofline byte/FLOP accounting per kernel.

On this CPU container the Pallas kernels run in interpret mode (Python
semantics — wall times are meaningless), so the measured comparison is
unfused-XLA vs fused-XLA epilogue, and the Pallas win is reported
structurally: HBM traffic eliminated by fusion (the activation tensor
round-trips the fused stage saves), which is what moves the memory roofline
term on real hardware.

The conv section measures the two conv lowerings of one streamlined stage —
the fused direct-conv path (shifted-window taps, no patch matrix) vs the
im2col + threshold_matmul fallback — on their XLA fast paths, next to the
lowering-aware traffic model from ``core.bops.stage_cost`` (the im2col
matrix write+read the direct kernel never pays)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import banner, emit_json, print_rows, row, time_call
from repro.core.bops import conv_input_band_bytes, stage_cost
from repro.core.streamline import make_threshold_stage
from repro.deploy.lower import (
    ConvGeom,
    FusedConvThresholdStage,
    _float_mm_safe,
)
from repro.kernels.ops import plan_conv_blocks
from repro.kernels.ref import qmatmul_ref


def _unfused(x_int, w_int, scale, bias):
    acc = jax.lax.dot_general(x_int.astype(jnp.int32), w_int.astype(jnp.int32),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32)          # stage 1 out
    y = y * scale[None, :]               # dequant stage
    y = y + bias[None, :]                # bias stage
    y = jnp.maximum(y, 0.0)              # relu stage
    q = jnp.round(y / 0.125)             # requant stage
    return jnp.clip(q, -128, 127).astype(jnp.int8)


def run():
    banner("Kernel bench: fused dataflow stage (qmatmul) traffic accounting")
    rng = np.random.default_rng(0)
    M, K, N = 512, 512, 512
    x = jnp.asarray(rng.integers(-127, 128, (M, K)).astype(np.int8))
    w = jnp.asarray(rng.integers(-127, 128, (K, N)).astype(np.int8))
    s = jnp.asarray(rng.uniform(1e-3, 1e-2, N).astype(np.float32))
    b = jnp.asarray(rng.standard_normal(N).astype(np.float32))

    t_unfused = time_call(jax.jit(_unfused), x, w, s, b)
    t_fused_xla = time_call(jax.jit(
        lambda x, w, s, b: qmatmul_ref(x, w, s, b, relu=True, out_scale=0.125)),
        x, w, s, b)

    # HBM traffic model: unfused writes/reads the (M,N) int32 accumulator and
    # the (M,N) f32 intermediate between stages; fused keeps both in VMEM.
    inter_stage_bytes = M * N * 4 * 2 * 2        # acc + f32, write+read
    io_bytes = M * K + K * N + N * 8 + M * N     # in/out tensors once
    rows = [
        row("kernel/qmatmul_unfused_xla", t_unfused,
            hbm_bytes_model=io_bytes + inter_stage_bytes),
        row("kernel/qmatmul_fused_xla_epilogue", t_fused_xla,
            hbm_bytes_model=io_bytes + inter_stage_bytes // 2),
        row("kernel/qmatmul_fused_pallas", 0.0,
            hbm_bytes_model=io_bytes,
            note="interpret-mode on CPU; traffic model only",
            traffic_saving=f"{inter_stage_bytes/(io_bytes+inter_stage_bytes):.0%}"),
    ]
    rows += _conv_lowering_bench(rng)
    rows += _megakernel_bench()
    print_rows(rows)
    emit_json("BENCH_kernels.json", {"rows": rows})
    return rows


def _conv_lowering_bench(rng):
    """Direct-conv vs im2col lowering of one streamlined conv stage."""
    banner("Kernel bench: fused direct-conv vs materialized im2col")
    h = w = 32
    c, f, k, bits = 16, 32, 3, 4
    w_int = jnp.asarray(rng.integers(-7, 8, (k * k * c, f)), jnp.int32)
    s_w = jnp.full((f,), 2.0 ** -4, jnp.float32)
    b = jnp.zeros((f,), jnp.float32)
    td = make_threshold_stage(w_int, s_w, b, in_scale=2.0 ** -5,
                              act_bits=bits, s_out=2.0 ** -3)
    geom = ConvGeom(kernel=k, stride=1, padding="SAME", in_h=h, in_w=w,
                    in_ch=c, out_h=h, out_w=w, out_ch=f)
    mm = _float_mm_safe(td.w_int, bits)
    mk = lambda kind: FusedConvThresholdStage(
        name=f"conv[{kind}]", stage=td, geom=geom, in_scale=2.0 ** -5,
        in_bits=bits, mm_float=mm, lowering=kind)
    direct, i2c = mk("direct"), mk("im2col")
    x = jnp.asarray(rng.integers(0, 2 ** bits, (8, h, w, c)), jnp.int32)
    f_direct = jax.jit(direct.apply_fast)
    f_i2c = jax.jit(i2c.apply_fast)
    assert bool(jnp.all(f_direct(x).reshape(-1) == f_i2c(x).reshape(-1)))
    t_direct = time_call(f_direct, x)
    t_i2c = time_call(f_i2c, x)
    traffic_d = stage_cost(direct).traffic_bytes
    traffic_i = stage_cost(i2c).traffic_bytes
    # the kernel planner's row block and its banded input bytes (halo rows
    # re-fetched per block)
    block_h = plan_conv_blocks(geom.out_h, geom.out_w, geom.out_ch)
    return [
        row("kernel/conv_threshold_direct", t_direct,
            hbm_bytes_model=int(traffic_d)),
        row("kernel/conv_threshold_im2col", t_i2c,
            hbm_bytes_model=int(traffic_i),
            im2col_bytes=int(traffic_i - traffic_d),
            direct_speedup=f"{t_i2c / max(t_direct, 1e-9):.2f}x"),
        row("kernel/conv_threshold_block_h", 0.0,
            block_h=block_h,
            banded_input_bytes=int(conv_input_band_bytes(geom, block_h))),
    ]


def _megakernel_bench():
    """Staged lax.map dispatch vs the whole-network-resident megakernel.

    Head-to-head on the two MLP goldens (KWS and AD — the single-segment
    waves where the staged pipeline's speedup over the host loop used to
    flatline near 1.0x): both modes run the same compiled segment
    programs on the same pool, and must agree bit for bit. A deep wave
    (many small micro-batches) makes the per-micro-batch per-stage
    dispatch the staged path pays visible; best-of-N timing because this
    shared-CPU container's noise floor swamps a median at millisecond
    scale. Next to the measured speedup sits the residency traffic
    model's saving — the per-stage weight/bank re-fetches and inter-stage
    HBM round-trips the fused dispatch deletes (``docs/megakernel.md``)."""
    banner("Kernel bench: megakernel vs staged segment dispatch (KWS/AD)")
    import os
    import time

    import jax.random

    from repro.core.bops import megakernel_traffic_bytes, staged_traffic_bytes
    from repro.core.qir import export_qmlp
    from repro.deploy import compile_graph
    from repro.models.tiny import ADAutoencoder, KWSMLP

    fast = os.environ.get("REPRO_FAST", "0") not in ("0", "")
    batch, mb, iters = (256, 4, 3) if fast else (1024, 4, 7)

    def best(run, x):
        y, _ = run(x, micro_batch=mb)
        jax.block_until_ready(y)             # compile + warm
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            y, _ = run(x, micro_batch=mb)
            jax.block_until_ready(y)
            times.append(time.perf_counter() - t0)
        return min(times)

    rng = np.random.default_rng(2022)
    builds = {
        "kws": (KWSMLP(width=32), jax.random.PRNGKey(10), 490),
        "ad": (ADAutoencoder(width=24), jax.random.PRNGKey(11), 128),
    }
    rows = []
    for name, (model, key, in_dim) in builds.items():
        params = model.init(key)
        hidden, _ = model.layers()
        graph = export_qmlp(hidden, params["hidden"], params["head"],
                            meta={"model": name}, freeze_scales=True,
                            in_scale=1.0 / 127.0)
        cm = compile_graph(graph, in_scale=1.0 / 127.0, use_pallas=False)
        x = jnp.asarray(rng.integers(-127, 128, (batch, in_dim)), jnp.int32)

        cm.set_megakernel(False)
        y_staged, _ = cm.streaming_compiled(x, micro_batch=mb)
        t_staged = best(cm.streaming_compiled, x)

        cm.set_megakernel(True)
        assert cm._mega_plans, f"{name}: planner admitted no megakernel"
        plan = next(iter(cm._mega_plans.values()))
        y_mega, stats = cm.streaming_compiled(x, micro_batch=mb)
        assert stats.megakernel == [(plan.start, plan.stop)]
        t_mega = best(cm.streaming_compiled, x)
        assert bool(jnp.all(jnp.isclose(y_staged, y_mega, atol=1e-5))), name

        run_stages = cm.schedule.stages[plan.start:plan.stop]
        n_micro = -(-batch // mb)
        mega_b = megakernel_traffic_bytes(run_stages, batch)
        staged_b = n_micro * staged_traffic_bytes(run_stages, mb)
        rows.append(row(
            f"kernel/megakernel_{name}", t_mega * 1e6,
            staged_us=round(t_staged * 1e6, 1),
            megakernel_speedup=f"{t_staged / max(t_mega, 1e-9):.2f}x",
            batch=batch, micro_batch=mb,
            fused_stages=plan.n_stages,
            resident_bytes=plan.total_bytes,
            modeled_bytes_saved=int(staged_b - mega_b),
            hbm_bytes_model=int(mega_b)))
    return rows


if __name__ == "__main__":
    run()
