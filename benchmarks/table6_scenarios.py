"""Beyond-paper Table 6: MLPerf-Tiny load scenarios over compiled deployments.

The paper reports single-inference latency/energy (Table 5). MLPerf Tiny
actually scores submissions under LoadGen scenarios; this section runs the
full sweep — SingleStream / MultiStream / Offline / Server — for all four
Table-1 models through ``repro.deploy``, every one of them on the real
compiler path:

  * KWS + AD:   QAT export -> QIR json -> streamline/fuse -> jit schedule
    (``export_qmlp``), all-dense fused threshold stages.
  * IC + CNV:   ``export_qcnn`` -> im2col fused conv threshold stages (with
    calibrated po2 activation scales for IC and FINN-style bipolar sign
    banks for the binary CNV) + integer MaxPool / Flatten stages.

Every Offline row compares the compiled executor against the unfused
per-node QIR interpreter (the "no compiler" baseline it must beat), checks
compiled-vs-unfused argmax parity, and carries a per-stage latency
breakdown (``stage_ms``) so conv-vs-dense stage costs are visible. The
energy proxy for compiled models comes from ``core.bops.schedule_cost`` —
Eq. 1 BOPs per lowered stage, conv stages included.

Conv models (IC, CNV) additionally compare the two conv lowerings head to
head on the same Offline pool: the fused direct-conv path (default; no
materialized im2col) vs ``conv_lowering="im2col"`` (patch matrix +
threshold_matmul), with the lowering-aware traffic model
(``ModelCost.traffic_bytes``) printed next to the measured speedup and
bit-exactness asserted between the two.

The streaming section runs every model through BOTH streaming executors —
the compiled segment-wave path (``streaming_compiled``: one jit program per
segment wave, no host loop) and the host queue-loop reference
(``streaming_host``) — at the executor's default micro-batch, asserts the
three-way bit-equality (offline == host == compiled), and reports the
compiled-vs-host speedup next to the FIFO plan's depths and modeled
cycles.

Everything is also emitted machine-readable to ``BENCH_scenarios.json``
(``REPRO_BENCH_DIR``) so the perf trajectory is tracked across PRs.

Set REPRO_FAST=1 for a reduced-size pass (CI / smoke).
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import banner, emit_json, print_rows, row, time_call
from repro.core.bops import schedule_cost
from repro.core.qir import export_qcnn, export_qmlp
from repro.deploy import compile_graph
from repro.deploy.scenarios import offline, server_streaming, single_stream
from repro.models.tiny import ADAutoencoder, CNVModel, ICModel, KWSMLP
from repro.serve import measure_wave_service_s
from repro.serving.engine import TinyModelServer

IN_SCALE = 1.0 / 127.0
FAST = os.environ.get("REPRO_FAST", "0") not in ("0", "")


def _compile_mlp(model, key):
    params = model.init(key)
    hidden_defs, _ = model.layers()
    graph = export_qmlp(hidden_defs, params["hidden"], params["head"],
                        meta={"model": type(model).__name__},
                        freeze_scales=True, in_scale=IN_SCALE)
    return compile_graph(graph, in_scale=IN_SCALE, use_pallas=False)


def _compile_conv(model, key, rng, conv_lowering=None):
    params = model.init(key)
    cal = rng.integers(-127, 128, (8, model.in_hw, model.in_hw,
                                   model.in_ch)).astype(np.int32)
    graph = export_qcnn(model, params, calibrate=cal)
    return compile_graph(graph, in_scale=graph.meta["in_scale"],
                         use_pallas=False, conv_lowering=conv_lowering)


def _time_offline(fn, xb, iters: int = 5) -> float:
    """Median queries/sec of fn over the batch."""
    jax.block_until_ready(fn(xb))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(xb))
        times.append(time.perf_counter() - t0)
    times.sort()
    return xb.shape[0] / times[len(times) // 2]




def run():
    banner("Table 6: MLPerf-Tiny scenarios over compiled deployments")
    key = jax.random.PRNGKey(0)
    rng = np.random.default_rng(0)

    entries = {}  # name -> (compiled, make_query, bits)

    kws, ad = KWSMLP(), ADAutoencoder()
    for name, model, dim, bits in (("KWS-FINN", kws, 490, 3),
                                   ("AD-hls4ml", ad, 128, 8)):
        cm = _compile_mlp(model, key)
        mk = (lambda d: lambda i: rng.integers(-127, 128, (d,)).astype(np.int32))(dim)
        entries[name] = (cm, mk, bits)

    ic, cnv = ICModel(), CNVModel()
    for name, model, bits in (("IC-hls4ml", ic, 8), ("IC-FINN-CNV", cnv, 1)):
        cm = _compile_conv(model, key, rng)
        hw, ch = model.in_hw, model.in_ch
        mk = (lambda h, c: lambda i: rng.integers(
            -127, 128, (h, h, c)).astype(np.int32))(hw, ch)
        entries[name] = (cm, mk, bits)

    rows = []
    scenario_json = {"rows": [], "streaming": [], "fast": FAST}
    for name, (cm, mk, bits) in entries.items():
        conv = cm.schedule.n_fused_conv > 0
        cost = schedule_cost(cm.schedule.stages)
        n_off = (16 if conv else 64) if FAST else (48 if conv else 256)
        n_ss = (8 if conv else 16) if FAST else (16 if conv else 48)

        ss = single_stream(cm.offline, mk, n_queries=n_ss,
                           model_cost=cost, bits=bits)
        off = offline(cm.offline, mk, n_samples=n_off,
                      model_cost=cost, bits=bits, compiled=cm)

        # unfused per-node baseline + parity on the same Offline pool
        n_ref = min(n_off, 8 if conv else n_off)   # eager conv is slow
        xb = jnp.asarray(np.stack([mk(i) for i in range(n_ref)]), jnp.int32)
        ref_qps = _time_offline(cm.reference, xb, iters=1)
        y_c = np.asarray(cm.offline(xb))
        y_r = np.asarray(cm.reference(xb))
        parity = float((np.argmax(y_c, -1) == np.argmax(y_r, -1)).mean())
        speedup = off.throughput_qps / max(ref_qps, 1e-9)

        rows.append(row(
            f"table6/{name}/SingleStream", ss.p50_ms * 1e3,
            p50_ms=f"{ss.p50_ms:.3f}", p99_ms=f"{ss.p99_ms:.3f}",
            roofline_uJ=f"{ss.energy_proxy_uJ:.2f}"))
        rows.append(row(
            f"table6/{name}/Offline", 0.0,
            compiled_qps=f"{off.throughput_qps:.0f}",
            unfused_ref_qps=f"{ref_qps:.0f}",
            compiled_speedup=f"{speedup:.1f}x",
            fused_stages=cm.schedule.n_fused,
            fused_conv=cm.schedule.n_fused_conv,
            argmax_parity=parity,
            beats_reference=speedup > 1.0))
        scenario_json["rows"].append(
            {"model": name, "single_stream": ss.row(), "offline": off.row(),
             "unfused_ref_qps": ref_qps, "compiled_speedup": speedup,
             "argmax_parity": parity})
        if off.stage_ms:
            top = sorted(off.stage_ms, key=lambda s: -s["ms"])[:3]
            print(f"stage_ms[{name}]: " + " ".join(
                f"{s['stage']}={s['ms']:.3f}ms" for s in top))

        # fused direct-conv vs im2col lowering, same graph, same pool
        if conv:
            cm_i2c = compile_graph(cm.graph,
                                   in_scale=cm.graph.meta["in_scale"],
                                   use_pallas=False, conv_lowering="im2col")
            xb_cmp = jnp.asarray(np.stack([mk(i) for i in range(n_off)]),
                                 jnp.int32)
            # one pass each: parity check doubles as the jit warm-up
            assert bool(jnp.all(cm.offline(xb_cmp) == cm_i2c.offline(xb_cmp)))
            qps_direct = _time_offline(cm.offline, xb_cmp)
            qps_i2c = _time_offline(cm_i2c.offline, xb_cmp)
            t_direct = cost.traffic_bytes
            t_i2c = schedule_cost(cm_i2c.schedule.stages).traffic_bytes
            rows.append(row(
                f"table6/{name}/Offline/conv_lowering", 0.0,
                fused_qps=f"{qps_direct:.0f}",
                im2col_qps=f"{qps_i2c:.0f}",
                fused_speedup=f"{qps_direct / max(qps_i2c, 1e-9):.2f}x",
                fused_traffic_B=f"{t_direct:.0f}",
                im2col_traffic_B=f"{t_i2c:.0f}",
                im2col_bytes_saved=f"{1 - t_direct / t_i2c:.0%}",
                beats_im2col=qps_direct > qps_i2c))
            scenario_json["rows"][-1]["conv_lowering"] = {
                "fused_qps": qps_direct, "im2col_qps": qps_i2c,
                "fused_traffic_bytes": t_direct,
                "im2col_traffic_bytes": t_i2c,
                "beats_im2col": bool(qps_direct > qps_i2c)}

    # -- streaming: default micro-batch, compiled segment waves vs the host
    #    queue loop, three-way bit-equality asserted --------------------------
    stream_rows = []
    for name, (cm, mk, _) in entries.items():
        conv = cm.schedule.n_fused_conv > 0
        n = (8 if conv else 16) if FAST else (16 if conv else 32)
        xb = jnp.asarray(np.stack([mk(i) for i in range(n)]), jnp.int32)
        y_off = cm.offline(xb)
        y_cmp, st_c = cm.streaming_compiled(xb)
        mb = st_c.micro_batch
        y_host, st_h = cm.streaming_host(xb, micro_batch=mb)
        assert bool(jnp.all(jnp.asarray(y_cmp) == jnp.asarray(y_off))), name
        assert bool(jnp.all(jnp.asarray(y_host) == jnp.asarray(y_off))), name
        t_cmp = time_call(
            lambda: cm.streaming_compiled(xb, micro_batch=mb)[0]) / 1e6
        t_host = time_call(
            lambda: cm.streaming_host(xb, micro_batch=mb)[0]) / 1e6
        speed = t_host / max(t_cmp, 1e-9)
        stream_rows.append(row(
            f"table6/{name}/Streaming", t_cmp * 1e6 / n,
            compiled_ms=f"{t_cmp * 1e3:.2f}",
            host_ms=f"{t_host * 1e3:.2f}",
            compiled_vs_host=f"{speed:.2f}x",
            micro_batch=mb,
            modeled_cycles=st_c.sim_cycles,
            fifo_depths=str(st_h.fifo_depths),
            segments=str(st_c.segments),
            bit_exact=True))
        print(f"streaming[{name}]: mb={mb} "
              f"fifo_depths={st_h.fifo_depths} "
              f"max_occupancy={st_h.max_occupancy} "
              f"sim_cycles={st_h.sim_cycles} "
              f"compiled_vs_host={speed:.2f}x matches_offline=True")
        scenario_json["streaming"].append({
            "model": name, "micro_batch": mb,
            "compiled_ms": t_cmp * 1e3, "host_ms": t_host * 1e3,
            "compiled_vs_host_speedup": speed,
            "modeled_cycles": st_c.sim_cycles,
            "fifo_depths": st_h.fifo_depths,
            "max_occupancy": st_h.max_occupancy,
            "segments": st_c.segments,
            "bit_exact_vs_offline": True})

        # ServerStreaming: Poisson traffic through the dynamic batcher at
        # ~0.7x the measured wave capacity, served from the same compiled
        # segment programs — bit-exactness asserted padding included
        # (serve_bench.py sweeps the full load curve; this is the smoke row)
        svc_s = measure_wave_service_s(cm, st_c.micro_batch, iters=3)
        sr = server_streaming(
            cm, mk, qps=0.7 * st_c.micro_batch / svc_s,
            n_queries=16 if FAST else 48,
            max_wait_ms=max(2.0, 1.5 * svc_s * 1e3))
        assert sr.extras["bit_exact_vs_offline"], name
        stream_rows.append(row(
            f"table6/{name}/ServerStreaming", sr.p99_ms * 1e3,
            p50_ms=f"{sr.p50_ms:.3f}", p99_ms=f"{sr.p99_ms:.3f}",
            qps=f"{sr.throughput_qps:.0f}",
            offered_qps=f"{sr.extras['offered_qps']:.0f}",
            micro_batch=sr.extras["micro_batch"],
            wave_occupancy=f"{sr.extras['wave_occupancy']:.2f}",
            bit_exact=sr.extras["bit_exact_vs_offline"]))
        scenario_json["streaming"][-1]["server_streaming"] = sr.row()
    rows += stream_rows
    print_rows(rows)

    # -- multi-tenant: all four models behind one queue --------------------
    server = TinyModelServer({n: e[0] for n, e in entries.items()},
                             max_batch=16)
    for i in range(32 if FAST else 96):
        name = list(entries)[i % len(entries)]
        server.submit(name, entries[name][1](i))
    server.run_until_drained()
    st = server.stats()
    agg = st.pop("_aggregate")
    tenants = " ".join(
        f"{n}:p99={v['p99_ms']:.1f}ms occ={v['wave_occupancy']:.2f}"
        for n, v in st.items())
    print(f"multitenant: {agg['n']} reqs {agg['throughput_qps']:.0f} qps "
          f"(compiled wave path)  {tenants}")
    scenario_json["multitenant"] = {"n": agg["n"],
                                    "throughput_qps": agg["throughput_qps"]}
    emit_json("BENCH_scenarios.json", scenario_json)
    return rows


if __name__ == "__main__":
    run()
