"""Chip smoke test: the compiled Table-1 serving path on a TPU, bit-exact.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four one-chip replicas (4-chip host)

With no option it builds KWS (490 -> 256 x 3 -> 12) at its published width
from seeded weights, compiles it on the Pallas kernel path, serves a few
hundred requests through ``Router`` + ``ReplicaPool`` on one device, runs
one full-width offline wave each of AD, IC and CNV, and one 256-row wave
of ResNet-8 through a ``ReplicaPool`` (the benchmark's seeded weights,
``chipbench/configs/resnet8.py``, whose codes stay spread). With
``--chips 4`` it runs only the replica path: KWS served by four one-chip
replicas behind the router, compared with one replica, and each replica's
outputs checked to live on its own device.

Every output is checked bit-exact against the same model compiled on the
host's CPU backend (XLA path, exact integer stages, not the TPU's matmul
precision). Any shed request, fault, mismatch or exception fails the run.
The script runs in one process, which holds the chip, and exits non-zero
without a result unless JAX's first device is a TPU. The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np


def _reference(graph, in_scale, x):
    """The model's outputs on the host CPU backend (XLA path)."""
    import jax

    from repro.deploy import compile_graph

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = compile_graph(graph, in_scale=in_scale, use_pallas=False)
        return np.asarray(ref.offline(jax.device_put(x, cpu)))


def _check_equal(label, got, want):
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or got.dtype != want.dtype:
        raise SystemExit(f"FAIL {label}: {got.shape} {got.dtype} vs "
                         f"{want.shape} {want.dtype}")
    bad = int(np.sum(got != want))
    if bad or not np.all(np.isfinite(got)):
        raise SystemExit(f"FAIL {label}: {bad}/{got.size} outputs differ "
                         "from the CPU reference")
    print(f"{label}: {got.shape[0]} outputs bit-exact")


def _queries(shape, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(-127, 128, (n,) + tuple(shape)).astype(np.int32)


def _compile(graph, in_scale, **kw):
    from repro.deploy import compile_graph
    from repro.obs import timer

    t0 = timer.now()
    cm = compile_graph(graph, in_scale=in_scale, **kw)
    if not cm.use_pallas:
        raise SystemExit("FAIL: compile_graph did not take the kernel path")
    return cm, timer.now() - t0


def serve(graph, in_scale, x, devices, seed, **kw):
    """Serve every row of ``x`` through ``Router`` + ``ReplicaPool`` with one
    replica per device; returns (outputs in request order, pool). Fails on
    any shed request or fault."""
    from repro.deploy import compile_graph
    from repro.obs import timer
    from repro.serve import (AsyncEngine, ReplicaPool, Router, RouterConfig,
                             ServiceModel, measure_wave_service_s,
                             poisson_trace)

    cm, _ = _compile(graph, in_scale, **kw)
    mb = cm.default_micro_batch
    wave_s = measure_wave_service_s(cm, mb)
    service = ServiceModel.from_compiled(cm, probe_batch=mb).recalibrated(
        wave_s, mb)
    pool = ReplicaPool(
        cm, factory=lambda: compile_graph(graph, in_scale=in_scale, **kw),
        devices=devices)
    for r in pool.replicas[1:]:         # compile every replica's programs
        r.model.submit_wave(x[:mb], micro_batch=mb)
    router = Router({"m": pool}, RouterConfig(micro_batch=mb),
                    service_models={"m": service}, engine=AsyncEngine())
    # an open-loop burst at twice the pool's modeled saturation rate: waves
    # queue, so least-work placement has to use every replica
    qps = 2.0 * len(devices) * service.saturation_qps(mb)
    trace = poisson_trace(qps=qps, n=len(x), seed=seed)
    t0 = timer.now()
    reqs = router.run_trace("m", trace, lambda i: x[i])
    dt = timer.now() - t0
    st = router.stats()["m"]
    snap = st["metrics"]
    print(f"served {len(reqs)} requests on {len(devices)} replica(s) in "
          f"{dt:.3f} s, wave={mb}, one-wave probe {wave_s * 1e3:.3f} ms, "
          f"wave p50 {snap.wave_service_p50_ms:.3f} ms")
    print("replicas:", json.dumps([{k: r[k] for k in ("replica", "device",
                                                      "dispatched",
                                                      "health")}
                                   for r in st["replicas"]]))
    shed = [r for r in reqs if r.shed]
    if shed or snap.fault_counts or snap.n_shed:
        raise SystemExit(f"FAIL: {len(shed)} shed, faults "
                         f"{snap.fault_counts}, reasons {snap.shed_reasons}")
    return np.stack([r.result for r in reqs]), pool


def one_chip(seed, n_requests, **kw):
    """KWS served on one device, then one offline wave of AD, IC, CNV."""
    import jax

    from repro.launch.serve import build_tiny_graph
    from repro.obs import timer

    graph, in_scale, shape = build_tiny_graph("kws", seed)
    x = _queries(shape, n_requests, seed)
    y, _ = serve(graph, in_scale, x, jax.devices()[:1], seed, **kw)
    _check_equal("kws served", y, _reference(graph, in_scale, x))
    for name in ("ad", "ic", "cnv"):
        graph, in_scale, shape = build_tiny_graph(name, seed)
        x = _queries(shape, 32, seed + 1)
        cm, t_build = _compile(graph, in_scale, **kw)
        t0 = timer.now()
        y = np.asarray(cm.offline(x))
        t1 = timer.now()
        y2 = np.asarray(cm.offline(x))
        t2 = timer.now()
        print(f"{name}: lowered in {t_build:.2f} s, first wave (compile) "
              f"{t1 - t0:.2f} s, second wave {(t2 - t1) * 1e3:.3f} ms")
        _check_equal(f"{name} offline", y, _reference(graph, in_scale, x))
        _check_equal(f"{name} offline again", y2, y)
    resnet8_wave(seed, **kw)


def resnet8_wave(seed, rows=256, **kw):
    """One full wave of ResNet-8 at its published widths through a
    one-replica ``ReplicaPool``: three residual joins on the join kernel,
    their skip values live inside the wave's program."""
    from functools import partial

    import jax

    from repro.deploy import compile_graph
    from repro.serve import ReplicaPool

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from chipbench import spec

    cfg, mod = spec.config("resnet8"), spec.config_module("resnet8")
    key = jax.random.wrap_key_data(np.array([0, seed], np.uint32))
    params = jax.jit(partial(mod.make_params, cfg))(key)
    graph, in_scale = mod.build_graph(cfg, params)
    cm, t_build = _compile(graph, in_scale, **kw)
    if cm.schedule.n_join != 3 or cm.host_segments:
        raise SystemExit(f"FAIL resnet8: {cm.schedule.describe()}")
    pool = ReplicaPool(cm, devices=jax.devices()[:1])
    x = _queries(mod.input_shape(cfg), rows, seed + 2)
    y, mask = pool.replicas[0].submit(x, micro_batch=rows).wait()
    y = np.asarray(y)[np.asarray(mask)]
    print(f"resnet8: lowered in {t_build:.2f} s, "
          f"{cm.schedule.n_join} joins")
    _check_equal("resnet8 wave", y, _reference(graph, in_scale, x))
    if np.all(y == y[:1]):
        raise SystemExit("FAIL resnet8: every row has the same logits")


def four_chips(seed, n_requests, **kw):
    """KWS on four one-chip replicas vs one replica, same requests."""
    import jax

    from repro.launch.serve import build_tiny_graph

    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"FAIL: --chips 4 needs 4 devices, found "
                         f"{len(devices)}")
    graph, in_scale, shape = build_tiny_graph("kws", seed)
    x = _queries(shape, n_requests, seed)
    y1, _ = serve(graph, in_scale, x, devices[:1], seed, **kw)
    y4, pool = serve(graph, in_scale, x, devices, seed, **kw)
    _check_equal("kws 4 replicas vs 1", y4, y1)
    _check_equal("kws 4 replicas vs CPU", y4, _reference(graph, in_scale, x))
    idle = [r.index for r in pool.replicas if r.n_dispatched == 0]
    if idle:
        raise SystemExit(f"FAIL: replicas {idle} dispatched no wave")
    mb = pool.default_micro_batch
    for r in pool.replicas:
        y, _ = r.submit(x[:mb], micro_batch=mb).wait()
        if y.devices() != {r.device}:
            raise SystemExit(f"FAIL: replica {r.index} output on "
                             f"{y.devices()}, expected {r.device}")
    print("every replica's outputs live on its own device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # the CPU backend computes the reference next to the chip
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.compile_cache import enable_compile_cache

    print("compile cache:", enable_compile_cache())
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    if args.chips == 4:
        four_chips(args.seed, args.requests)
    else:
        one_chip(args.seed, args.requests)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
