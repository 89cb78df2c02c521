"""Serving launcher: continuous-batching engine with optional int8 deployment
quantization — the paper's streamlined-deployment path for the LM archs —
plus the tiny-model stack behind ``--stack tiny``: a compiled Table-1 model
(KWS, AD, IC or CNV; the Pallas kernel path on a TPU) served through the
``repro.serve`` router by one replica per device and a selectable dispatch
engine.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --reduced --requests 16 --quant-bits 8

    PYTHONPATH=src python -m repro.launch.serve --stack tiny \
        --tiny-model kws --replicas 2 --engine async --requests 256
"""

import argparse
import logging

import numpy as np

import jax

from repro.obs import timer as obs_timer

from repro.configs import ARCH_IDS, get_config
from repro.models.model import Model
from repro.serving.engine import Request, ServeEngine

logging.basicConfig(level=logging.INFO, format="%(message)s")
log = logging.getLogger("repro.launch.serve")


#: Table-1 families the tiny stack serves: name -> per-sample input shape
TINY_MODELS = {"kws": (490,), "ad": (128,), "ic": (32, 32, 3),
               "cnv": (32, 32, 3)}


def build_tiny_graph(name: str, seed: int = 0):
    """One Table-1 model at its published width, weights from ``seed``,
    exported to QIR. Returns ``(graph, in_scale, input_shape)``; requests
    are signed 8-bit input codes of ``input_shape``."""
    from repro.core.qir import export_qcnn, export_qmlp
    from repro.models.tiny import ADAutoencoder, CNVModel, ICModel, KWSMLP

    key = jax.random.PRNGKey(seed)
    if name in ("kws", "ad"):
        model = KWSMLP() if name == "kws" else ADAutoencoder()
        params = model.init(key)
        hidden_defs, _ = model.layers()
        in_scale = 1.0 / 127.0
        graph = export_qmlp(hidden_defs, params["hidden"], params["head"],
                            meta={"model": type(model).__name__},
                            freeze_scales=True, in_scale=in_scale)
    elif name in ("ic", "cnv"):
        model = ICModel() if name == "ic" else CNVModel()
        graph = export_qcnn(model, model.init(key))
        in_scale = graph.meta["in_scale"]
    else:
        raise ValueError(f"unknown tiny model {name!r}; "
                         f"expected one of {sorted(TINY_MODELS)}")
    return graph, in_scale, TINY_MODELS[name]


def _run_tiny(args):
    """Compile one Table-1 model (the Pallas kernel path on a TPU), give
    each of ``--replicas`` replicas its own executor on its own device, and
    drive a Poisson trace through the router under the chosen engine."""
    from repro.deploy import compile_graph
    from repro.deploy.autotune import autotune_model
    from repro.serve import (
        AsyncEngine,
        ReplicaPool,
        Router,
        RouterConfig,
        ServiceModel,
        SyncEngine,
        measure_wave_service_s,
        poisson_trace,
    )

    devices = jax.devices()
    if args.replicas > len(devices):
        raise SystemExit(f"--replicas {args.replicas}: only "
                         f"{len(devices)} devices")
    graph, in_scale, shape = build_tiny_graph(args.tiny_model)
    cm = compile_graph(graph, in_scale=in_scale)
    tuned = None
    if args.autotune != "off":
        tuned = autotune_model(cm, batch=32, mode=args.autotune)
        cm.apply_tuned(tuned)
    mb = cm.default_micro_batch
    if args.autotune == "model":
        # cold-start path: no wall-clock reads before the first request —
        # the learned predictor prices admission from wave 0 and the
        # router's EWMA corrects it online (docs/costmodel.md)
        from repro.costmodel import load_default
        from repro.serve import PredictedServiceModel

        service = PredictedServiceModel.from_predictor(load_default(), cm)
    else:
        service = ServiceModel.from_compiled(
            cm, probe_batch=mb).recalibrated(
                measure_wave_service_s(cm, mb), mb)
    engine = AsyncEngine() if args.engine == "async" else SyncEngine()

    # replica 0 reuses the executor built above on the default device;
    # every other replica compiles its own on its own device
    pool = ReplicaPool(
        cm, factory=lambda: compile_graph(graph, in_scale=in_scale,
                                          tuned=tuned),
        devices=devices[:args.replicas])
    router = Router({args.tiny_model: pool},
                    RouterConfig(micro_batch=mb),
                    service_models={args.tiny_model: service},
                    engine=engine)
    rng = np.random.default_rng(args.seed)
    qps = args.qps or 0.5 * args.replicas * service.saturation_qps(mb)
    trace = poisson_trace(qps=qps, n=args.requests, seed=args.seed)
    t0 = obs_timer.now()
    reqs = router.run_trace(
        args.tiny_model, trace,
        lambda i: rng.integers(-127, 128, shape).astype(np.int32))
    dt = obs_timer.now() - t0
    served = [r for r in reqs if not r.shed]
    lats_ms = np.asarray([r.latency_s for r in served]) * 1e3
    snap = router.stats()[args.tiny_model]["metrics"]
    log.info("tiny stack: %s x%d replicas on %s, %s engine, wave=%d",
             args.tiny_model, args.replicas, devices[0].device_kind,
             args.engine, mb)
    log.info("offered %.0f qps | served %d/%d in %.2fs (%.0f qps)",
             qps, len(served), len(reqs), dt, len(served) / max(dt, 1e-9))
    log.info("p50 %.2f ms | p99 %.2f ms | wave p50 %.2f ms | occupancy %.2f",
             float(np.percentile(lats_ms, 50)),
             float(np.percentile(lats_ms, 99)),
             snap.wave_service_p50_ms, snap.mean_occupancy)
    return {"served": len(served), "n": len(reqs),
            "p99_ms": float(np.percentile(lats_ms, 99)),
            "throughput_qps": len(served) / max(dt, 1e-9)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--stack", choices=("lm", "tiny"), default="lm",
                    help="lm: continuous-batching ServeEngine; tiny: "
                         "compiled Table-1 model through the serve router")
    ap.add_argument("--tiny-model", choices=sorted(TINY_MODELS),
                    default="kws")
    ap.add_argument("--autotune", choices=("off", "probe", "model"),
                    default="probe",
                    help="tiny stack tuning: probe = measured search, "
                         "model = probe-free learned cost model (cold-"
                         "start admission priced by the predictor), "
                         "off = compiled defaults")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--engine", choices=("sync", "async"), default="sync")
    ap.add_argument("--qps", type=float, default=0.0,
                    help="offered load; 0 = half the pool's saturation")
    ap.add_argument("--arch", choices=ARCH_IDS, default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--quant-bits", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.stack == "tiny":
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache()
        return _run_tiny(args)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode path")

    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.quant_bits < 16:
        params = model.quantize_params(params, bits=args.quant_bits)
        log.info("deployment quantization: int%d weights", args.quant_bits)

    eng = ServeEngine(model, params, n_slots=args.slots, max_len=args.max_len)
    rng = np.random.default_rng(args.seed)
    t0 = obs_timer.now()
    for i in range(args.requests):
        plen = int(rng.integers(3, 12))
        eng.submit(Request(
            uid=i, prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
            max_new_tokens=args.max_new))
    steps = eng.run_until_drained()
    dt = obs_timer.now() - t0

    s = eng.stats()
    log.info("drained %d requests in %d steps / %.2fs", s["n_requests"],
             steps, dt)
    log.info("TTFT %.1f ms | latency %.1f ms | %.1f tok/s",
             s["mean_ttft_s"] * 1e3, s["mean_latency_s"] * 1e3,
             s["throughput_tok_s"])
    return s


if __name__ == "__main__":
    main()
