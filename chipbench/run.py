"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 -m chipbench.run --workload kws-singlestream --seed 7 \
        --seconds 10 --trace 0

One process holds the chips. Set-up (counted in ``setup_s``, from the start
of this module to the start of the window): the weights from the seed, the
program's export and compile of them on the Pallas kernel path (JAX's
persistent compile cache at ``<checkout>/.jax_cache`` serves every run
after the first), one replica per chip, and a warm-up of the cell's own
traffic so every program the window drives is compiled. The window then
drives ``Router.submit`` through the router's own event loop for
``--seconds``. Afterwards the program's state is freed and a sample of the
served answers, drawn from the seed, is compared with the configuration's
plain reference.

``--trace 1`` follows the window with two more of the same length, one
with the router's span recorder on and one under the profiler, and
reports the cell's per-layer metrics; ``--trace 0`` reports its
end-to-end metrics. The last line of
standard output is the result, one JSON object. Without a TPU, or with
fewer chips than the cell asks for, the run exits with code 2 and prints
no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from functools import partial  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

from chipbench import check, spec, traffic as traffic_mod  # noqa: E402

LANE = "m"
#: seconds of the cell's own traffic in the warm-up
WARM_S = 0.5
#: router events kept for the per-layer spans of a traced window
TRACER_CAPACITY = 1 << 22


def use_checkout_cache() -> str:
    """Point JAX's persistent compile cache at ``<checkout>/.jax_cache``, a
    fixed path inside the checkout, whatever the environment held. Call
    before JAX is imported: JAX reads the variable then, and the program's
    ``enable_compile_cache`` takes the directory it names."""
    path = os.path.join(spec.ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int) -> List:
    """The first ``n`` TPU devices; raises ``NoChip`` otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devices[0].platform}, "
                     "not a TPU")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def seed_key(seed: int):
    """A PRNG key holding all 64 bits of ``seed``."""
    import jax

    s = int(seed) & (2 ** 64 - 1)
    return jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def annotate(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


# -- the system under test -------------------------------------------------

def build_pool(graph, in_scale, devices):
    """One replica of the compiled program per device, on the Pallas kernel
    path."""
    import jax
    from repro.deploy import compile_graph
    from repro.serve import ReplicaPool

    with jax.default_device(devices[0]):
        cm = compile_graph(graph, in_scale=in_scale)
    if not cm.use_pallas:
        raise RuntimeError("compile_graph did not take the kernel path")
    return ReplicaPool(cm, factory=lambda: compile_graph(
        graph, in_scale=in_scale), devices=list(devices))


def make_router(pool, serving: Dict, tracer=None):
    """The program's router with the mix's engine and wave over ``pool``."""
    from repro.serve import AsyncEngine, Router, RouterConfig, SyncEngine

    engine = (SyncEngine() if serving["engine"] == "sync"
              else AsyncEngine(max_inflight=int(serving["max_inflight"])))
    cfg = RouterConfig(micro_batch=int(serving["wave"]),
                       max_wait_ms=float(serving["max_wait_ms"]))
    return Router({LANE: pool}, cfg, engine=engine, tracer=tracer)


# -- the window's loops ----------------------------------------------------

def closed_loop(router, rows, query: int, seconds: float, ann: bool):
    """Queries of ``query`` requests, each sent when the last has returned,
    until ``seconds`` have passed; the window closes when the query in
    flight at that moment has returned. Returns (requests, window_s)."""
    clock = router.clock
    n = len(rows)
    out = []
    t0 = clock.now()
    i = 0
    while clock.now() - t0 < seconds:
        with annotate(ann, "bench.submit"):
            batch = [router.submit(LANE, rows[(i + k) % n])
                     for k in range(query)]
        i += query
        while not all(r.result is not None or r.shed for r in batch):
            with annotate(ann, "bench.step"):
                router.step()
            with annotate(ann, "bench.reap"):
                router.reap(block=True)
            # nothing is in flight now; a partial wave leaves at its
            # deadline
            dl = router.next_deadline()
            now = clock.now()
            if dl is not None and dl > now:
                with annotate(ann, "bench.wait_deadline"):
                    clock.sleep(dl - now)
        out.extend(batch)
    return out, clock.now() - t0


def open_loop(router, rows, arrivals, seconds: float, ann: bool):
    """Requests at ``arrivals`` through ``Router.run_trace``, which times
    each from when it was due; the window runs until the last has
    returned. Returns (requests, window_s)."""
    from repro.serve.traffic import Trace

    n = len(rows)
    clock = router.clock
    t0 = clock.now()
    with annotate(ann, "bench.run_trace"):
        reqs = router.run_trace(LANE, Trace(kind="bench", arrivals=arrivals),
                                lambda i: rows[i % n])
    return reqs, max(clock.now() - t0, seconds)


def drive(router, tm: Dict, rows, seconds: float, seed: int, ann: bool):
    if tm["loop"] == "closed":
        return closed_loop(router, rows, int(tm["query_samples"]), seconds,
                           ann)
    return open_loop(router, rows, traffic_mod.arrivals(tm, seconds, seed),
                     seconds, ann)


def warm_up(pool, tm: Dict, rows, seed: int):
    """Compile every program the window drives: one full wave per replica
    (padding is on the host, so every fill level shares the one wave
    shape), then ``WARM_S`` of the cell's own traffic through a router that
    is thrown away."""
    import jax

    wave = int(tm["serving"]["wave"])
    for r in pool.replicas:
        y, _ = r.model.submit_wave(rows[:wave], micro_batch=wave)
        jax.block_until_ready(y)
    drive(make_router(pool, tm["serving"]), tm, rows, WARM_S, seed + 1,
          ann=False)


# -- one run ---------------------------------------------------------------

def prepare(bench: Dict, workload: str, seed: int, devices):
    """Set-up: the cell's files, the weights from the seed, the program's
    export and compile of them on every replica's chip, the inputs, and the
    warm-up. Returns the run's state."""
    import jax

    cell = spec.workload(bench, workload)
    cfg = spec.config(cell["config"])
    mod = spec.config_module(cell["config"])
    tm = traffic_mod.validate(spec.traffic(cell["traffic"]))
    used = list(devices[:int(tm["serving"]["replicas"])])

    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    with jax.default_device(used[0]):
        params = jax.jit(partial(mod.make_params, cfg))(seed_key(seed))
        params = jax.block_until_ready(params)
    graph, in_scale = mod.build_graph(cfg, params)
    rows = traffic_mod.input_rows(tm, mod.input_shape(cfg),
                                  int(cfg["input_bits"]), seed)
    pool = build_pool(graph, in_scale, used)
    warm_up(pool, tm, rows, seed)
    return types.SimpleNamespace(
        workload=workload, seed=seed, cell=cell, cfg=cfg, mod=mod, tm=tm,
        limits=spec.limits(workload), used=used, rows=rows,
        params=jax.tree.map(np.asarray, params), pool=pool)


def window(state, seconds: float, trace: bool, t_start: Optional[float]):
    """The measured window over a fresh router on the built replicas.
    ``t_start`` (the process start) sets ``setup_s``. With ``trace`` two
    windows of the same length follow, one with the router's span recorder
    on (``span_window``) and one under the profiler (``profiled_window``):
    each slows the host path (cnv-offline's rate by a fifth and by three
    quarters), so the metrics of the host clock read the measured window,
    the span metrics the second and the device metrics the third. Returns
    what the metrics read."""
    router = make_router(state.pool, state.tm["serving"])
    setup_s = None if t_start is None else time.monotonic() - t_start
    reqs, window_s = drive(router, state.tm, state.rows, seconds,
                           state.seed, False)
    served = [r for r in reqs if r.result is not None and not r.shed]
    w = types.SimpleNamespace(
        cfg=state.cfg, layers=state.mod.layers(state.cfg), cell=state.cell,
        traffic=state.tm, setup_s=setup_s, window_s=window_s,
        latencies_ms=np.asarray([r.latency_s * 1e3 for r in served]),
        samples=len(served), chips=len(state.used),
        spans=[], device=None, peaks=None,
        results={r.uid: np.asarray(r.result, np.float64) for r in served},
        index={r.uid: i for i, r in enumerate(reqs)})
    if trace:
        from chipbench import peaks

        w.spans = span_window(state, seconds)
        w.device = profiled_window(state, seconds)
        w.peaks = peaks.peaks(state.used[0].device_kind)
    w.memory_peak_bytes = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in state.used)
    return w


def span_window(state, seconds: float) -> List:
    """A window of the cell's traffic with the router's span recorder on;
    returns its spans."""
    from repro.obs import Tracer

    tracer = Tracer(capacity=TRACER_CAPACITY)
    drive(make_router(state.pool, state.tm["serving"], tracer), state.tm,
          state.rows, seconds, state.seed, False)
    return tracer.spans()


def profiled_window(state, seconds: float) -> Dict:
    """A window of the cell's traffic under the JAX profiler (Python tracer
    off, host tracer at level 1 for the harness's annotations), reduced to
    device metrics by ``trace.reduce``. Adds the window's served samples
    and its rate (``samples``, ``samples_per_s``)."""
    import jax

    from chipbench import trace as trace_mod

    router = make_router(state.pool, state.tm["serving"])
    prof_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(prof_dir, profiler_options=opts)
    with annotate(True, "bench.window"):
        reqs, window_s = drive(router, state.tm, state.rows, seconds,
                               state.seed, True)
    jax.profiler.stop_trace()
    try:
        chips, host = trace_mod.load(trace_mod.find_xplane(prof_dir))
    finally:
        shutil.rmtree(prof_dir, ignore_errors=True)
    device = trace_mod.reduce(chips, host)
    device["samples"] = sum(1 for r in reqs
                            if r.result is not None and not r.shed)
    device["samples_per_s"] = device["samples"] / window_s
    return device


def judge(state, w, served_override=None) -> Dict:
    """The correctness numbers of one window beside their limits."""
    return check.compare(state.mod, state.cfg, state.params, state.rows,
                         w.results, w.index, len(state.rows), state.limits,
                         traffic_mod.rng(state.seed, traffic_mod.CHECK),
                         int(state.tm["check_rows"]),
                         served_override=served_override)


def run_cell(bench: Dict, workload: str, seed: int, seconds: float,
             trace: bool, devices, t_start: float = T_START) -> Dict:
    """One run of one cell; returns the result object."""
    state = prepare(bench, workload, seed, devices)
    w = window(state, seconds, trace, t_start)
    state.pool = None            # the program's state goes before the
    gc.collect()                 # reference runs
    numbers = judge(state, w)
    metrics = {}
    for m in spec.metrics_for(bench, workload, trace):
        value = spec.reducer(m["name"]).reduce(w)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    d0 = state.used[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(state.used),
              "memory_peak_bytes": w.memory_peak_bytes}
    out = {"correct": check.passed(numbers), "attempted": len(w.index),
           "failed": len(w.index) - len(w.results), "metrics": metrics,
           "device": device}
    if w.device is not None:
        device["busy_s"] = w.device["busy_s"]
        device["window_s"] = w.device["window_s"]
        out["breakdown"] = w.device["breakdown"]
        # the profiled window's rate over the measured window's: how far
        # the profiler slowed the host path under the device metrics
        out["profiled_rate_share"] = (w.device["samples_per_s"]
                                      * w.window_s / max(w.samples, 1))
    out["checks"] = numbers
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    use_checkout_cache()
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    try:
        devices = require_chips(int(cell["chips"]))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
