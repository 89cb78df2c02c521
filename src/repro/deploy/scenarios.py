"""MLPerf-Tiny-style load scenarios over a compiled executor.

MLPerf Tiny (Banbury et al. 2021) measures every submission under fixed load
generators; the paper's Table 5 latency/energy numbers are its SingleStream
results. This module reproduces the four LoadGen modes against any object
with an ``offline(x) -> y`` callable (``deploy.executor`` compiled models):

  * SingleStream — one query at a time, batch 1; report latency percentiles
    (MLPerf scores the 90th percentile; we report p50/p90/p99).
  * MultiStream  — N concurrent streams issued as one batch per step.
  * Offline      — the whole query pool in one batch; max throughput.
  * Server       — Poisson arrivals at a target QPS into a single queue;
    latency includes queueing delay (the jitter the FIFO work absorbs).

Energy has no Joulescope here, so each report carries the paper-style proxy:
the roofline latency/energy model of ``core.codesign.deploy_report`` driven
by the model's BOPs/weight bits (``core.bops``), next to a measured proxy
``board_watts x measured_latency``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from repro.core.codesign import CHIP_WATTS, deploy_report
from repro.obs import timer as obs_timer
from repro.obs.tracer import NULL_TRACER


@dataclasses.dataclass
class ScenarioReport:
    scenario: str
    n_queries: int
    p50_ms: float
    p90_ms: float
    p99_ms: float
    throughput_qps: float
    energy_proxy_uJ: Optional[float] = None      # roofline (BOPs) model
    measured_energy_uJ: Optional[float] = None   # board watts x wall latency
    stage_ms: Optional[List[Dict]] = None        # per-stage latency breakdown
    extras: Dict = dataclasses.field(default_factory=dict)

    def row(self) -> Dict[str, object]:
        d = {
            "scenario": self.scenario,
            "n": self.n_queries,
            "p50_ms": round(self.p50_ms, 4),
            "p90_ms": round(self.p90_ms, 4),
            "p99_ms": round(self.p99_ms, 4),
            "qps": round(self.throughput_qps, 1),
        }
        if self.energy_proxy_uJ is not None:
            d["roofline_uJ"] = round(self.energy_proxy_uJ, 3)
        if self.measured_energy_uJ is not None:
            d["measured_uJ"] = round(self.measured_energy_uJ, 1)
        if self.stage_ms is not None:
            d["stage_ms"] = "|".join(
                f"{s['stage']}:{s['ms']:.3f}" for s in self.stage_ms)
        d.update(self.extras)
        return d


def _percentiles(lat_s: List[float]) -> Dict[str, float]:
    a = np.asarray(lat_s) * 1e3
    return {"p50": float(np.percentile(a, 50)),
            "p90": float(np.percentile(a, 90)),
            "p99": float(np.percentile(a, 99))}


def _finish(scenario, lats, n, span, model_cost=None, bits=8,
            stage_ms=None, **extras):
    p = _percentiles(lats)
    energy = None
    if model_cost is not None:
        energy = deploy_report(model_cost, batch=1, bits=bits)["energy_uJ"]
    return ScenarioReport(
        scenario=scenario, n_queries=n,
        p50_ms=p["p50"], p90_ms=p["p90"], p99_ms=p["p99"],
        throughput_qps=n / max(span, 1e-9),
        energy_proxy_uJ=energy,
        measured_energy_uJ=float(np.median(lats)) * CHIP_WATTS * 1e6,
        stage_ms=stage_ms,
        extras=extras)


def _stage_breakdown(compiled, x) -> Optional[List[Dict]]:
    """Per-stage latency probe on a representative batch, when the executor
    exposes one (``CompiledTinyModel.stage_latencies``); None otherwise.
    Uses the probe's own default sampling (median of 5 after a discarded
    warm iteration)."""
    probe = getattr(compiled, "stage_latencies", None)
    if probe is None:
        return None
    return probe(x)


def single_stream(infer: Callable, make_query: Callable[[int], np.ndarray],
                  n_queries: int = 64, warmup: int = 3,
                  model_cost=None, bits: int = 8,
                  compiled=None, tracer=None) -> ScenarioReport:
    """Batch-1 queries back to back; MLPerf scores p90 latency.

    ``make_query(i)`` returns ONE unbatched sample; the scenario adds the
    batch-1 axis (every scenario batches for itself). Pass the compiled
    executor as ``compiled`` to attach a per-stage latency breakdown.
    """
    tr = tracer if tracer is not None else NULL_TRACER
    for w in range(warmup):
        jax.block_until_ready(infer(np.asarray(make_query(w))[None]))
    lats = []
    t_start = obs_timer.now()
    for i in range(n_queries):
        x = np.asarray(make_query(i))[None]
        t0 = obs_timer.now()
        jax.block_until_ready(infer(x))
        lats.append(obs_timer.now() - t0)
    span = obs_timer.now() - t_start
    if tr.enabled:
        tr.add_span("scenario", t_start, t_start + span, cat="scenario",
                    args={"scenario": "SingleStream", "n": n_queries})
    stage_ms = (None if compiled is None
                else _stage_breakdown(compiled, np.asarray(make_query(0))[None]))
    return _finish("SingleStream", lats, n_queries, span, model_cost, bits,
                   stage_ms=stage_ms)


def multi_stream(infer: Callable, make_query: Callable[[int], np.ndarray],
                 n_streams: int = 8, n_queries: int = 64, warmup: int = 2,
                 model_cost=None, bits: int = 8,
                 tracer=None) -> ScenarioReport:
    """N concurrent streams per step: one batched inference serves all
    streams; a step's latency applies to every query in it."""
    tr = tracer if tracer is not None else NULL_TRACER
    steps = max(1, n_queries // n_streams)
    batch0 = np.stack([make_query(s) for s in range(n_streams)])
    for _ in range(warmup):
        jax.block_until_ready(infer(batch0))
    lats = []
    t_start = obs_timer.now()
    for i in range(steps):
        xb = np.stack([make_query(i * n_streams + s) for s in range(n_streams)])
        t0 = obs_timer.now()
        jax.block_until_ready(infer(xb))
        lats.extend([obs_timer.now() - t0] * n_streams)
    span = obs_timer.now() - t_start
    if tr.enabled:
        tr.add_span("scenario", t_start, t_start + span, cat="scenario",
                    args={"scenario": "MultiStream",
                          "n": steps * n_streams, "streams": n_streams})
    return _finish("MultiStream", lats, steps * n_streams, span,
                   model_cost, bits, streams=n_streams)


def offline(infer: Callable, make_query: Callable[[int], np.ndarray],
            n_samples: int = 256, warmup: int = 2, iters: int = 3,
            model_cost=None, bits: int = 8, compiled=None,
            tracer=None) -> ScenarioReport:
    """Whole pool in one batch; the throughput scenario.

    Times ``iters`` post-warmup runs and reports the *median* span — a
    single run's wall clock flaps on CPU noise, which is what used to flip
    marginal speedup flags (``beats_im2col``) between benchmark runs.
    """
    tr = tracer if tracer is not None else NULL_TRACER
    xb = np.stack([make_query(i) for i in range(n_samples)])
    for _ in range(warmup):
        jax.block_until_ready(infer(xb))
    spans = []
    for it in range(max(iters, 1)):
        t0 = obs_timer.now()
        jax.block_until_ready(infer(xb))
        t1 = obs_timer.now()
        if tr.enabled:
            tr.add_span("scenario", t0, t1, cat="scenario",
                        args={"scenario": "Offline", "n": n_samples,
                              "iter": it})
        spans.append(t1 - t0)
    spans.sort()
    span = spans[len(spans) // 2]
    per_query = span / n_samples
    stage_ms = None if compiled is None else _stage_breakdown(compiled, xb)
    return _finish("Offline", [per_query] * n_samples, n_samples, span,
                   model_cost, bits, stage_ms=stage_ms, batch=n_samples,
                   iters=max(iters, 1))


def streaming_pipeline(compiled, make_query: Callable[[int], np.ndarray],
                       n_samples: int = 256, micro_batch: Optional[int] = None,
                       warmup: int = 1, iters: int = 3,
                       model_cost=None, bits: int = 8,
                       tracer=None) -> ScenarioReport:
    """The Offline pool through the compiled streaming pipeline.

    Runs ``compiled.streaming_compiled`` (one jit program per segment wave)
    over the whole pool; ``micro_batch=None`` consumes the executor's
    default wave size.
    Reports the median span of ``iters`` runs like ``offline``, plus the
    FIFO plan that scheduled it.
    """
    tr = tracer if tracer is not None else NULL_TRACER
    xb = np.stack([make_query(i) for i in range(n_samples)])
    for _ in range(max(warmup, 1)):
        y, _ = compiled.streaming_compiled(xb, micro_batch=micro_batch)
        jax.block_until_ready(y)
    spans = []
    stats = None
    for it in range(max(iters, 1)):
        t0 = obs_timer.now()
        y, stats = compiled.streaming_compiled(xb, micro_batch=micro_batch)
        jax.block_until_ready(y)
        t1 = obs_timer.now()
        if tr.enabled:
            tr.add_span("scenario", t0, t1, cat="scenario",
                        args={"scenario": "StreamingOffline",
                              "n": n_samples, "iter": it})
        spans.append(t1 - t0)
    spans.sort()
    span = spans[len(spans) // 2]
    return _finish("StreamingOffline", [span / n_samples] * n_samples,
                   n_samples, span, model_cost, bits,
                   micro_batch=stats.micro_batch,
                   fifo_depths=str(stats.fifo_depths),
                   segments=str(stats.segments), batch=n_samples)


def server_poisson(infer: Callable, make_query: Callable[[int], np.ndarray],
                   qps: float = 200.0, n_queries: int = 128, seed: int = 0,
                   warmup: int = 3, model_cost=None, bits: int = 8,
                   tracer=None) -> ScenarioReport:
    """Poisson arrivals into a single-worker queue.

    Arrival times are drawn up front; the worker serves FIFO, so reported
    latency = queueing delay + service time. This is MLPerf's Server mode
    shrunk to one process: it answers "at what offered load do tails blow
    up", which is the question the paper's FIFO sizing answers on-chip.

    The whole query pool is materialized (and batched) before the clock
    starts, and the warmup ends with a discarded warm iteration on a real
    pool query (the ``stage_latencies`` convention) — so the compiled
    program is reused, warm, across the Poisson loop and no per-query
    host-side array construction or compile ever lands inside a measured
    latency.
    """
    tr = tracer if tracer is not None else NULL_TRACER
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / qps, n_queries))
    queries = [np.asarray(make_query(i))[None] for i in range(n_queries)]
    for w in range(max(warmup, 1)):
        jax.block_until_ready(infer(queries[w % n_queries]))
    jax.block_until_ready(infer(queries[0]))   # discarded warm iteration
    lats = []
    t_start = obs_timer.now()
    free_at = 0.0
    for i in range(n_queries):
        now = obs_timer.now() - t_start
        if now < arrivals[i]:
            obs_timer.sleep(arrivals[i] - now)
        jax.block_until_ready(infer(queries[i]))
        done = obs_timer.now() - t_start
        lats.append(done - arrivals[i])
        free_at = done
    span = free_at - arrivals[0]
    if tr.enabled:
        tr.add_span("scenario", t_start, t_start + free_at, cat="scenario",
                    args={"scenario": "Server", "n": n_queries,
                          "offered_qps": qps})
    return _finish("Server", lats, n_queries, span, model_cost, bits,
                   offered_qps=qps)


def server_streaming(compiled, make_query: Callable[[int], np.ndarray],
                     qps: float = 200.0, n_queries: int = 128, seed: int = 0,
                     max_wait_ms: float = 2.0,
                     p99_budget_ms: Optional[float] = None,
                     micro_batch: Optional[int] = None,
                     service_model=None, warmup: int = 1,
                     model_cost=None, bits: int = 8,
                     tracer=None, engine=None) -> ScenarioReport:
    """MLPerf Server mode over the dynamic-batching serve router.

    Where ``server_poisson`` serves each arrival alone (batch 1, one
    worker), this scenario drives the ``repro.serve`` router: Poisson
    arrivals are coalesced into padded micro-batch waves (the executor's
    default wave size, ``max_wait_ms`` deadline) and dispatched through
    the executor's compiled segment programs — the PR-4 streaming pipeline
    finally fed by request traffic rather than a pre-batched pool. With a
    ``p99_budget_ms`` the SLO controller sheds load it estimates would
    blow the budget; shed requests count into ``shed_rate`` but not into
    the latency percentiles (MLPerf Server accounting: an over-SLO result
    is invalid either way, an explicit shed is at least cheap).

    When the executor exposes ``offline``, every served result is checked
    bit-exact against it (``extras["bit_exact_vs_offline"]``) — padded
    partial waves included, which is the wave-padding contract under real
    traffic.
    """
    from repro.serve import Router, RouterConfig, poisson_trace

    class _Clock:
        """Adapter reading through the injectable obs timer
        (``repro.obs.timer``) so the deterministic-clock tests control
        the router too."""

        def now(self) -> float:
            return obs_timer.now()

        def sleep(self, seconds: float) -> None:
            if seconds > 0:
                obs_timer.sleep(seconds)

    queries = [np.asarray(make_query(i)) for i in range(n_queries)]
    submit = getattr(compiled, "submit_wave", None)
    for w in range(max(warmup, 0)):
        if submit is None:
            break
        y, _ = submit(queries[w % n_queries][None],
                      micro_batch=micro_batch)
        jax.block_until_ready(y)               # compile the wave program
    cfg = RouterConfig(max_wait_ms=max_wait_ms, micro_batch=micro_batch,
                       p99_budget_ms=p99_budget_ms)
    router = Router({"m": compiled}, cfg, clock=_Clock(),
                    service_models=(None if service_model is None
                                    else {"m": service_model}),
                    tracer=tracer, engine=engine)
    trace = poisson_trace(qps=qps, n=n_queries, seed=seed)
    reqs = router.run_trace("m", trace, lambda i: queries[i])
    served = [r for r in reqs if not r.shed]
    shed = len(reqs) - len(served)
    lats = [r.latency_s for r in served] or [0.0]
    span = (max(r.done_t for r in served) - min(r.arrival_t for r in served)
            if served else 1e-9)
    snap = router.stats()["m"]["metrics"]
    exact = None
    if served and hasattr(compiled, "offline"):
        xb = np.stack([r.x for r in served])
        y_ref = np.asarray(compiled.offline(xb))
        got = np.stack([np.asarray(r.result) for r in served])
        exact = bool(np.array_equal(got, y_ref)) if np.issubdtype(
            y_ref.dtype, np.integer) else bool(
            np.allclose(got, y_ref, rtol=1e-6, atol=1e-6))
    extras = dict(offered_qps=qps, served=len(served), shed=shed,
                  shed_rate=shed / max(len(reqs), 1),
                  micro_batch=router.lanes["m"].micro_batch,
                  wave_occupancy=snap.mean_occupancy,
                  n_waves=snap.n_waves)
    if p99_budget_ms is not None:
        extras["p99_budget_ms"] = p99_budget_ms
        extras["met_slo"] = bool(served) and bool(np.percentile(
            np.asarray(lats) * 1e3, 99) <= p99_budget_ms)
    if exact is not None:
        extras["bit_exact_vs_offline"] = exact
    return _finish("ServerStreaming", lats, len(served), span,
                   model_cost, bits, **extras)


def run_all_scenarios(infer: Callable, make_query: Callable[[int], np.ndarray],
                      n_queries: int = 64, n_streams: int = 8,
                      offline_samples: int = 256, server_qps: float = 200.0,
                      model_cost=None, bits: int = 8, compiled=None,
                      tracer=None) -> List[ScenarioReport]:
    """The full MLPerf-Tiny sweep for one deployed model.

    When ``compiled`` exposes a streaming executor
    (``CompiledTinyModel.streaming_compiled``), the sweep also measures the
    Offline pool through the compiled streaming pipeline at its default
    micro-batch; when it exposes the wave-submission API
    (``submit_wave``), the Server load is additionally replayed through
    the dynamic-batching router (``ServerStreaming``).
    """
    reports = [
        single_stream(infer, make_query, n_queries=n_queries,
                      model_cost=model_cost, bits=bits, compiled=compiled,
                      tracer=tracer),
        multi_stream(infer, make_query, n_streams=n_streams,
                     n_queries=n_queries, model_cost=model_cost, bits=bits,
                     tracer=tracer),
        offline(infer, make_query, n_samples=offline_samples,
                model_cost=model_cost, bits=bits, compiled=compiled,
                tracer=tracer),
        server_poisson(infer, make_query, qps=server_qps,
                       n_queries=n_queries, model_cost=model_cost, bits=bits,
                       tracer=tracer),
    ]
    if compiled is not None and hasattr(compiled, "streaming_compiled"):
        reports.append(streaming_pipeline(
            compiled, make_query, n_samples=offline_samples,
            model_cost=model_cost, bits=bits, tracer=tracer))
    if compiled is not None and hasattr(compiled, "submit_wave"):
        reports.append(server_streaming(
            compiled, make_query, qps=server_qps, n_queries=n_queries,
            model_cost=model_cost, bits=bits, tracer=tracer))
    return reports
