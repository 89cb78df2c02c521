"""Deterministic-clock unit tests for the MLPerf-Tiny scenario runtime.

The scenario functions (``deploy.scenarios``) read wall time only through
the process-wide injectable obs timer (``repro.obs.timer``), so a fake
clock installed there makes every latency, percentile, and throughput
number exactly computable: the fake ``infer`` advances the clock by a
scripted service time, ``sleep`` advances it by the requested amount, and
the tests then reproduce the expected numbers with independent arithmetic
— percentile math, MultiStream step accounting, Offline per-query
amortization, the Server mode's Poisson arrival bookkeeping (latency =
queueing delay + service), and the ``stage_ms`` breakdown summing to the
end-to-end latency.
"""

import numpy as np
import pytest

from repro.deploy.scenarios import (
    _percentiles,
    multi_stream,
    offline,
    server_poisson,
    server_streaming,
    single_stream,
    streaming_pipeline,
)
from repro.obs import timer as obs_timer


class FakeClock:
    """now/sleep stand-in: time only moves when told to."""

    def __init__(self):
        self.t = 0.0

    def now(self) -> float:
        return self.t

    # historical alias kept so tests can read the clock either way
    perf_counter = now

    def sleep(self, s: float):
        assert s >= 0
        self.t += s

    def advance(self, s: float):
        self.t += s


@pytest.fixture()
def clock():
    ck = FakeClock()
    with obs_timer.fake(ck):
        yield ck


def _mk(i):
    return np.zeros((4,), np.int32)


def test_percentile_math_matches_numpy():
    lats_s = [0.001 * (i + 1) for i in range(10)]
    p = _percentiles(lats_s)
    a = np.asarray(lats_s) * 1e3
    assert p["p50"] == float(np.percentile(a, 50))
    assert p["p90"] == float(np.percentile(a, 90))
    assert p["p99"] == float(np.percentile(a, 99))


def test_single_stream_reports_exact_latencies(clock):
    service = [0.004, 0.002, 0.010, 0.001, 0.003, 0.005, 0.007, 0.006]
    calls = []

    def infer(x):
        # warmup calls (3) then the measured queries, in order
        s = 0.001 if len(calls) < 3 else service[len(calls) - 3]
        calls.append(s)
        clock.advance(s)
        return np.zeros((1, 2), np.float32)

    rep = single_stream(infer, _mk, n_queries=len(service), warmup=3)
    expect = np.asarray(service) * 1e3
    assert rep.n_queries == len(service)
    assert rep.p50_ms == pytest.approx(float(np.percentile(expect, 50)))
    assert rep.p90_ms == pytest.approx(float(np.percentile(expect, 90)))
    assert rep.p99_ms == pytest.approx(float(np.percentile(expect, 99)))
    # back-to-back: the span is exactly the sum of service times
    assert rep.throughput_qps == pytest.approx(
        len(service) / sum(service))


def test_multi_stream_applies_step_latency_to_every_stream(clock):
    step_s = 0.005
    seen = []

    def infer(xb):
        seen.append(xb.shape)
        clock.advance(step_s)
        return np.zeros((xb.shape[0], 2), np.float32)

    rep = multi_stream(infer, _mk, n_streams=4, n_queries=12, warmup=1)
    # 12 queries / 4 streams = 3 steps (+1 warmup), all batched by 4
    assert seen == [(4, 4)] * 4
    assert rep.n_queries == 12
    assert rep.p50_ms == pytest.approx(step_s * 1e3)
    assert rep.p99_ms == pytest.approx(step_s * 1e3)
    assert rep.throughput_qps == pytest.approx(12 / (3 * step_s))


def test_offline_amortizes_batch_latency_per_query(clock):
    span_s = 0.064

    def infer(xb):
        clock.advance(span_s)
        return np.zeros((xb.shape[0], 2), np.float32)

    rep = offline(infer, _mk, n_samples=32, warmup=2)
    assert rep.extras["batch"] == 32
    assert rep.p50_ms == pytest.approx(span_s / 32 * 1e3)
    assert rep.throughput_qps == pytest.approx(32 / span_s)


def test_server_poisson_latency_is_queueing_plus_service(clock):
    """Reproduce the Server scenario's bookkeeping exactly: FIFO single
    worker, deterministic service, Poisson arrivals regenerated from the
    same seed — reported latency must equal completion - arrival."""
    qps, n, seed, service = 250.0, 24, 3, 0.007

    def infer(x):
        clock.advance(service)
        return np.zeros((1, 2), np.float32)

    rep = server_poisson(infer, _mk, qps=qps, n_queries=n, seed=seed,
                         warmup=2)
    arrivals = np.cumsum(
        np.random.default_rng(seed).exponential(1.0 / qps, n))
    expect, done = [], 0.0
    for a in arrivals:
        start = max(a, done)          # queue behind the previous completion
        done = start + service
        expect.append(done - a)
    expect_ms = np.asarray(expect) * 1e3
    assert rep.n_queries == n
    assert rep.p50_ms == pytest.approx(float(np.percentile(expect_ms, 50)))
    assert rep.p99_ms == pytest.approx(float(np.percentile(expect_ms, 99)))
    # offered load (service/interarrival ~ 1.75) forces real queueing:
    # tail latency must exceed bare service time
    assert rep.p99_ms > service * 1e3
    assert rep.extras["offered_qps"] == qps
    assert rep.throughput_qps == pytest.approx(
        n / (done - arrivals[0]))


def test_server_poisson_reuses_warm_program_per_query(clock):
    """Satellite: the Poisson loop must pre-materialize every query and
    run exactly warmup + 1 discarded-warm + n_queries inferences — the
    compile/warm work happens before the clock starts, never per query."""
    calls = []

    def infer(x):
        calls.append(np.asarray(x).shape)
        clock.advance(0.002)
        return np.zeros((1, 2), np.float32)

    made = []
    def mk(i):
        made.append(i)
        return np.zeros((4,), np.int32)

    rep = server_poisson(infer, mk, qps=400.0, n_queries=6, seed=1,
                         warmup=2)
    assert len(calls) == 2 + 1 + 6          # warmup, discarded warm, timed
    assert all(s == (1, 4) for s in calls)  # pre-batched (1, d) queries
    assert made == list(range(6))           # pool built once, up front
    assert rep.n_queries == 6


def test_server_streaming_exact_accounting_under_fake_clock(clock):
    """ServerStreaming through the real router under the fake clock: a
    zero-service wave executor makes every latency pure batching wait,
    reproduced here by an independent simulation of the documented
    contract (pairs dispatch on fill, partial waves at the deadline)."""
    waves = []

    class FakeCompiled:
        default_micro_batch = 2

        def submit_wave(self, x, valid=None, micro_batch=None):
            mb = int(micro_batch or self.default_micro_batch)
            n = np.asarray(x).shape[0]
            waves.append(n)
            mask = np.concatenate([np.ones(n, bool), np.zeros(mb - n, bool)])
            return np.zeros((mb, 2), np.float32), mask

    qps, n, seed, wait_ms = 250.0, 9, 4, 6.0
    rep = server_streaming(FakeCompiled(), _mk, qps=qps, n_queries=n,
                           seed=seed, max_wait_ms=wait_ms, micro_batch=2,
                           warmup=1)
    # independent reference: same arrivals (same seed), same batching rules
    arrivals = np.cumsum(
        np.random.default_rng(seed).exponential(1.0 / qps, n))
    w = wait_ms / 1e3
    expect, exp_waves, pending = [], [], []
    for a in arrivals:
        while pending and pending[0] + w < a:      # deadline flush first
            t = pending[0] + w
            expect.extend(t - p for p in pending)
            exp_waves.append(len(pending))
            pending = []
        pending.append(a)
        if len(pending) == 2:                      # full wave on fill
            expect.extend(a - p for p in pending)
            exp_waves.append(2)
            pending = []
    if pending:                                    # tail: deadline flush
        t = pending[0] + w
        expect.extend(t - p for p in pending)
        exp_waves.append(len(pending))
    expect_ms = np.asarray(sorted(expect)) * 1e3
    assert waves[1:] == exp_waves                  # waves[0] is the warmup
    assert rep.scenario == "ServerStreaming"
    assert rep.n_queries == n and rep.extras["shed"] == 0
    assert rep.extras["micro_batch"] == 2
    assert rep.extras["n_waves"] == len(exp_waves)
    got = np.asarray(sorted(
        [rep.p50_ms, rep.p90_ms, rep.p99_ms]))
    want = np.asarray([float(np.percentile(expect_ms, q))
                       for q in (50, 90, 99)])
    np.testing.assert_allclose(np.sort(want), got, rtol=1e-9, atol=1e-12)


def test_server_streaming_sheds_into_extras(clock):
    """With a p99 budget and a scripted service model the report carries
    the shed accounting and the met-SLO flag."""
    from repro.serve import ServiceModel

    class SlowWave:
        default_micro_batch = 2

        def submit_wave(self, x, valid=None, micro_batch=None):
            mb = int(micro_batch or 2)
            n = np.asarray(x).shape[0]
            clock.advance(0.050)               # 50ms per wave
            mask = np.concatenate([np.ones(n, bool), np.zeros(mb - n, bool)])
            return np.zeros((mb, 2), np.float32), mask

    svc = ServiceModel(works=[("s", 0)], sec_per_cycle=0.050 / 9)
    rep = server_streaming(SlowWave(), _mk, qps=500.0, n_queries=40,
                           seed=0, max_wait_ms=2.0, micro_batch=2,
                           p99_budget_ms=120.0, service_model=svc,
                           warmup=0)
    assert rep.extras["shed"] > 0
    assert rep.extras["served"] + rep.extras["shed"] == 40
    assert rep.extras["shed_rate"] == pytest.approx(
        rep.extras["shed"] / 40)
    assert rep.extras["p99_budget_ms"] == 120.0
    assert rep.extras["met_slo"] == (rep.p99_ms <= 120.0)


def test_stage_ms_breakdown_sums_to_end_to_end(clock, monkeypatch):
    """``stage_latencies`` accounting: with scripted per-stage costs the
    breakdown must recover each stage cost exactly and sum to the
    end-to-end latency of the chained pipeline."""
    from repro.core.qir import export_qmlp
    from repro.deploy import compile_graph
    from repro.models.tiny import KWSMLP
    import jax

    model = KWSMLP(width=16)
    params = model.init(jax.random.PRNGKey(0))
    hidden_defs, _ = model.layers()
    graph = export_qmlp(hidden_defs, params["hidden"], params["head"])
    cm = compile_graph(graph, in_scale=1.0 / 127.0, use_pallas=False)

    # stage_latencies reads the obs timer, already faked by the fixture
    costs = [0.002 * (i + 1) for i in range(len(cm.schedule.stages))]

    def fake_fn(c):
        def fn(h):
            clock.advance(c)
            return h
        return fn

    monkeypatch.setattr(cm, "_stage_fns", [fake_fn(c) for c in costs])
    x = np.zeros((1, 490), np.int32)
    breakdown = cm.stage_latencies(x, iters=3)
    assert [b["stage"] for b in breakdown] == \
        [s.name for s in cm.schedule.stages]
    for b, c in zip(breakdown, costs):
        assert b["ms"] == pytest.approx(c * 1e3)
    # the breakdown is additive: sum == end-to-end pipeline latency
    t0 = clock.perf_counter()
    h = x
    for fn in cm._stage_fns:
        h = fn(h)
    e2e_ms = (clock.perf_counter() - t0) * 1e3
    assert sum(b["ms"] for b in breakdown) == pytest.approx(e2e_ms)


def test_offline_reports_median_span_over_iters(clock):
    """Satellite: offline(iters=) must report the MEDIAN of the timed
    spans, not a single (noisy) run."""
    spans = iter([0.010, 0.010,          # warmup (2)
                  0.090, 0.032, 0.001])  # timed: median = 0.032

    def infer(xb):
        clock.advance(next(spans))
        return np.zeros((xb.shape[0], 2), np.float32)

    rep = offline(infer, _mk, n_samples=32, warmup=2, iters=3)
    assert rep.extras["iters"] == 3
    assert rep.p50_ms == pytest.approx(0.032 / 32 * 1e3)
    assert rep.throughput_qps == pytest.approx(32 / 0.032)


def test_streaming_pipeline_scenario_uses_tuned_default(clock):
    """The streaming scenario uses the executor's default micro-batch
    (``micro_batch=None``) and reports the FIFO plan that scheduled the
    run."""
    calls = []

    class FakeStats:
        micro_batch = 8
        fifo_depths = [2, 2]
        segments = [(0, 2)]

    class FakeCompiled:
        def streaming_compiled(self, xb, micro_batch=None):
            calls.append(micro_batch)
            clock.advance(0.016)
            return np.zeros((xb.shape[0], 2), np.float32), FakeStats()

    rep = streaming_pipeline(FakeCompiled(), _mk, n_samples=16,
                             warmup=1, iters=3)
    assert rep.scenario == "StreamingOffline"
    assert calls == [None] * 4            # warmup + 3 timed, the default
    assert rep.extras["micro_batch"] == 8
    assert rep.extras["fifo_depths"] == "[2, 2]"
    assert rep.p50_ms == pytest.approx(0.016 / 16 * 1e3)
    assert rep.throughput_qps == pytest.approx(16 / 0.016)


def test_offline_report_attaches_stage_breakdown(clock, monkeypatch):
    """The Offline report's stage_ms rows come from the compiled model's
    probe and align 1:1 with its schedule."""

    class FakeCompiled:
        def stage_latencies(self, x, iters=2):
            return [{"stage": "s0", "kind": "K", "ms": 1.0},
                    {"stage": "s1", "kind": "K", "ms": 2.0}]

    def infer(xb):
        clock.advance(0.004)
        return np.zeros((xb.shape[0], 2), np.float32)

    rep = offline(infer, _mk, n_samples=8, warmup=1, compiled=FakeCompiled())
    assert [s["stage"] for s in rep.stage_ms] == ["s0", "s1"]
    assert "stage_ms" in rep.row()
    assert rep.row()["stage_ms"] == "s0:1.000|s1:2.000"
