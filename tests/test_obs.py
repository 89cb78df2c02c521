"""repro.obs tests: tracer semantics, deterministic export, and the
cross-checks that keep instrumentation honest.

The two load-bearing properties here are the ISSUE's acceptance criteria:

  * **byte-identical export** — two serve runs under the same
    ``ManualClock`` schedule must produce the same Chrome-trace bytes
    (trace diffs are only reviewable if identical runs serialize
    identically);
  * **bit-exact agreement** — p50/p90/p99 recomputed from request spans
    must equal the ``ServeMetrics`` snapshot with ``==``, not approx: the
    trace and the metrics window observe the same completions through
    different code paths, and any drift means one of them is lying.

Plus the ServeMetrics edge cases the tentpole work fixed (sheds-only cold
start opening the throughput window, the inclusive prune boundary) and
the timer/lint satellites.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.obs import (
    NULL_TRACER,
    Tracer,
    chrome_events,
    chrome_json,
    export_chrome,
    export_jsonl,
    jsonl_lines,
    latency_percentiles,
    prediction_error,
    prediction_records,
    request_latencies_ms,
    stage_medians_ms,
)
from repro.obs import timer as obs_timer
from repro.serve import (
    ManualClock,
    Router,
    RouterConfig,
    ServeMetrics,
    ServiceModel,
    poisson_trace,
)


class FakeClock:
    """now/sleep stand-in for the process-wide obs timer."""

    def __init__(self):
        self.t = 0.0

    def now(self) -> float:
        return self.t

    def sleep(self, s: float):
        assert s >= 0
        self.t += s

    def advance(self, s: float):
        self.t += s


@pytest.fixture()
def clock():
    with obs_timer.fake(FakeClock()) as ck:
        yield ck


class ScriptedModel:
    """submit_wave fake with the executor's padding contract (the
    test_serve idiom): each wave advances the manual clock by a fixed
    service time."""

    def __init__(self, clock, service_s=0.003, micro_batch=4):
        self.clock = clock
        self.service_s = service_s
        self.default_micro_batch = micro_batch

    def submit_wave(self, x, valid=None, micro_batch=None):
        mb = int(micro_batch or self.default_micro_batch)
        x = np.asarray(x)
        n = x.shape[0]
        mask = np.concatenate([np.ones(n, bool), np.zeros(mb - n, bool)])
        self.clock.advance(self.service_s)
        y = np.zeros((mb, 1), np.float32)
        y[:n, 0] = x.reshape(n, -1).sum(axis=1)
        return y, mask


def _mk(i):
    return np.full((4,), i, np.int32)


def _serve_run(n=32):
    """One deterministic traced serve run: fresh ManualClock, fresh
    tracer, same arrival trace — the unit the determinism tests repeat."""
    ck = ManualClock()
    tr = Tracer(clock=ck)
    model = ScriptedModel(ck, service_s=0.003, micro_batch=4)
    svc = ServiceModel(works=[("s", 64)], sec_per_cycle=1e-6)
    router = Router({"m": model}, RouterConfig(max_wait_ms=2.0),
                    clock=ck, service_models={"m": svc}, tracer=tr)
    router.run_trace("m", poisson_trace(qps=400.0, n=n, seed=3), _mk)
    return ck, tr, router


# ---------------------------------------------------------------------------
# tracer semantics
# ---------------------------------------------------------------------------

def test_span_context_manager_records_clock_interval():
    ck = ManualClock(start=5.0)
    tr = Tracer(clock=ck)
    with tr.span("work", cat="c", pid=2, tid=3) as sp:
        ck.advance(0.5)
        sp.set(k=1)
    (ev,) = tr.spans(name="work")
    assert (ev.t0, ev.t1) == (5.0, 5.5)
    assert ev.dur == 0.5
    assert (ev.pid, ev.tid, ev.cat) == (2, 3, "c")
    assert ev.args == {"k": 1}


def test_instant_counter_and_filters():
    tr = Tracer(clock=ManualClock())
    tr.instant("shed", t=1.0, cat="router", uid=7)
    tr.counter("backlog", 3, t=1.5, cat="router")
    tr.add_span("wave", 1.0, 2.0, cat="exec")
    assert len(tr) == 3
    (inst,) = tr.events(kind="instant")
    assert inst.t0 == inst.t1 == 1.0 and inst.args == {"uid": 7}
    (ctr,) = tr.counters(name="backlog")
    assert ctr.value == 3.0
    assert tr.spans(cat="exec")[0].name == "wave"
    assert tr.events(cat="router", kind="counter") == [ctr]


def test_ring_capacity_drops_oldest_and_counts():
    tr = Tracer(clock=ManualClock(), capacity=4)
    for i in range(6):
        tr.instant(f"i{i}", t=float(i))
    assert len(tr) == 4
    assert tr.n_dropped == 2
    evs = tr.events()
    assert [e.name for e in evs] == ["i2", "i3", "i4", "i5"]
    assert [e.seq for e in evs] == [2, 3, 4, 5]


def test_capacity_must_be_positive():
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_clear_resets_ring_seq_and_drop_count():
    tr = Tracer(clock=ManualClock(), capacity=2)
    for i in range(5):
        tr.instant("x", t=float(i))
    tr.clear()
    assert len(tr) == 0 and tr.n_dropped == 0
    tr.instant("y", t=0.0)
    assert tr.events()[0].seq == 0


def test_concurrent_appends_keep_every_event_and_unique_seq():
    tr = Tracer(clock=ManualClock())
    n_threads, per = 8, 500

    def worker(k):
        for i in range(per):
            tr.instant("e", t=0.0, pid=k)

    ts = [threading.Thread(target=worker, args=(k,))
          for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    evs = tr.events()
    assert len(evs) == n_threads * per
    assert len({e.seq for e in evs}) == n_threads * per


def test_null_tracer_is_inert_and_shared():
    assert NULL_TRACER.enabled is False
    with NULL_TRACER.span("x") as sp:
        sp.set(a=1)
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b")
    NULL_TRACER.add_span("x", 0.0, 1.0)
    NULL_TRACER.instant("x")
    NULL_TRACER.counter("x", 1.0)
    assert NULL_TRACER.events() == [] and len(NULL_TRACER) == 0
    assert NULL_TRACER.now() == 0.0


def test_router_keeps_an_empty_tracer_instance():
    """Regression: ``Tracer`` defines ``__len__``, which historically made
    an EMPTY tracer falsy, so ``tracer or NULL_TRACER`` silently degraded
    a fresh tracer to the NullTracer before its first event. Fixed by an
    explicit ``__bool__``; injection points testing ``is not None`` were
    always safe."""
    ck = ManualClock()
    tr = Tracer(clock=ck)
    router = Router({"m": ScriptedModel(ck)}, RouterConfig(),
                    clock=ck, tracer=tr)
    assert router.tracer is tr


def test_empty_tracer_is_truthy_null_tracer_is_falsy():
    """The ``__bool__`` fix: a real tracer is truthy even before its first
    event (``len() == 0``), while the disabled NullTracer stays falsy —
    so both injection idioms now keep a fresh tracer."""
    tr = Tracer(clock=ManualClock())
    assert len(tr) == 0 and bool(tr)
    assert (tr or NULL_TRACER) is tr
    assert not bool(NULL_TRACER)
    assert (NULL_TRACER or tr) is tr


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def test_chrome_events_shapes_and_metadata_order():
    tr = Tracer(clock=ManualClock())
    tr.add_span("wave", 0.001, 0.003, cat="router", pid=1, tid=2,
                args={"n": 4})
    tr.instant("shed", t=0.002, cat="router")
    tr.counter("backlog", 5, t=0.004)
    evs = chrome_events(tr.events(), process_names={1: "replica0",
                                                    0: "router"},
                        thread_names={(0, 1): "lane:m"})
    assert [e["ph"] for e in evs[:3]] == ["M", "M", "M"]
    assert evs[0]["args"]["name"] == "router"       # pids sorted
    assert evs[1]["args"]["name"] == "replica0"
    assert evs[2] == {"ph": "M", "name": "thread_name", "pid": 0, "tid": 1,
                      "args": {"name": "lane:m"}}
    span, inst, ctr = evs[3:]
    assert span["ph"] == "X" and span["ts"] == 0.001 * 1e6
    assert span["dur"] == (0.003 - 0.001) * 1e6
    assert span["args"] == {"n": 4}
    assert inst["ph"] == "i" and inst["s"] == "t"
    assert ctr["ph"] == "C" and ctr["args"] == {"backlog": 5.0}


def test_export_sanitizes_args_to_json_primitives():
    tr = Tracer(clock=ManualClock())
    tr.add_span("s", 0.0, 1.0, args={"a": np.float32(1.5),
                                     "b": [np.int32(2), "x"],
                                     "c": object()})
    (ev,) = chrome_events(tr.events())[0:1]
    args = ev["args"]
    assert args["a"] == 1.5 and type(args["a"]) is float
    assert args["b"] == [2, "x"]
    assert isinstance(args["c"], str)
    json.dumps(args)  # round-trips as plain JSON


def test_manual_clock_runs_export_byte_identically(tmp_path):
    """ISSUE acceptance: two fresh runs under the same ManualClock
    schedule produce byte-identical Chrome-trace and JSONL files."""
    _, tr1, router1 = _serve_run()
    _, tr2, router2 = _serve_run()
    s1 = chrome_json(tr1, **router1.trace_names())
    s2 = chrome_json(tr2, **router2.trace_names())
    assert s1 == s2
    assert len(tr1) > 0           # non-vacuous: the runs actually traced
    p1 = export_chrome(tr1, str(tmp_path / "a" / "t1.json"),
                       **router1.trace_names())
    p2 = export_chrome(tr2, str(tmp_path / "b" / "t2.json"),
                       **router2.trace_names())
    b1, b2 = open(p1, "rb").read(), open(p2, "rb").read()
    assert b1 == b2
    doc = json.loads(b1)
    assert doc["otherData"]["n_dropped"] == 0
    # spans and counters; the router records no per-request instants
    assert {e["ph"] for e in doc["traceEvents"]} == {"M", "X", "C"}
    assert {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"} == {
        "request", "wave", "wave.wait", "router.fetch"}
    assert jsonl_lines(tr1) == jsonl_lines(tr2)
    j1 = export_jsonl(tr1, str(tmp_path / "a" / "t1.jsonl"))
    assert all(json.loads(line) for line in open(j1))


def test_export_creates_parent_directories(tmp_path):
    tr = Tracer(clock=ManualClock())
    tr.instant("x", t=0.0)
    path = str(tmp_path / "deep" / "nested" / "trace.json")
    assert export_chrome(tr, path) == path
    assert os.path.exists(path)


# ---------------------------------------------------------------------------
# span-derived reports vs serve metrics — the bit-exact cross-check
# ---------------------------------------------------------------------------

def test_span_percentiles_equal_snapshot_to_the_bit():
    """ISSUE acceptance: p50/p90/p99 recomputed from request spans equal
    the ServeMetrics snapshot with ``==`` — same floats, same
    np.percentile, no approx."""
    _, tr, router = _serve_run()
    snap = router.stats()["m"]["metrics"]
    pct = latency_percentiles(tr, model="m")
    assert pct["n"] == snap.n_completed > 0
    assert pct["p50_ms"] == snap.p50_ms
    assert pct["p90_ms"] == snap.p90_ms
    assert pct["p99_ms"] == snap.p99_ms


def test_request_latency_population_excludes_sheds():
    tr = Tracer(clock=ManualClock())
    tr.add_span("request", 0.0, 0.010, args={"uid": 0, "model": "m"})
    tr.add_span("request", 1.0, 1.0, args={"uid": 1, "model": "m",
                                           "shed": True})
    tr.add_span("request", 0.0, 0.020, args={"uid": 2, "model": "other"})
    lats = request_latencies_ms(tr, model="m")
    np.testing.assert_array_equal(lats, [10.0])
    assert latency_percentiles(tr)["n"] == 2   # both models, sheds out


def test_wave_spans_carry_the_fifo_prediction():
    """Every dispatched wave records predicted_ms (the raw FIFO-cost-model
    estimate) next to its measured duration."""
    _, tr, router = _serve_run()
    waves = tr.spans(name="wave")
    rows = prediction_records(tr)
    assert len(rows) == len(waves) > 0
    svc = router.lanes["m"].service
    for row, ev in zip(rows, waves):
        assert row["predicted_ms"] == svc.wave_service_s(4) * 1e3
        assert row["measured_ms"] == (ev.t1 - ev.t0) * 1e3
        assert row["model"] == "m"


def test_prediction_error_statistics_are_exact():
    tr = Tracer(clock=ManualClock())
    base = {"model": "m", "platform": "cpu", "micro_batch": 4, "n_valid": 4}
    tr.add_span("wave", 0.0, 0.012, args={**base, "predicted_ms": 10.0})
    tr.add_span("wave", 0.0, 0.008, args={**base, "predicted_ms": 10.0})
    tr.add_span("wave", 0.0, 0.008, args=base)   # no prediction -> skipped
    assert len(prediction_records(tr)) == 2
    err = prediction_error(tr)["m@cpu"]
    assert err["n_waves"] == 2
    assert err["predicted_ms_mean"] == 10.0
    assert err["measured_ms_mean"] == pytest.approx(10.0)
    assert err["mean_abs_rel_err"] == pytest.approx(0.2)
    assert err["median_abs_rel_err"] == pytest.approx(0.2)
    assert err["bias_rel"] == pytest.approx(0.0, abs=1e-12)


def test_stage_latencies_cross_check_against_trace(clock, monkeypatch):
    """``stage_medians_ms`` recomputes the ``stage_latencies`` breakdown
    from the probe spans with identical arithmetic — medians must match
    exactly, float for float."""
    import jax

    from repro.core.qir import export_qmlp
    from repro.deploy import compile_graph
    from repro.models.tiny import KWSMLP

    model = KWSMLP(width=16)
    params = model.init(jax.random.PRNGKey(0))
    hidden_defs, _ = model.layers()
    graph = export_qmlp(hidden_defs, params["hidden"], params["head"])
    tr = Tracer()          # no clock= -> reads the faked obs timer
    cm = compile_graph(graph, in_scale=1.0 / 127.0, use_pallas=False,
                       tracer=tr)
    assert cm.tracer is tr

    costs = [0.002 * (i + 1) for i in range(len(cm.schedule.stages))]

    def fake_fn(c):
        def fn(h):
            clock.advance(c)
            return h
        return fn

    monkeypatch.setattr(cm, "_stage_fns", [fake_fn(c) for c in costs])
    breakdown = cm.stage_latencies(np.zeros((1, 490), np.int32), iters=3)
    assert len(tr.spans(name="stage")) == 3 * len(costs)
    med = stage_medians_ms(tr)
    assert set(med) == {b["stage"] for b in breakdown}
    for b in breakdown:
        assert med[b["stage"]] == b["ms"]


# ---------------------------------------------------------------------------
# ServeMetrics edge cases (tentpole fixes)
# ---------------------------------------------------------------------------

def test_snapshot_on_empty_window_is_all_zeros():
    snap = ServeMetrics(window_s=5.0).snapshot(123.4)
    assert snap.n_completed == snap.n_shed == snap.n_admitted == 0
    assert snap.p50_ms == snap.p99_ms == 0.0
    assert snap.throughput_qps == 0.0
    assert snap.shed_rate == 0.0 and snap.mean_occupancy == 0.0


def test_cold_start_sheds_open_the_throughput_window():
    """The fixed bug: a recorder idling from t=0 whose first traffic (all
    sheds) lands at t=100 must measure qps over the traffic span, not the
    recorder lifetime — sheds open the window too."""
    m = ServeMetrics(window_s=30.0, start_t=0.0)
    m.record_shed(100.0)
    m.record_completion(100.5, 0.010)
    assert m.first_event_t == 100.0
    snap = m.snapshot(101.0)
    assert snap.throughput_qps == 1.0 / (101.0 - 100.0)
    assert snap.shed_rate == 1.0    # 1 shed / (0 admits + 1 shed)


def test_sheds_only_window_reports_zero_qps_full_shed_rate():
    m = ServeMetrics(window_s=30.0)
    for t in (10.0, 10.1, 10.2):
        m.record_shed(t)
    snap = m.snapshot(11.0)
    assert snap.n_completed == 0 and snap.throughput_qps == 0.0
    assert snap.n_shed == 3 and snap.shed_rate == 1.0


def test_prune_boundary_is_inclusive():
    """An event stamped exactly at ``now - window_s`` stays (strict ``<``
    comparison) — the documented tie direction manual-clock tests rely
    on."""
    m = ServeMetrics(window_s=10.0)
    m.record_completion(0.0, 0.001)
    assert m.snapshot(10.0).n_completed == 1
    assert m.snapshot(10.0 + 1e-6).n_completed == 0


def test_wave_occupancy_histogram_with_mixed_micro_batch_sizes():
    """Waves dispatched under different micro-batch sizes (a lane's wave
    size can change between runs): the histogram keys on n_valid and the
    mean normalizes each wave by ITS OWN micro_batch."""
    m = ServeMetrics(window_s=30.0)
    m.record_wave(1.0, 4, 4)     # full wave at mb=4
    m.record_wave(1.1, 2, 4)     # half wave at mb=4
    m.record_wave(1.2, 2, 8)     # quarter wave at mb=8
    snap = m.snapshot(2.0)
    assert snap.n_waves == 3
    assert snap.occupancy_hist == {4: 1, 2: 2}
    assert snap.mean_occupancy == pytest.approx((1.0 + 0.5 + 0.25) / 3)


# ---------------------------------------------------------------------------
# timer + lint satellites
# ---------------------------------------------------------------------------

def test_timer_fake_installs_and_restores():
    real = obs_timer.get_timer()
    fk = FakeClock()
    with obs_timer.fake(fk):
        assert obs_timer.get_timer() is fk
        fk.advance(2.5)
        assert obs_timer.now() == 2.5
        obs_timer.sleep(0.5)
        assert fk.t == 3.0
        # manual clocks have no walltime: provenance stamps fall back to
        # the real epoch clock rather than leaking fake durations
        assert obs_timer.walltime() > 1e9
    assert obs_timer.get_timer() is real


def test_tracer_without_clock_reads_process_timer(clock):
    tr = Tracer()
    clock.advance(7.0)
    assert tr.now() == 7.0
    with tr.span("s"):
        clock.advance(1.0)
    assert tr.spans(name="s")[0].dur == 1.0


def test_no_raw_clock_lint_passes():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, os.path.join(root, "scripts",
                                      "check_no_raw_clock.py")],
        capture_output=True, text=True, cwd=root)
    assert res.returncode == 0, res.stdout + res.stderr
