"""The phases of a wave's host shell as spans: parent links, one clock,
the tracer handed down by the router, and the events the router no
longer records.

Every wave the router submits gets an id. While the router submits,
waits on or settles the wave, ``obs.wave_scope`` holds it as the
tracer's parent link, so each ``obs.phase`` span inside (``replica.put``,
``executor.pad``, ``executor.launch``, ``wave.wait``, ``router.fetch``)
names its wave; the ``wave`` span and the wave's ``request`` spans carry
the same id. Under a ``ManualClock`` shared by the router and the tracer
the children nest exactly inside their wave span, with waves
overlapping under ``AsyncEngine(2)``.
"""

import jax
import numpy as np
import pytest

from repro.obs import (
    NULL_TRACER,
    Tracer,
    chrome_json,
    jsonl_lines,
    phase,
    wave_scope,
)
from repro.serve import (
    AsyncEngine,
    ManualClock,
    ReplicaPool,
    Router,
    RouterConfig,
    SyncEngine,
    poisson_trace,
)

PHASES = ("replica.put", "executor.pad", "executor.launch", "wave.wait",
          "router.fetch")
PAD_S, LAUNCH_S, SERVICE_S = 0.0002, 0.0003, 0.004


class _Handle:
    """Scripted in-flight wave: done at ``ready_t`` on the manual clock;
    ``wait`` advances the clock there."""

    def __init__(self, clock, ready_t, y, mask):
        self.clock, self.ready_t = clock, ready_t
        self.done_t = None
        self._y, self._mask = y, mask

    def wait(self):
        self.clock.advance(max(self.ready_t - self.clock.now(), 0.0))
        self.done_t = self.ready_t
        return self._y, self._mask


class PhasedModel:
    """Executor fake that spends scripted time in the executor's phases:
    ``PAD_S`` padding, ``LAUNCH_S`` dispatching. The sync form also
    spends the wave's ``SERVICE_S`` inside the launch; the async form
    (``submit_wave_async``) schedules it on the device, one wave after
    another."""

    def __init__(self, clock, micro_batch=4, asynchronous=False):
        self.clock = clock
        self.default_micro_batch = micro_batch
        self.tracer = NULL_TRACER
        self.busy_until = 0.0
        if asynchronous:
            self.submit_wave_async = self._submit_async

    def set_tracer(self, tracer):
        self.tracer = tracer if tracer is not None else NULL_TRACER
        return self

    def _run(self, x, micro_batch, service_s):
        mb = int(micro_batch or self.default_micro_batch)
        with phase(self.tracer, "executor.pad", cat="executor"):
            x = np.asarray(x)
            n = x.shape[0]
            mask = np.concatenate([np.ones(n, bool),
                                   np.zeros(mb - n, bool)])
            self.clock.advance(PAD_S)
        with phase(self.tracer, "executor.launch", cat="executor"):
            y = np.zeros((mb, 1), np.float32)
            y[:n, 0] = x.reshape(n, -1).sum(axis=1)
            self.clock.advance(LAUNCH_S + service_s)
        return y, mask

    def submit_wave(self, x, valid=None, micro_batch=None):
        return self._run(x, micro_batch, SERVICE_S)

    def _submit_async(self, x, valid=None, micro_batch=None):
        y, mask = self._run(x, micro_batch, 0.0)
        self.busy_until = max(self.clock.now(), self.busy_until) + SERVICE_S
        return _Handle(self.clock, self.busy_until, y, mask)


def _mk(i):
    return np.full((4,), i, np.int32)


def _phased_run(engine, n=48, qps=2000.0):
    """A traced ManualClock run of ``n`` Poisson requests through one
    replica on the CPU device (so ``Replica.submit`` runs its
    ``device_put``)."""
    clock = ManualClock()
    tracer = Tracer(clock=clock)
    model = PhasedModel(clock, asynchronous=not engine.blocking)
    pool = ReplicaPool(model, devices=[jax.devices("cpu")[0]])
    router = Router({"m": pool}, RouterConfig(micro_batch=4,
                                              max_wait_ms=1.0),
                    clock=clock, tracer=tracer, engine=engine)
    reqs = router.run_trace("m", poisson_trace(qps=qps, n=n, seed=5), _mk)
    assert all(r.result is not None for r in reqs)
    return tracer, router


@pytest.mark.parametrize("engine", ["sync", "async2"])
def test_every_wave_gets_its_phase_children(engine):
    eng = SyncEngine() if engine == "sync" else AsyncEngine(max_inflight=2)
    tracer, _ = _phased_run(eng)
    spans = tracer.spans()
    waves = {s.args["wave"]: s for s in spans if s.name == "wave"}
    assert len(waves) == len([s for s in spans if s.name == "wave"]) > 3
    children = {}
    for s in spans:
        if s.name in PHASES:
            children.setdefault(s.args["wave"], []).append(s)
    assert set(children) == set(waves)
    for wid, w in waves.items():
        kids = children[wid]
        assert sorted(k.name for k in kids) == sorted(PHASES), wid
        for k in kids:
            # the child's own id, its wave's track, inside its wave span
            assert k.args == {"wave": wid}
            assert (k.pid, k.tid) == (w.pid, w.tid)
            assert w.t0 <= k.t0 <= k.t1 <= w.t1, (wid, k.name)
        dur = {k.name: k.dur for k in kids}
        assert dur["executor.pad"] == pytest.approx(PAD_S)
        assert dur["replica.put"] == 0.0 == dur["router.fetch"]
        if engine == "sync":
            assert dur["executor.launch"] == pytest.approx(
                LAUNCH_S + SERVICE_S)
            # the children and the wave's self time partition its span
            assert sum(dur.values()) == pytest.approx(w.dur)
        else:
            assert dur["executor.launch"] == pytest.approx(LAUNCH_S)
    # every served request's span names a wave that ends with it
    reqs = [s for s in spans if s.name == "request"]
    assert len(reqs) == 48
    for r in reqs:
        assert r.t1 == waves[r.args["wave"]].t1
    if engine == "async2":
        # non-vacuous: the pairing holds while waves overlap
        ws = sorted(waves.values(), key=lambda s: s.t0)
        assert any(b.t0 < a.t1 for a, b in zip(ws, ws[1:]))


def test_phase_spans_carry_the_open_wave_link_and_restore_it():
    clock = ManualClock()
    tr = Tracer(clock=clock)
    with phase(tr, "executor.pad"):
        clock.advance(1.0)
    with wave_scope(tr, 7, pid=2, tid=3):
        assert tr.link == (7, 2, 3)
        with wave_scope(tr, 8, pid=1, tid=1):
            with phase(tr, "wave.wait", cat="replica"):
                clock.advance(0.5)
        assert tr.link == (7, 2, 3)
        with phase(tr, "router.fetch", cat="router"):
            clock.advance(0.25)
    assert tr.link is None
    outside, inner, fetch = tr.spans()
    assert (outside.name, outside.args, outside.pid) == (
        "executor.pad", None, 0)
    assert (inner.args, inner.pid, inner.tid, inner.dur) == (
        {"wave": 8}, 1, 1, 0.5)
    assert (fetch.args, fetch.pid, fetch.tid, fetch.cat) == (
        {"wave": 7}, 2, 3, "router")
    # the disabled tracer records nothing and keeps no link
    with wave_scope(NULL_TRACER, 1):
        with phase(NULL_TRACER, "executor.pad"):
            pass
    assert NULL_TRACER.link is None and NULL_TRACER.events() == []


def test_phase_args_join_the_wave_link():
    tr = Tracer(clock=ManualClock())
    with wave_scope(tr, 3):
        with phase(tr, "executor.pad") as ph:
            ph.set(rows=2, pulled_back=False)
    with phase(tr, "executor.pad") as ph:
        ph.set(rows=1)
    linked, alone = tr.spans()
    assert linked.args == {"wave": 3, "rows": 2, "pulled_back": False}
    assert alone.args == {"rows": 1}
    # with the tracer off nothing is kept
    with phase(NULL_TRACER, "executor.pad") as ph:
        ph.set(rows=2)
    assert ph.args is None


def test_router_records_no_per_request_events():
    tracer, router = _phased_run(SyncEngine())
    names = {e.name for e in tracer.events()}
    assert not names & {"enqueue", "admit", "wave_assemble", "segment"}
    assert tracer.events(kind="instant") == []
    n_waves = len(tracer.spans(name="wave"))
    # backlog is sampled once at dispatch and once at completion per wave
    assert len(tracer.counters(name="backlog")) == 2 * n_waves
    assert len(tracer.spans(name="request")) == 48


def test_phase_spans_export_byte_identically():
    runs = [_phased_run(AsyncEngine(max_inflight=2)) for _ in range(2)]
    (t1, r1), (t2, r2) = runs
    assert chrome_json(t1, **r1.trace_names()) \
        == chrome_json(t2, **r2.trace_names())
    assert jsonl_lines(t1) == jsonl_lines(t2)
    assert {s.name for s in t1.spans()} >= set(PHASES)


@pytest.fixture(scope="module")
def kws_small():
    from repro.core.qir import export_qmlp
    from repro.deploy import compile_graph
    from repro.models.tiny import KWSMLP

    model = KWSMLP(width=16)
    params = model.init(jax.random.PRNGKey(0))
    hidden_defs, _ = model.layers()
    graph = export_qmlp(hidden_defs, params["hidden"], params["head"])
    return lambda: compile_graph(graph, in_scale=1.0 / 127.0,
                                 use_pallas=False)


def _serve(router, n, wave=4):
    rng = np.random.default_rng(0)
    reqs = [router.submit("m", rng.integers(-127, 128, (490,))
                          .astype(np.int32)) for _ in range(n)]
    router.flush()
    router.drain()
    assert all(r.result is not None for r in reqs)


def test_router_hands_its_tracer_down_and_a_later_router_clears_it(
        kws_small):
    cm = kws_small()
    pool = ReplicaPool(cm, devices=[jax.devices("cpu")[0]])
    first = Tracer()
    cfg = RouterConfig(micro_batch=4, max_wait_ms=1.0)
    _serve(Router({"m": pool}, cfg, tracer=first), 8)
    assert pool.tracer is first and pool.replicas[0].tracer is first
    assert cm.tracer is first
    spans = first.spans()
    waves = {s.args["wave"] for s in spans if s.name == "wave"}
    assert len(waves) == 2
    for name in PHASES:
        got = [s for s in spans if s.name == name]
        # the real executor's phases, one per wave, each naming its wave
        assert {s.args["wave"] for s in got} == waves, name
        assert len(got) == len(waves), name
    assert not [s for s in spans if s.name == "segment"]
    # the executor makes the wave's one put (``replica.put``), of the host
    # rows the router stacked: nothing was pulled back to pad them
    for s in spans:
        if s.name == "executor.pad":
            assert s.args == {"wave": s.args["wave"], "rows": 4,
                              "pad_rows": 0, "put_bytes": 4 * 490 * 4,
                              "pulled_back": False}
    n_first = len(first)

    # a router built without a tracer over the same pool installs
    # NULL_TRACER: nothing more lands in the first router's tracer
    _serve(Router({"m": pool}, cfg), 8)
    assert pool.tracer is NULL_TRACER and cm.tracer is NULL_TRACER
    assert pool.replicas[0].tracer is NULL_TRACER
    assert len(first) == n_first

    # and a third router with its own tracer gets every phase again
    third = Tracer()
    _serve(Router({"m": pool}, cfg, tracer=third), 4)
    assert {s.name for s in third.spans()} >= set(PHASES)
    assert len(first) == n_first


def test_router_and_tracer_default_clocks_are_one_source(monkeypatch):
    """The router's real clock and the tracer's default read the same
    ``perf_counter``, so a wave span and its phase spans share one
    clock."""
    import time

    from repro.serve.clock import SystemClock

    monkeypatch.setattr(time, "perf_counter", lambda: 123.25)
    assert SystemClock().now() == Tracer().now() == 123.25
