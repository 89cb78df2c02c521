"""Executable form of a lowered QIR graph: one jit program + a micro-batched
streaming pipeline whose buffer depths come from the FIFO simulator.

Three execution modes mirror the paper's deployment measurements:

  * **offline**  — the whole stage schedule compiled into a single XLA
    program over the full batch (max throughput; MLPerf Offline). Fused
    integer stages run on the Pallas kernels on TPU — ``threshold_matmul``
    for dense stages, the fused direct-conv ``conv_threshold`` (no
    materialized im2col) for conv stages lowered ``direct`` — and as the
    XLA-fused jnp reference otherwise (same integers either way).
  * **streaming_compiled** — the deployment hot path: the stage schedule is
    grouped into *segments* (``lower.group_segments`` — maximal runs of
    fused/integer stages between host boundaries) and each segment executes
    the whole micro-batched wave as ONE jit program: micro-batches advance
    through the segment's stages inside ``jax.lax`` control flow, with
    buffers donated between segment programs where the backend supports it.
    Python is crossed once per segment, not once per stage per micro-batch.
  * **streaming_host** — the reference queue-loop pipeline: micro-batches
    flow through per-stage programs connected by bounded queues whose
    capacities are *decided* by ``core.dataflow.optimize_fifo_depths`` — the
    paper's simulate-big/record-max/shrink-to-max+1 pass feeding a real
    execution. Kept for its observable occupancy/backpressure stats; it is
    asserted bit-identical to the compiled path.

The unfused per-node interpreter (``reference``) is kept as the baseline the
benchmarks compare against — it is what running the QIR graph layer by layer
without the compiler looks like.

The default streaming micro-batch (and the direct-conv kernel's row block)
can come from the FIFO-model autotuner (``deploy.autotune``) via
``apply_tuned`` / ``compile_graph(..., autotune=True)`` instead of the
historical hard-coded 16.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dataflow import micro_batch_stage, optimize_fifo_depths
from repro.core.qir import Graph
from repro.obs import timer as obs_timer
from repro.obs.tracer import NULL_TRACER
from repro.deploy.lower import (
    FlattenStage,
    FloatHeadStage,
    FusedConvThresholdStage,
    FusedThresholdStage,
    IntPoolStage,
    MegakernelSegment,
    RefChainStage,
    Segment,
    StageSchedule,
    group_segments,
    lower_graph,
    plan_megakernel,
)

#: Historical default micro-batch; used only when no tuned config is applied.
DEFAULT_MICRO_BATCH = 16


def stage_work(s) -> int:
    """Per-sample element count driving the FIFO cost model for one stage:
    ``fifo_work`` where the stage defines it (lowering-aware for convs),
    MACs for matmul-like stages, in*out as the last resort. Shared by
    ``plan_streaming`` and the serve-side service-time model
    (``repro.serve.slo``) so the two never disagree about stage cost."""
    work = getattr(s, "fifo_work", None)
    if work is None:
        work = getattr(s, "macs", None)
    if work is None:
        work = s.in_dim * s.out_dim
    return int(work)


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU: the kernel path is then
    the default. No fallback: a backend that fails to start raises here."""
    return jax.devices()[0].platform == "tpu"


@dataclasses.dataclass
class StreamingStats:
    """What the FIFO pass decided and what the pipeline actually did.

    ``mode`` distinguishes the host queue loop ("host": ``max_occupancy`` is
    *observed*) from the compiled segment-wave path ("compiled":
    ``max_occupancy`` is the FIFO simulator's modeled occupancy — the
    compiled program has no per-hop queues to observe). ``segments`` lists
    the (start, stop) stage ranges of the executed segment grouping.
    """

    micro_batch: int
    n_micro: int
    fifo_depths: List[int]
    max_occupancy: List[int]
    sim_cycles: int
    mode: str = "host"
    segments: Optional[List[Tuple[int, int]]] = None
    #: stage ranges that executed as whole-network-resident megakernels
    #: (``docs/megakernel.md``); empty/None when every segment ran staged
    megakernel: Optional[List[Tuple[int, int]]] = None


class CompiledTinyModel:
    """A compiled spatial-dataflow executor for one lowered QIR graph.

    Bound to the device that is JAX's default while it is built (a
    ``jax.default_device`` context, as ``ReplicaPool`` gives each replica):
    its weights live there, and ``submit_wave`` places each wave there, so
    every program of this executor runs on that one device.
    """

    def __init__(self, schedule: StageSchedule, graph: Optional[Graph] = None,
                 use_pallas: Optional[bool] = None,
                 interpret: bool = False,
                 megakernel: Optional[bool] = None,
                 megakernel_budget_bytes: Optional[int] = None,
                 tracer=None):
        self.schedule = schedule
        self.graph = graph
        #: the kernel path (Pallas) is the default on a TPU; ``interpret``
        #: runs its kernels through the Pallas interpreter (CPU tests)
        self.use_pallas = on_tpu() if use_pallas is None else use_pallas
        self.interpret = bool(interpret)
        #: None = JAX's default device at the call
        self.device = jax.config.jax_default_device
        self.tuned = None          # deploy.autotune.TunedConfig, if applied
        #: megakernel dispatch: None = auto (fused whenever the residency
        #: planner admits the segment), True = same but assert-intent,
        #: False = force the per-stage reference path. The autotuner's
        #: measured megakernel-vs-staged choice lands here via apply_tuned.
        self.megakernel = megakernel
        self.megakernel_budget_bytes = megakernel_budget_bytes
        #: obs.Tracer sink for segment/stage spans and FIFO occupancy
        #: counters; NULL_TRACER keeps every instrumentation site a no-op
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._rebuild()

    def set_tracer(self, tracer) -> "CompiledTinyModel":
        """Install (or clear, with ``None``) the obs tracer; returns self."""
        self.tracer = tracer if tracer is not None else NULL_TRACER
        return self

    def set_megakernel(self, mode: Optional[bool],
                       budget_bytes: Optional[int] = None
                       ) -> "CompiledTinyModel":
        """Re-plan megakernel dispatch (None = auto / True / False) and drop
        the stale compiled programs; ``budget_bytes`` overrides the planner's
        VMEM cap (tests force the fallback with a tiny one) and None restores
        the default cap. Returns self."""
        self.megakernel = mode
        self.megakernel_budget_bytes = budget_bytes
        self._rebuild()
        return self

    def _rebuild(self):
        """(Re)create every compiled entry point from the current schedule —
        called at construction and after ``apply_tuned`` mutates stage
        parameters (jit closures capture the stage objects at trace time, so
        stale programs must be dropped)."""
        # residency-plan megakernel runs per compiled segment first — the
        # offline and segment programs below dispatch through the plans
        self._mega_plans: Dict[int, MegakernelSegment] = {}
        self._mega_by_start: Dict[int, MegakernelSegment] = {}
        self.segments: List[Segment] = group_segments(self.schedule.stages)
        if self.megakernel is not False:
            for k, seg in enumerate(self.segments):
                plan = plan_megakernel(
                    self.schedule.stages, seg,
                    budget_bytes=self.megakernel_budget_bytes)
                if plan is not None:
                    self._mega_plans[k] = plan
                    self._mega_by_start[plan.start] = plan
        self._offline = jax.jit(self._run_all)
        self._stage_fns = [jax.jit(self._make_stage_fn(s))
                           for s in self.schedule.stages]
        self._segment_fns: Dict[int, Callable] = {}
        self._plan_cache: Dict[Tuple[int, int], Tuple[List[int], int]] = {}

    @property
    def default_micro_batch(self) -> int:
        return (self.tuned.micro_batch if self.tuned is not None
                else DEFAULT_MICRO_BATCH)

    def apply_tuned(self, cfg) -> "CompiledTinyModel":
        """Adopt an autotuned config (``deploy.autotune.TunedConfig``): the
        streaming default micro-batch, per-conv-stage ``block_h``,
        per-dense-stage ``block_m``/``block_n``, and the measured
        megakernel-vs-staged segment dispatch choice (schema v3) replace
        the magic constants. Returns self for chaining."""
        for s in self.schedule.stages:
            if isinstance(s, FusedConvThresholdStage):
                bh = cfg.block_h.get(s.name)
                if bh is not None:
                    s.block_h = min(int(bh), s.geom.out_h)
            elif isinstance(s, FusedThresholdStage):
                mn = getattr(cfg, "block_mn", {}).get(s.name)
                if mn is not None:
                    s.block_m, s.block_n = int(mn[0]), int(mn[1])
        mode = getattr(cfg, "segment_mode", None)
        if mode in ("megakernel", "staged"):
            self.megakernel = mode == "megakernel"
        self.tuned = cfg
        self._rebuild()
        return self

    # -- single-program (offline) path -----------------------------------
    def _apply_stage(self, s, h):
        if isinstance(s, (FusedThresholdStage, FusedConvThresholdStage)):
            if self.use_pallas:
                return s.apply_kernel(h, interpret=self.interpret)
            return s.apply_fast(h)
        if isinstance(s, (IntPoolStage, FlattenStage, FloatHeadStage)):
            return s.apply_ref(h)
        if isinstance(s, RefChainStage):
            if jnp.issubdtype(h.dtype, jnp.integer):
                h = h.astype(jnp.float32) * s.in_scale
            return s.apply_ref(h)
        raise TypeError(type(s))  # pragma: no cover

    def _make_stage_fn(self, s) -> Callable:
        return lambda h: self._apply_stage(s, h)

    def _apply_mega(self, plan: MegakernelSegment, h):
        """One planned stage run as a single program: the Pallas megakernel
        (weights/banks resident in VMEM, inter-stage tiles in scratch) on
        the kernel path, or the same chain as one straight-line fused XLA
        computation on CPU — either way ZERO per-stage dispatch, and
        bit-identical to the staged reference (order-free integer ops)."""
        stages = self.schedule.stages[plan.start:plan.stop]
        if self.use_pallas:
            from repro.kernels import ops

            return ops.mlp_megakernel(
                h, tuple(s.stage.w_int for s in stages),
                tuple(s.stage.thresholds for s in stages),
                operands=tuple(s.mxu[0] for s in stages),
                offsets=tuple(s.mxu[1] for s in stages),
                block_m=plan.block_m, interpret=self.interpret)
        for s in stages:
            h = s.apply_fast(h)
        return h

    def _run_all(self, x_int):
        h = x_int
        stages = self.schedule.stages
        i = 0
        while i < len(stages):
            plan = self._mega_by_start.get(i)
            if plan is not None:
                h = self._apply_mega(plan, h)
                i = plan.stop
            else:
                h = self._apply_stage(stages[i], h)
                i += 1
        return h

    def offline(self, x_int) -> jnp.ndarray:
        """Full batch through the single fused program (MLPerf Offline)."""
        return self._offline(jnp.asarray(x_int))

    def stage_outputs(self, x_int) -> List[jnp.ndarray]:
        """Per-stage outputs (integer codes for fused stages) — the parity
        surface the exactness tests check against the float reference."""
        outs, h = [], jnp.asarray(x_int)
        for fn in self._stage_fns:
            h = fn(h)
            outs.append(h)
        return outs

    def predict(self, x_int) -> jnp.ndarray:
        return jnp.argmax(self.offline(x_int), axis=-1)

    # -- unfused reference (what the benchmarks beat) ---------------------
    def reference(self, x_int) -> jnp.ndarray:
        """Per-node eager interpretation of the source QIR graph."""
        if self.graph is None:
            raise ValueError("compile with graph= to keep the reference path")
        x = np.asarray(x_int, np.float32) * self.schedule.in_scale
        out = self.graph.run({self.graph.inputs[0]: x})
        return jnp.asarray(out[self.graph.outputs[0]])

    # -- per-stage timing (feeds the scenario stage_ms breakdown) ---------
    def stage_latencies(self, x, iters: int = 5) -> List[Dict[str, object]]:
        """Median wall-time per compiled stage on one representative batch.

        Per stage: one compile call, one *discarded* warm iteration, then
        ``iters`` timed samples, median reported — enough samples that the
        breakdown (and the autotuner's measured refinement it seeds) is
        stable against scheduler noise. Runs the per-stage programs in
        schedule order (each stage's input is the previous stage's real
        output) so conv-vs-dense costs are visible in scenario reports.

        Every timed sample is also recorded as a ``stage`` span on the
        model's tracer; the returned medians are computed from the SAME
        clock readings the spans carry, so
        ``obs.report.stage_medians_ms`` reproduces this breakdown from the
        trace exactly (cross-checked in tests)."""
        tr = self.tracer
        out = []
        h = jnp.asarray(x)
        for s, fn in zip(self.schedule.stages, self._stage_fns):
            y = fn(h)
            jax.block_until_ready(y)      # compile
            jax.block_until_ready(fn(h))  # discarded warm iteration
            times = []
            for it in range(max(iters, 1)):
                t0 = obs_timer.now()
                jax.block_until_ready(fn(h))
                t1 = obs_timer.now()
                if tr.enabled:
                    tr.add_span("stage", t0, t1, cat="probe",
                                args={"stage": s.name,
                                      "kind": type(s).__name__, "iter": it})
                times.append(t1 - t0)
            times.sort()
            out.append({"stage": s.name, "kind": type(s).__name__,
                        "ms": times[len(times) // 2] * 1e3})
            h = y
        return out

    # -- streaming (micro-batched pipeline) -------------------------------
    def plan_streaming(self, n_micro: int, micro_batch: int = 1
                       ) -> Tuple[List[int], int]:
        """Size the inter-stage queues with the paper's FIFO pass.

        Each stage's simulated service time scales with its per-sample work
        times the micro-batch size, plus a fixed per-hop overhead
        (``core.dataflow.micro_batch_stage``) — the cost model the
        micro-batch autotuner searches over. Work is parameterized on the
        lowering kind: MACs for dense stages, im2col tile counts (output
        tiles x patch size) for ``im2col`` conv stages, but only *output*
        tiles for ``direct`` fused conv stages — the fused kernel never
        emits patch tiles into the pipeline, so sizing its FIFOs from im2col
        counts would over-buffer (``fifo_work`` on each stage class). Rate
        mismatches between wide and narrow layers then show up as occupancy,
        exactly what the RTL simulation measured on the FPGA.

        Plans are memoized per (n_micro, micro_batch) — the simulation is
        deterministic, and the streaming entry points re-plan every call.
        """
        cached = self._plan_cache.get((n_micro, micro_batch))
        if cached is not None:
            return list(cached[0]), cached[1]
        sim = [micro_batch_stage(s.name, stage_work(s), micro_batch)
               for s in self.schedule.stages]
        res = optimize_fifo_depths(sim, n_tokens=n_micro)
        plan = (list(res["optimized_depths"]), int(res["optimized_cycles"]))
        self._plan_cache[(n_micro, micro_batch)] = plan
        return list(plan[0]), plan[1]

    def _pad_micro(self, x_int, micro_batch: int):
        x_int = jnp.asarray(x_int)
        n = x_int.shape[0]
        pad = (-n) % micro_batch
        if pad:
            x_int = jnp.concatenate(
                [x_int, jnp.zeros((pad,) + x_int.shape[1:], x_int.dtype)])
        return x_int, n, x_int.shape[0] // micro_batch

    def streaming_host(self, x_int, micro_batch: Optional[int] = None,
                       fifo_depths: Optional[Sequence[int]] = None,
                       feed_order: Optional[Sequence[int]] = None,
                       ) -> Tuple[jnp.ndarray, StreamingStats]:
        """The reference queue-loop pipeline: bounded host-side queues.

        Numerically identical to ``offline`` / ``streaming_compiled``; the
        difference is the execution schedule: at most ``depth[i]``
        micro-batches may queue in front of stage i, the capacities coming
        from the FIFO optimizer. This path crosses Python once per stage per
        micro-batch, so it is NOT the deployment hot path — it is kept as
        the observable reference: its occupancy stats are what validate the
        FIFO model, and the compiled path is asserted bit-identical to it.

        ``micro_batch=None`` resolves to the same (autotuned) default as
        ``streaming_compiled``, so the two entry points always compare the
        same schedule. ``fifo_depths`` overrides the optimizer's capacities
        (backpressure testing: depth-1 FIFOs must still make progress);
        ``feed_order`` permutes micro-batch admission (the idx bookkeeping
        must restore batch order regardless).
        """
        micro_batch = (int(micro_batch) if micro_batch
                       else self.default_micro_batch)
        x_int, n, n_micro = self._pad_micro(x_int, micro_batch)
        depths, sim_cycles = self.plan_streaming(n_micro,
                                                 micro_batch=micro_batch)
        if fifo_depths is not None:
            if len(fifo_depths) != len(depths):
                raise ValueError(
                    f"fifo_depths has {len(fifo_depths)} entries for "
                    f"{len(depths)} pipeline queues: {list(fifo_depths)}")
            depths = [max(1, int(d)) for d in fifo_depths]

        n_stages = len(self.schedule.stages)
        queues = [collections.deque() for _ in range(n_stages + 1)]
        max_occ = [0] * (n_stages + 1)
        order = list(feed_order) if feed_order is not None \
            else list(range(n_micro))
        if sorted(order) != list(range(n_micro)):
            raise ValueError(
                f"feed_order must be a permutation of range({n_micro}), "
                f"got {order}")
        feed = [(i, x_int[i * micro_batch:(i + 1) * micro_batch])
                for i in order]
        feed_i = 0
        done: List[Optional[jnp.ndarray]] = [None] * n_micro

        tr = self.tracer
        while feed_i < n_micro or any(len(q) > 0 for q in queues[:-1]):
            # admit into the input queue while its FIFO has room
            while feed_i < n_micro and len(queues[0]) < depths[0]:
                queues[0].append(feed[feed_i])
                max_occ[0] = max(max_occ[0], len(queues[0]))
                feed_i += 1
            if tr.enabled:
                tr.counter("fifo0", len(queues[0]), cat="fifo", tid=1)
            # fire stages downstream-first so space frees upstream
            for si in reversed(range(n_stages)):
                out_cap = depths[si + 1] if si + 1 < n_stages else n_micro + 1
                if queues[si] and len(queues[si + 1]) < out_cap:
                    idx, h = queues[si].popleft()
                    t0 = obs_timer.now() if tr.enabled else 0.0
                    h = self._stage_fns[si](h)
                    queues[si + 1].append((idx, h))
                    max_occ[si + 1] = max(max_occ[si + 1], len(queues[si + 1]))
                    if tr.enabled:
                        tr.add_span("fire", t0, obs_timer.now(), cat="fifo",
                                    tid=si + 1,
                                    args={"stage": self.schedule
                                          .stages[si].name, "micro": idx})
                        tr.counter(f"fifo{si + 1}", len(queues[si + 1]),
                                   cat="fifo", tid=si + 2)
            while queues[-1]:
                idx, y = queues[-1].popleft()
                done[idx] = y
        y = jnp.concatenate([jnp.asarray(d) for d in done])[:n]
        return y, StreamingStats(micro_batch=micro_batch, n_micro=n_micro,
                                 fifo_depths=depths, max_occupancy=max_occ,
                                 sim_cycles=sim_cycles, mode="host",
                                 segments=[(s.start, s.stop)
                                           for s in self.segments])

    # the historical name stays pointed at the observable reference path
    streaming = streaming_host

    # -- wave submission (the serve router's entry point) ------------------
    def submit_wave(self, x_int, valid: Optional[Sequence[bool]] = None,
                    micro_batch: Optional[int] = None
                    ) -> Tuple[jnp.ndarray, np.ndarray]:
        """Run ONE (possibly partially filled) micro-batch wave.

        The dynamic batcher (``repro.serve.router``) coalesces arriving
        requests into waves of at most ``micro_batch`` samples and cannot
        always fill a wave before its deadline — so this entry point accepts
        ``n <= micro_batch`` rows plus an optional ``valid`` mask, zero-pads
        up to the wave size (code 0 is value 0 under the export contract, so
        padding rows are inert), and pushes the wave through the SAME
        compiled segment programs as ``streaming_compiled`` (shape
        ``(1, micro_batch, ...)`` — one jit program per segment, compiled
        once per wave size). Returns ``(y, mask)`` where ``y`` covers the
        full wave and ``mask`` marks the rows that carry real queries;
        ``y[mask]`` is bit-identical to ``offline`` on the valid rows.

        The padding contract: invalid rows are forced to zero codes *before*
        execution (whatever the caller left in them), and nothing about an
        invalid row can perturb a valid one — stages are row-independent
        (matmul/conv/threshold act per sample), which the golden-model
        padded-wave tests assert.
        """
        mb = int(micro_batch) if micro_batch else self.default_micro_batch
        xb = np.asarray(x_int)
        n = xb.shape[0]
        if n > mb:
            raise ValueError(f"wave of {n} rows exceeds micro_batch={mb}")
        mask = np.ones(n, bool) if valid is None \
            else np.asarray(valid, bool).reshape(-1)
        if mask.shape[0] != n:
            raise ValueError(f"valid mask has {mask.shape[0]} entries "
                             f"for a wave of {n} rows")
        mask = np.concatenate([mask, np.zeros(mb - n, bool)])
        # pad + zero invalid rows on the HOST: the device only ever sees
        # the one constant (1, mb, ...) wave shape, so a lane serving
        # every fill level reuses a single compiled program — eager
        # device-side padding would trace a new program per fill level,
        # which is a mid-serve compile stall (a measured 20x wave-time
        # tail before this was moved host-side)
        buf = np.zeros((mb,) + xb.shape[1:], xb.dtype)
        buf[:n][mask[:n]] = xb[mask[:n]]
        wave = jax.device_put(buf[None], self.device)
        try:
            wave = self._run_segments(wave, 1, mode="submit_wave")
        except Exception as e:
            # raw backend/runtime exceptions must not escape the serving
            # entry point untyped: wrap them so the router's failure
            # machinery (retry on another replica, quarantine) can catch
            # one class instead of guessing. The validation ValueErrors
            # above stay raw — a malformed wave is a caller bug, not a
            # device failure. Imported lazily on the failure path only:
            # deploy must not depend on serve at module level.
            from repro.serve.faults import WaveError

            raise WaveError(
                f"wave of {n}/{mb} rows failed in the compiled segment "
                f"pipeline: {type(e).__name__}: {e}") from e
        return wave[0], mask

    def _run_segments(self, wave, n_micro: int, mode: str):
        """Push a stacked wave through every segment program, recording one
        ``segment`` span per segment when a tracer is installed. Spans
        measure host-side dispatch (tid = segment index + 1); on CPU, where
        XLA dispatch is effectively synchronous, that is the execution time
        — on accelerators the wave-level span (router) is the honest
        end-to-end number."""
        tr = self.tracer
        for k, seg in enumerate(self.segments):
            t0 = obs_timer.now() if tr.enabled else 0.0
            if seg.compiled:
                wave = self._segment_fn(k)(wave)
            else:
                # host boundary: the fallback interpreter, per micro-batch
                outs = [wave[i] for i in range(n_micro)]
                for si in range(seg.start, seg.stop):
                    outs = [self._stage_fns[si](h) for h in outs]
                wave = jnp.stack(outs)
            if tr.enabled:
                tr.add_span("segment", t0, obs_timer.now(), cat="executor",
                            tid=k + 1,
                            args={"segment": k, "mode": mode,
                                  "compiled": bool(seg.compiled),
                                  "megakernel": k in self._mega_plans,
                                  "stages": [seg.start, seg.stop]})
        return wave

    # -- streaming, compiled (the deployment hot path) ---------------------
    def _segment_fn(self, k: int) -> Callable:
        """One jit program running segment k's whole micro-batch wave.

        Staged form: ``jax.lax.map`` advances every micro-batch through the
        segment's stage chain on device. When the residency planner admitted
        a megakernel for this segment, the planned stage run executes as ONE
        resident program over the *flattened* wave instead — no per-stage
        dispatch and no per-micro-batch loop (row-independent stages make
        the flattening exact); only the segment's pre/post remainder stages
        (e.g. the float head) still ride ``lax.map``. Either way the wave
        buffer is donated between segment programs on backends that support
        donation (TPU/GPU), so segment boundaries don't double-buffer the
        whole wave."""
        fn = self._segment_fns.get(k)
        if fn is None:
            seg = self.segments[k]
            plan = self._mega_plans.get(k)
            stages = self.schedule.stages[seg.start:seg.stop]

            def chain(run, h):
                for s in run:
                    h = self._apply_stage(s, h)
                return h

            if plan is None:
                def run_wave(wave):
                    return jax.lax.map(lambda h: chain(stages, h), wave)
            else:
                pre = self.schedule.stages[seg.start:plan.start]
                post = self.schedule.stages[plan.stop:seg.stop]

                def run_wave(wave):
                    if pre:
                        wave = jax.lax.map(lambda h: chain(pre, h), wave)
                    n_micro, mb = wave.shape[0], wave.shape[1]
                    flat = wave.reshape((n_micro * mb,) + wave.shape[2:])
                    flat = self._apply_mega(plan, flat)
                    wave = flat.reshape((n_micro, mb) + flat.shape[1:])
                    if post:
                        wave = jax.lax.map(lambda h: chain(post, h), wave)
                    return wave

            donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()
            fn = jax.jit(run_wave, donate_argnums=donate)
            self._segment_fns[k] = fn
        return fn

    def streaming_compiled(self, x_int, micro_batch: Optional[int] = None
                           ) -> Tuple[jnp.ndarray, StreamingStats]:
        """Run the batch as a micro-batched pipeline without the host loop.

        The batch is cut into micro-batches, stacked into one wave array,
        and pushed through each compiled segment as ONE jit program
        (``_segment_fn``); only host-boundary segments (fallback float
        chains) return to Python, once per micro-batch. Bit-identical to
        ``offline`` and ``streaming_host`` — same stage semantics, different
        schedule. ``micro_batch=None`` uses the autotuned default
        (``apply_tuned``), else ``DEFAULT_MICRO_BATCH``.
        """
        mb = int(micro_batch) if micro_batch else self.default_micro_batch
        x_int, n, n_micro = self._pad_micro(x_int, mb)
        depths, sim_cycles = self.plan_streaming(n_micro, micro_batch=mb)
        wave = x_int.reshape((n_micro, mb) + x_int.shape[1:])
        wave = self._run_segments(wave, n_micro, mode="streaming_compiled")
        y = wave.reshape((n_micro * mb,) + wave.shape[2:])[:n]
        # no host queues to observe: report the FIFO model's occupancy
        # (depth = max occupancy + 1 by construction of the optimizer)
        return y, StreamingStats(micro_batch=mb, n_micro=n_micro,
                                 fifo_depths=depths,
                                 max_occupancy=[d - 1 for d in depths],
                                 sim_cycles=sim_cycles, mode="compiled",
                                 segments=[(s.start, s.stop)
                                           for s in self.segments],
                                 megakernel=[(p.start, p.stop) for p in
                                             self._mega_plans.values()])


def compile_graph(graph: Graph, in_scale: float = 1.0 / 127.0,
                  use_pallas: Optional[bool] = None,
                  interpret: bool = False,
                  conv_lowering: Optional[str] = None,
                  megakernel: Optional[bool] = None,
                  autotune: bool = False,
                  tuned=None, tracer=None) -> CompiledTinyModel:
    """The one-call deployment entry point: QIR json graph -> executor.

    ``conv_lowering`` picks the conv stage algorithm ("direct" fused kernel
    by default, "im2col" fallback) for both offline and streaming modes —
    the stage methods the executor dispatches through carry the choice.
    ``use_pallas`` defaults to the kernel path on a TPU and to the XLA
    path elsewhere; ``interpret=True`` runs the kernel path's Pallas
    kernels through the interpreter (CPU tests).
    ``megakernel`` forces the whole-network-resident fused dispatch on
    (True) or off (False); the default None lets the residency planner
    decide per segment (``docs/megakernel.md``), and an applied tuned
    config's measured ``segment_mode`` choice overrides it.

    ``tuned`` applies a prebuilt ``deploy.autotune.TunedConfig``;
    ``autotune=True`` instead loads (or searches and caches) the config for
    this (model, platform) via ``deploy.autotune.autotune_model`` — honours
    the ``REPRO_AUTOTUNE*`` knobs, see ``docs/pipeline.md``.
    """
    schedule = lower_graph(graph, in_scale=in_scale,
                           conv_lowering=conv_lowering)
    cm = CompiledTinyModel(schedule, graph=graph, use_pallas=use_pallas,
                           interpret=interpret, megakernel=megakernel,
                           tracer=tracer)
    if tuned is not None:
        cm.apply_tuned(tuned)
    elif autotune:
        from repro.deploy.autotune import autotune_mode, autotune_model

        mode = autotune_mode()
        if mode != "off":
            cm.apply_tuned(autotune_model(cm, mode=mode))
    return cm


class CompiledJaxModel:
    """Deployment wrapper for models without a QIR export path: ``offline``
    is the whole forward as one jit program, ``reference`` the eager
    per-layer forward. The four Table-1 models all lower through the real
    compiler now (``export_qmlp``/``export_qcnn`` + ``compile_graph``); this
    stays as the harness for arbitrary research models."""

    def __init__(self, fwd: Callable, params, name: str = "jax"):
        self.name = name
        self.params = params
        self._fwd = fwd
        self._offline = jax.jit(fwd)

    def offline(self, x) -> jnp.ndarray:
        return self._offline(self.params, x)

    def reference(self, x) -> jnp.ndarray:
        return self._fwd(self.params, x)

    def predict(self, x) -> jnp.ndarray:
        return jnp.argmax(self.offline(x), axis=-1)
