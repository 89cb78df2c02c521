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
    A residual join's skip value (the output of its ``skip_from`` stage)
    stays live inside the segment program, per micro-batch, until the join
    reads it.
  * **streaming_host** — the reference queue-loop pipeline: micro-batches
    flow through per-stage programs connected by bounded queues whose
    capacities are *decided* by ``core.dataflow.optimize_fifo_depths`` — the
    paper's simulate-big/record-max/shrink-to-max+1 pass feeding a real
    execution. Kept for its observable occupancy/backpressure stats; it is
    asserted bit-identical to the compiled path.

The unfused per-node interpreter (``reference``) is kept as the baseline the
benchmarks compare against — it is what running the QIR graph layer by layer
without the compiler looks like.
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
from repro.obs.tracer import NULL_TRACER, phase
from repro.deploy.lower import (
    ConvAddThresholdStage,
    FlattenStage,
    FloatHeadStage,
    FusedConvThresholdStage,
    FusedThresholdStage,
    IntPoolStage,
    IntSumPoolStage,
    MegakernelSegment,
    RefChainStage,
    Segment,
    StageSchedule,
    group_segments,
    lower_graph,
    plan_megakernel,
)

#: Wave size of the streaming entry points when the caller names none.
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
                 megakernel: bool = True,
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
        #: megakernel dispatch: True = fused wherever the residency planner
        #: admits the segment, False = force the per-stage reference path
        self.megakernel = bool(megakernel)
        self.megakernel_budget_bytes = megakernel_budget_bytes
        #: obs.Tracer sink for the wave phases (``executor.pad``,
        #: ``replica.put``, ``executor.launch``), ``stage`` probe spans and
        #: FIFO occupancy counters; NULL_TRACER keeps every span site a no-op
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._rebuild()

    def set_tracer(self, tracer) -> "CompiledTinyModel":
        """Install (or clear, with ``None``) the obs tracer; returns self."""
        self.tracer = tracer if tracer is not None else NULL_TRACER
        return self

    def set_megakernel(self, on: bool, budget_bytes: Optional[int] = None
                       ) -> "CompiledTinyModel":
        """Re-plan megakernel dispatch (``on=False`` forces the staged
        chain) and drop the stale compiled programs; ``budget_bytes``
        overrides the planner's VMEM cap (tests force the fallback with a
        tiny one) and None restores the default cap. Returns self."""
        self.megakernel = bool(on)
        self.megakernel_budget_bytes = budget_bytes
        self._rebuild()
        return self

    def _rebuild(self):
        """(Re)create every compiled entry point from the schedule and the
        megakernel dispatch: called at construction and by
        ``set_megakernel``, whose re-plan leaves the old programs stale."""
        # residency-plan megakernel runs per compiled segment first — the
        # offline and segment programs below dispatch through the plans
        self._mega_plans: Dict[int, MegakernelSegment] = {}
        self._mega_by_start: Dict[int, MegakernelSegment] = {}
        stages = self.schedule.stages
        self.segments: List[Segment] = group_segments(stages)
        #: indices of the stages whose outputs a later join reads: kept live
        self._keep = {s.skip_from for s in stages
                      if isinstance(s, ConvAddThresholdStage)}
        self._skip_row_bytes = 4 * sum(stages[k].out_dim for k in self._keep)
        self.host_segments = self.schedule.host_segments
        #: stages whose kernel runs the O(1) activation (none off the
        #: kernel path)
        self.affine_stages = (self.schedule.affine_stages
                              if self.use_pallas else 0)
        joined = set()
        for k, seg in enumerate(self.segments):
            for i in range(seg.start, seg.stop):
                s = stages[i]
                if isinstance(s, ConvAddThresholdStage):
                    if not seg.start <= s.skip_from < i:
                        raise NotImplementedError(
                            f"join stage {s.name!r} reads stage "
                            f"{s.skip_from}, outside its compiled segment "
                            f"[{seg.start}, {seg.stop})")
                    joined.add(k)
        if self.megakernel:
            for k, seg in enumerate(self.segments):
                if k in joined:
                    continue   # the megakernel plans chains, not joins
                plan = plan_megakernel(
                    self.schedule.stages, seg,
                    budget_bytes=self.megakernel_budget_bytes)
                if plan is not None:
                    self._mega_plans[k] = plan
                    self._mega_by_start[plan.start] = plan
        self._offline = jax.jit(self._run_all)
        self._stage_fns = [jax.jit(self._make_stage_fn(s))
                           for s in stages]
        self._segment_fns: Dict[int, Callable] = {}
        self._plan_cache: Dict[Tuple[int, int], Tuple[List[int], int]] = {}

    #: the wave size ``micro_batch=None`` resolves to (router, pool)
    default_micro_batch = DEFAULT_MICRO_BATCH

    def zero_batch(self, batch: int) -> jnp.ndarray:
        """A zero input batch of ``batch`` rows shaped from the first
        stage: the probe input of the service-time calibration."""
        s0 = self.schedule.stages[0]
        if isinstance(s0, FusedConvThresholdStage):
            g = s0.geom
            return jnp.zeros((batch, g.in_h, g.in_w, g.in_ch), jnp.int32)
        return jnp.zeros((batch, s0.in_dim), jnp.int32)

    # -- single-program (offline) path -----------------------------------
    def _apply_stage(self, s, h, skip=None):
        if isinstance(s, ConvAddThresholdStage):
            if self.use_pallas:
                return s.apply_kernel(h, skip, interpret=self.interpret)
            return s.apply_fast(h, skip)
        if isinstance(s, (FusedThresholdStage, FusedConvThresholdStage)):
            if self.use_pallas:
                return s.apply_kernel(h, interpret=self.interpret)
            return s.apply_fast(h)
        if isinstance(s, (IntPoolStage, IntSumPoolStage, FlattenStage,
                          FloatHeadStage)):
            return s.apply_ref(h)
        if isinstance(s, RefChainStage):
            if jnp.issubdtype(h.dtype, jnp.integer):
                h = h.astype(jnp.float32) * s.in_scale
            return s.apply_ref(h)
        raise TypeError(type(s))  # pragma: no cover

    def _make_stage_fn(self, s) -> Callable:
        """One stage as a function of its input; a join's takes its skip
        value too."""
        if isinstance(s, ConvAddThresholdStage):
            return lambda h, skip: self._apply_stage(s, h, skip)
        return lambda h: self._apply_stage(s, h)

    def _call_stage(self, i: int, h, outs: Dict[int, jnp.ndarray]):
        """Stage i's program on ``h``, a join's with ``outs[skip_from]``;
        keeps the output in ``outs`` where a later join reads it."""
        s = self.schedule.stages[i]
        if isinstance(s, ConvAddThresholdStage):
            y = self._stage_fns[i](h, outs[s.skip_from])
        else:
            y = self._stage_fns[i](h)
        if i in self._keep:
            outs[i] = y
        return y

    def _chain(self, start: int, stop: int, h):
        """Stages [start, stop) traced inline on ``h`` (one micro-batch):
        each join reads its skip value from the outputs kept live here."""
        live: Dict[int, jnp.ndarray] = {}
        for i in range(start, stop):
            s = self.schedule.stages[i]
            skip = (live[s.skip_from] if isinstance(s, ConvAddThresholdStage)
                    else None)
            h = self._apply_stage(s, h, skip)
            if i in self._keep:
                live[i] = h
        return h

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
                # up to the next planned megakernel; no plan lies inside a
                # segment with joins, so a join's source stays in range
                stop = min((p for p in self._mega_by_start if p > i),
                           default=len(stages))
                h = self._chain(i, stop, h)
                i = stop
        return h

    def offline(self, x_int) -> jnp.ndarray:
        """Full batch through the single fused program (MLPerf Offline)."""
        return self._offline(jnp.asarray(x_int))

    def stage_outputs(self, x_int) -> List[jnp.ndarray]:
        """Per-stage outputs (integer codes for fused stages) — the parity
        surface the exactness tests check against the float reference."""
        outs, h, kept = [], jnp.asarray(x_int), {}
        for i in range(len(self._stage_fns)):
            h = self._call_stage(i, h, kept)
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
        breakdown is stable against scheduler noise. Runs the per-stage
        programs in schedule order (each stage's input is the previous stage's real
        output) so conv-vs-dense costs are visible in scenario reports.

        Every timed sample is also recorded as a ``stage`` span on the
        model's tracer; the returned medians are computed from the SAME
        clock readings the spans carry, so
        ``obs.report.stage_medians_ms`` reproduces this breakdown from the
        trace exactly (cross-checked in tests)."""
        tr = self.tracer
        out = []
        h = jnp.asarray(x)
        kept: Dict[int, jnp.ndarray] = {}
        for i, s in enumerate(self.schedule.stages):
            y = self._call_stage(i, h, kept)
            jax.block_until_ready(y)      # compile

            def fn(h, i=i):
                return self._call_stage(i, h, dict(kept))

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
        (``core.dataflow.micro_batch_stage``). Work is parameterized on the
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

        ``micro_batch=None`` resolves to the same default as
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
        #: micro-batch -> the outputs its later joins read
        kept: Dict[int, Dict[int, jnp.ndarray]] = collections.defaultdict(
            dict)
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
                    h = self._call_stage(si, h, kept[idx])
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

        Placement: the wave reaches ``self.device`` in ONE explicit
        ``device_put`` (the ``replica.put`` phase) of the padded
        ``(1, micro_batch, ...)`` host buffer; a full wave of valid rows is
        put as it is, with no copy. Rows that arrive as a device array are
        pulled back to the host first (correct, but a round trip): the
        ``executor.pad`` span says so with ``pulled_back``, beside ``rows``
        (valid), ``pad_rows`` (appended zero rows) and ``put_bytes``.
        """
        mb = int(micro_batch) if micro_batch else self.default_micro_batch
        with phase(self.tracer, "executor.pad", cat="executor") as ph:
            pulled_back = isinstance(x_int, jax.Array)
            xb = np.asarray(x_int)
            n = xb.shape[0]
            if n > mb:
                raise ValueError(
                    f"wave of {n} rows exceeds micro_batch={mb}")
            mask = np.ones(n, bool) if valid is None \
                else np.asarray(valid, bool).reshape(-1)
            if mask.shape[0] != n:
                raise ValueError(f"valid mask has {mask.shape[0]} entries "
                                 f"for a wave of {n} rows")
            mask = np.concatenate([mask, np.zeros(mb - n, bool)])
            n_valid = int(mask.sum())
            if n_valid == mb:
                buf = xb
            else:
                # pad + zero invalid rows on the HOST: the device only
                # ever sees the one constant (1, mb, ...) wave shape, so a
                # lane serving every fill level reuses a single compiled
                # program — eager device-side padding would trace a new
                # program per fill level, which is a mid-serve compile
                # stall (a measured 20x wave-time tail before this was
                # moved host-side)
                buf = np.zeros((mb,) + xb.shape[1:], xb.dtype)
                buf[:n] = xb
                buf[~mask] = 0
            ph.set(rows=n_valid, pad_rows=mb - n, put_bytes=int(buf.nbytes),
                   pulled_back=pulled_back)
        with phase(self.tracer, "replica.put", cat="executor"):
            wave = jax.device_put(buf[None], self.device)
        try:
            with phase(self.tracer, "executor.launch",
                       cat="executor") as ph:
                ph.set(host_segments=self.host_segments,
                       skip_bytes=self._skip_row_bytes * mb,
                       affine_stages=self.affine_stages)
                # squeeze, not ``[0]``: an eager integer index sends its
                # start index to the device, a second host transfer
                y = jnp.squeeze(self._run_segments(wave, 1), 0)
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
        return y, mask

    def _run_segments(self, wave, n_micro: int):
        """Push a stacked wave through every segment program: one jit
        program per compiled segment, the fallback interpreter per
        micro-batch at a host boundary. Under async dispatch this returns
        once every program is dispatched, not when the device is done."""
        for k, seg in enumerate(self.segments):
            if seg.compiled:
                wave = self._segment_fn(k)(wave)
            else:
                # host boundary: the fallback interpreter, per micro-batch
                outs = [wave[i] for i in range(n_micro)]
                for si in range(seg.start, seg.stop):
                    outs = [self._stage_fns[si](h) for h in outs]
                wave = jnp.stack(outs)
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

            if plan is None:
                def run_wave(wave):
                    return jax.lax.map(
                        lambda h: self._chain(seg.start, seg.stop, h), wave)
            else:
                def run_wave(wave):
                    if plan.start > seg.start:
                        wave = jax.lax.map(
                            lambda h: self._chain(seg.start, plan.start, h),
                            wave)
                    n_micro, mb = wave.shape[0], wave.shape[1]
                    flat = wave.reshape((n_micro * mb,) + wave.shape[2:])
                    flat = self._apply_mega(plan, flat)
                    wave = flat.reshape((n_micro, mb) + flat.shape[1:])
                    if seg.stop > plan.stop:
                        wave = jax.lax.map(
                            lambda h: self._chain(plan.stop, seg.stop, h),
                            wave)
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
        schedule. ``micro_batch=None`` uses ``DEFAULT_MICRO_BATCH``.
        """
        mb = int(micro_batch) if micro_batch else self.default_micro_batch
        x_int, n, n_micro = self._pad_micro(x_int, mb)
        depths, sim_cycles = self.plan_streaming(n_micro, micro_batch=mb)
        wave = x_int.reshape((n_micro, mb) + x_int.shape[1:])
        with phase(self.tracer, "executor.launch", cat="executor") as ph:
            ph.set(host_segments=self.host_segments,
                   skip_bytes=self._skip_row_bytes * n_micro * mb,
                   affine_stages=self.affine_stages)
            wave = self._run_segments(wave, n_micro)
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
                  megakernel: bool = True,
                  tracer=None) -> CompiledTinyModel:
    """The one-call deployment entry point: QIR json graph -> executor.

    ``conv_lowering`` picks the conv stage algorithm ("direct" fused kernel
    by default, "im2col" fallback) for both offline and streaming modes —
    the stage methods the executor dispatches through carry the choice.
    ``use_pallas`` defaults to the kernel path on a TPU and to the XLA
    path elsewhere; ``interpret=True`` runs the kernel path's Pallas
    kernels through the interpreter (CPU tests).
    ``megakernel=True`` lets the residency planner fuse each segment's
    dense run into one resident program where it admits one
    (``docs/megakernel.md``); False forces the staged chain.
    """
    schedule = lower_graph(graph, in_scale=in_scale,
                           conv_lowering=conv_lowering)
    return CompiledTinyModel(schedule, graph=graph, use_pallas=use_pallas,
                             interpret=interpret, megakernel=megakernel,
                             tracer=tracer)


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
