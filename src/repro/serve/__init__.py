"""repro.serve — SLO-aware dynamic-batching inference server runtime.

The layer between request traffic and the compiled streaming pipeline
(``repro.deploy``): a dynamic batcher (``router``) coalesces arriving
requests into padded micro-batch waves and dispatches them through the
executor's compiled segment programs (``CompiledTinyModel.submit_wave``),
a replica pool (``replica``) places waves across devices by least
outstanding work, an injectable dispatch engine (``dispatch``) decides
whether waves block in the submit path (``SyncEngine`` — the exact
discrete-event default) or overlap across replicas through an in-flight
table (``AsyncEngine`` — JAX async dispatch, completions reaped by the
event loop), an admission controller (``slo``) sheds load before the
p99 budget blows using the FIFO cost model calibrated by measured stage
latencies, traffic generators (``traffic``) produce seedable
Poisson/bursty/diurnal/replay arrival traces, and sliding-window metrics
(``metrics``) report percentiles, throughput, shed rate, and wave
occupancy. A seedable fault-injection plane (``faults``) drives wave
timeouts, replica crashes/slowdowns, corrupt outputs, and transient
submit errors through a deterministic schedule, and the router answers
with wave deadlines, bounded retries, a replica health state machine,
and an output integrity guard — see ``docs/faults.md``.
Everything reads time through an injectable clock (``clock``),
so the whole server is a deterministic discrete-event system under
``ManualClock`` — see ``docs/serving.md``.

    from repro.serve import Router, RouterConfig, poisson_trace
    router = Router({"ic": compiled}, RouterConfig(p99_budget_ms=50.0))
    done = router.run_trace("ic", poisson_trace(qps=200, n=512), make_query)
"""

from repro.serve.clock import ManualClock, SystemClock  # noqa: F401
from repro.serve.dispatch import (  # noqa: F401
    AsyncEngine,
    DispatchEngine,
    SyncEngine,
    WaveHandle,
)
from repro.serve.faults import (  # noqa: F401
    DEFAULT_OUTPUT_BOUND,
    CorruptWave,
    FaultError,
    FaultPlan,
    FaultSpec,
    FaultyModel,
    NoReplicaAvailable,
    ReplicaCrashed,
    TransientSubmitError,
    WaveError,
    WaveTimeout,
    faulty_pool,
    wave_integrity_ok,
)
from repro.serve.metrics import (  # noqa: F401
    MetricsSnapshot,
    ServeMetrics,
)
from repro.serve.replica import Replica, ReplicaPool  # noqa: F401
from repro.serve.router import (  # noqa: F401
    Router,
    RouterConfig,
    ServeRequest,
)
from repro.serve.slo import (  # noqa: F401
    ServiceModel,
    SLOController,
    measure_wave_service_s,
    queued_waves,
    slo_operating_point,
)
from repro.serve.traffic import (  # noqa: F401
    GENERATORS,
    Trace,
    diurnal_trace,
    mmpp_trace,
    poisson_trace,
    replay_trace,
)
