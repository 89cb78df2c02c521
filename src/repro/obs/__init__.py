"""repro.obs — end-to-end tracing, counters, and timeline export.

The observability layer under the compile→execute→serve stack
(``docs/observability.md``): one injectable process-wide timer
(``obs.timer`` — the only place raw clocks live, enforced by
``scripts/check_no_raw_clock.py``), a ring-buffered thread-safe ``Tracer``
with spans / instant events / counter series and a near-zero-overhead
``NullTracer`` default, exporters to Chrome trace-event JSON (load in
Perfetto: pid per replica, tid per lane/segment/FIFO) and flat JSONL, and
span-derived reports — latency percentiles that must match the serve
metrics to the bit, and the FIFO-model predicted-vs-measured service-time
table that seeds the learned cost model (ROADMAP direction 5).

    from repro.obs import Tracer, export_chrome
    tracer = Tracer()                       # or Tracer(clock=ManualClock())
    router = Router({"ic": cm}, cfg, tracer=tracer)
    router.run_trace("ic", poisson_trace(200, 512), make_query)
    export_chrome(tracer, "serve_trace.json")   # open in ui.perfetto.dev
"""

from repro.obs import timer  # noqa: F401
from repro.obs.export import (  # noqa: F401
    chrome_events,
    chrome_json,
    export_chrome,
    export_jsonl,
    jsonl_lines,
)
from repro.obs.report import (  # noqa: F401
    latency_percentiles,
    prediction_error,
    prediction_records,
    request_latencies_ms,
    stage_medians_ms,
)
from repro.obs.tracer import (  # noqa: F401
    COUNTER,
    INSTANT,
    NULL_TRACER,
    SPAN,
    NullTracer,
    TraceEvent,
    Tracer,
    phase,
    wave_scope,
)
