"""Observability subsystem: request-scoped tracing, engine step telemetry,
and the flight recorder.

- ``obs.trace``     dependency-free spans, contextvar propagation, W3C
                    ``traceparent`` ingest/emit, jax.profiler annotations
- ``obs.steploop``  per-engine-step gauges/counters + TTFT/TPOT/queue-wait
                    histograms with explicit buckets (stdlib-only; the
                    serve layer adapts them to Prometheus/JSON lines)
- ``obs.flight``    bounded ring buffers of recent request timelines and
                    engine-step records, dumped by ``GET /debug/flight``
                    and served per-trace by ``GET /trace/{trace_id}``
- ``obs.autopsy``   cross-pod trace assembly + per-category latency
                    attribution (the ``/trace/{id}`` fleet autopsy)
- ``obs.hbm``       live HBM ledger: per-pool byte attribution, headroom/
                    fragmentation gauges, steady-state leak drift detector
- ``obs.slo``       per-model TTFT/TPOT/error objectives as rolling
                    multi-window burn rates (the failover trigger feed)
- ``obs.sentinel``  live tok/s vs PERF_MODEL.json projection conformance
- ``obs.stops``     when the process did not run: garbage-collection pauses
                    from ``gc.callbacks``, whole-process stops from a
                    heartbeat thread, each with its cause

Layering: ``obs`` imports nothing from the rest of the package (and no
third-party deps), so engine AND serve may both depend on it.
"""

# NOTE: the ``autopsy`` FUNCTION is deliberately not re-exported here —
# it would shadow the ``obs.autopsy`` submodule attribute that cova and
# the CLI import as a module (``from ..obs import autopsy``)
from .autopsy import assemble, format_report  # noqa: F401
from .flight import FlightRecorder  # noqa: F401
from .hbm import DriftDetector, HbmLedger  # noqa: F401
from .sentinel import PerfSentinel  # noqa: F401
from .slo import SloEngine, SloTargets  # noqa: F401
from .stops import ProcessStops  # noqa: F401
from .steploop import (  # noqa: F401
    BucketHistogram,
    QUEUE_WAIT_BUCKETS,
    StepTelemetry,
    TPOT_BUCKETS,
    TTFT_BUCKETS,
)
from .trace import (  # noqa: F401
    Trace,
    annotate,
    begin_request_trace,
    configure,
    current_span,
    current_trace,
    current_traceparent,
    enabled,
    format_traceparent,
    parse_traceparent,
    span,
    use_trace,
    well_formed_problems,
)
