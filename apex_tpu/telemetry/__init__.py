"""Training telemetry: metrics bus, goodput accounting, flight recorder.

The *online* observability layer (ISSUE 4) — the offline half (trace
capture, per-op attribution) is :mod:`apex_tpu.profiling`:

- **bus** — :class:`TelemetryBus` with a closed set of typed events
  (:data:`EVENT_TYPES`) and pluggable sinks (:class:`JsonlSink`,
  :class:`MemorySink`, :class:`StdoutSink`); every event stamped with
  run id, step, monotonic time, and mesh topology;
- **accounting** — :class:`StepAccountant` splits wall time into
  data-wait / step / checkpoint-fence (+ restore / rebuild / compile)
  buckets, batches scalar fetches into one ``device_get`` per logging
  window, and computes **goodput** (productive-step fraction);
- **flight recorder** — :class:`FlightRecorder` ring of the last N
  events, flushed to ``postmortem_*.jsonl`` on SIGTERM, watchdog
  escalation, or device loss (``bus.flush_postmortem``);
- **phases** — :func:`phase` (ISSUE 27): host-phase spans as
  ``apex:<name>`` ``TraceAnnotation``s on the profiler's clock, every
  one also a :class:`PhaseRecord` in :data:`PHASE_RING`, the one
  bounded in-memory ring (the serving engine's step phases; the
  benchmark's per-layer readers cut their window out of it);
- **scopes** — :mod:`apex_tpu.telemetry.scopes` (ISSUE 37): the device
  half of the phases.  :func:`register` takes every executable the hot
  path compiles at warm-up (a dict entry); :func:`scope_maps`
  turns each, on request, into a map from optimized-HLO instruction to
  the ``named_scope`` path it came from, and :func:`by_scope` sums a
  profile's device events by it (``python -m apex_tpu.telemetry scopes``);
- **schema** — :func:`validate_event` / :func:`validate_jsonl`, the
  CI-side contract every producer is tested against;
- **sampler** — :class:`ProfileSampler` (ISSUE 9): periodic in-run
  capture + phase/collective/HBM attribution through the bus
  (``profile``/``memory`` events), overhead booked to its own goodput
  bucket and budget-bounded ≤1%;
- **tracing** — :mod:`apex_tpu.telemetry.tracing` (ISSUE 19):
  request-scoped causal spans over the fleet (``span`` events; trace
  id = fleet rid), reconstruction of per-request span trees from any
  set of per-replica streams, critical-path extraction, TTFT
  decomposition, and the fleet flight recorder
  (:func:`~apex_tpu.telemetry.tracing.maybe_dump_flight_record`);
- **CLI** — ``python -m apex_tpu.telemetry summarize run.jsonl``
  (p50/p95/p99 step time, goodput %, phase breakdown, event counts,
  ``--diff`` A/B; ``trace STREAM.jsonl...`` — span-tree
  reconstruction + TTFT decomposition).

See ``docs/telemetry.md`` for the event schema and wiring examples.
"""

from apex_tpu.telemetry.accounting import (  # noqa: F401
    PAUSE_KINDS,
    StepAccountant,
)
from apex_tpu.telemetry.bus import (  # noqa: F401
    EVENT_TYPES,
    JsonlSink,
    MemorySink,
    StdoutSink,
    TelemetryBus,
    TelemetryError,
    default_mesh_topology,
    install_recompile_listener,
)
from apex_tpu.telemetry.phases import (  # noqa: F401
    PHASE_RING,
    PhaseRecord,
    phase,
)
from apex_tpu.telemetry.recorder import FlightRecorder  # noqa: F401
from apex_tpu.telemetry.scopes import (  # noqa: F401
    ScopeMap,
    by_scope,
    register,
    scope_maps,
)
from apex_tpu.telemetry.sampler import (  # noqa: F401
    JaxProfilerTracer,
    ProfileSampler,
    device_memory_payload,
)
from apex_tpu.telemetry.schema import (  # noqa: F401
    SchemaError,
    load_jsonl,
    validate_event,
    validate_events,
    validate_jsonl,
)
from apex_tpu.telemetry.summarize import (  # noqa: F401
    format_diff,
    format_summary,
    summarize_events,
    summarize_file,
)
from apex_tpu.telemetry.tracing import (  # noqa: F401
    SPAN_KINDS,
    TTFT_SUM_TOLERANCE_MS,
    Span,
    Trace,
    admission_life,
    build_traces,
    critical_path,
    load_trace_streams,
    maybe_dump_flight_record,
    run_trace_cli,
    ttft_decomposition,
    validate_trace,
)

__all__ = [
    "EVENT_TYPES",
    "FlightRecorder",
    "PHASE_RING",
    "PhaseRecord",
    "phase",
    "ScopeMap",
    "by_scope",
    "register",
    "scope_maps",
    "SPAN_KINDS",
    "Span",
    "TTFT_SUM_TOLERANCE_MS",
    "Trace",
    "admission_life",
    "build_traces",
    "critical_path",
    "load_trace_streams",
    "maybe_dump_flight_record",
    "run_trace_cli",
    "ttft_decomposition",
    "validate_trace",
    "JsonlSink",
    "MemorySink",
    "PAUSE_KINDS",
    "SchemaError",
    "StdoutSink",
    "StepAccountant",
    "TelemetryBus",
    "TelemetryError",
    "default_mesh_topology",
    "format_diff",
    "format_summary",
    "install_recompile_listener",
    "JaxProfilerTracer",
    "ProfileSampler",
    "device_memory_payload",
    "load_jsonl",
    "summarize_events",
    "summarize_file",
    "validate_event",
    "validate_events",
    "validate_jsonl",
]
