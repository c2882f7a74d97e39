"""tnc_tpu.obs — pipeline tracing + metrics.

Spans have two sinks. While a ``jax.profiler`` session records, every
``obs.span`` is a host annotation ``perf:tnc.<name>`` in the profiler's
trace (the session is the switch). ``TNC_TPU_TRACE`` gates the registry:
unset → spans/counters record nothing in-process; ``1`` → they do;
``TNC_TPU_TRACE=<path>.json`` → additionally auto-export a
Chrome-trace/Perfetto timeline at interpreter exit. With neither on
every API here is a near-zero-cost no-op. See ``docs/observability.md``.
"""

from tnc_tpu.obs.core import (  # noqa: F401
    MetricsRegistry,
    NULL_SPAN,
    PROFILER_SPAN_PREFIX,
    QuantileSummary,
    Span,
    SpanRecord,
    configure,
    counter_add,
    collect_phases,
    counters_by_prefix,
    enabled,
    gauge_set,
    get_registry,
    observe,
    phase,
    process_trace_path,
    profiler_recording,
    refresh_from_env,
    reset,
    span,
    step_timing_enabled,
    trace_args,
    trace_path,
    traced,
)
from tnc_tpu.obs.export import (  # noqa: F401
    chrome_trace_events,
    emit_metrics,
    export_chrome_trace,
    export_jsonl,
    format_serve_rollup,
    format_summary_table,
    load_trace_events,
    merge_trace_files,
    serve_trace_rollup,
    trace_summary,
)
from tnc_tpu.obs.calibrate import (  # noqa: F401
    CalibratedCostModel,
    DeviceModel,
    StepSample,
    calibration_report,
    fit_device_model,
    step_samples,
)
from tnc_tpu.obs.op_table import (  # noqa: F401
    device_op_table,
    parse_hlo_ops,
    step_scope_name,
    step_seconds,
)
from tnc_tpu.obs.slo import (  # noqa: F401
    BurnWindow,
    DriftDetector,
    LatencyObjective,
    SLOConfig,
    SLOEngine,
)
from tnc_tpu.obs.cost_truth import (  # noqa: F401
    CostTruth,
    CostTruthConfig,
    ModelRegistry,
    ModelRegistryWatcher,
    PlanScoreboard,
    ProductionSampler,
    refit_model,
)
# the HTTP endpoint layer re-exports lazily (PEP 562): `from tnc_tpu
# import obs` happens in every module of the library, and only
# telemetry-serving processes should pay the http.server import
_HTTP_EXPORTS = (
    "TelemetryServer",
    "parse_prometheus",
    "parse_prometheus_types",
    "render_prometheus",
)

# the fleet plane (cross-host trace propagation, replica registry,
# federation, flight recorder) re-exports lazily for the same reason
_FLEET_EXPORTS = (
    "FleetAggregator",
    "FleetRegistry",
    "FlightRecorder",
    "Heartbeat",
    "TraceContext",
    "adopt_trace_context",
    "current_dispatch_context",
    "dispatch_context",
    "flight_annotations",
    "flight_recorder",
    "maybe_flight_recorder",
    "merge_fleet_metrics",
    "replica_identity",
    "replica_name",
    "set_flight_annotation",
)


def __getattr__(name: str):
    if name in _HTTP_EXPORTS:
        from tnc_tpu.obs import http as _http

        return getattr(_http, name)
    if name in _FLEET_EXPORTS:
        from tnc_tpu.obs import fleet as _fleet

        return getattr(_fleet, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
