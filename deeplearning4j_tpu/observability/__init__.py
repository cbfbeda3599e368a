"""Unified telemetry substrate: metrics registry, span tracing,
Prometheus exposition, trace export.

One low-overhead layer beneath every workload (training, serving,
checkpointing, resilience) — the TPP-style uniform instrumentation
argument applied to this stack. See metrics.py and tracing.py module
docstrings for the design; README "Observability" for the operator
recipes (scrape /metrics, export a Perfetto trace)."""

from deeplearning4j_tpu.observability.metrics import (  # noqa: F401
    DERIVED_METRICS,
    MetricsRegistry,
    REGISTERED_METRICS,
    StepAccumulator,
    count,
    count_observe,
    enable,
    gauge_fn,
    get_registry,
    observe,
    parse_prometheus,
    parse_prometheus_snapshot,
    set_gauge,
    telemetry_enabled,
)
from deeplearning4j_tpu.observability.metrics import (  # noqa: F401
    render_prometheus,
)
from deeplearning4j_tpu.observability.perf import (  # noqa: F401
    StepPhaseProfiler,
    aggregate_prometheus_text,
    aggregate_snapshots,
    dump_snapshot,
)
from deeplearning4j_tpu.observability.tracing import (  # noqa: F401
    Span,
    Tracer,
)
from deeplearning4j_tpu.observability.telemetry import (  # noqa: F401
    TelemetryListener,
)
