"""Observability: metrics registry + request-lifecycle tracing.

The reference's only observability is a windowed worker ops/s log line
(worker.rs:254-283, SURVEY §5). This package gives the serving stack a
real measurement substrate, dependency-free:

  * `obs.metrics` — a Prometheus-style registry (`Counter`, `Gauge`,
    `Histogram`, all with label support) rendering the text exposition
    format; `ApiServer.metrics()` serves it at `/api/v1/metrics` and
    `/metrics`.
  * `obs.tracing` — per-request lifecycle traces: timestamped spans
    (admitted → queued → prefill → first_token → decode → retired /
    error / cancelled) with queue-wait, prefill seconds, TTFT,
    inter-token gaps and e2e latency, kept in a bounded ring, dumpable
    via `GET /api/v1/requests`, optionally streamed to a JSONL event
    log (`--trace-events PATH`).
  * `obs.steps` — step-level performance telemetry: a bounded step
    flight recorder (`GET /api/v1/steps`, `--step-log PATH` JSONL),
    XLA cost-analysis MFU / HBM-utilization accounting, jit-recompile
    counters, per-device HBM gauges, and the single-flight live
    profiler capture behind `POST /api/v1/profile`. Each step record
    also carries `phases` (host seconds by engine-loop phase —
    admin / schedule / build / dispatch / sample / fetch / emit —
    since the previous record) and `gap_s` (previous fetch end to this
    dispatch start: the device with nothing queued); the same phases
    are `cake/<phase>` TraceAnnotations with the step number in a
    capture (`StepTelemetry.span`).
  * `obs.startup` — what is read of start-up's own clock
    (`cake_tpu/startup.py`: named phases from the process's start to
    the first healthy answer): `cake_startup_phase_seconds{phase}`,
    the `startup` block of `/api/v1/health`; and the seconds that go
    into making programs, from `jax.monitoring` (`cake_jit_trace/
    lower/backend/cost_analysis_seconds_total`, cache hits and
    misses).
  * `obs.events` — the cross-subsystem event bus: typed,
    request-linked events (preempted, kv_spill/kv_restore, prefix_hit,
    recovered/poisoned, reconfigured, shed, fault_injected, recompile)
    in a bounded ring at `GET /api/v1/events` with an optional
    `--event-log` JSONL sink.
  * `obs.timeline` — the per-request explain: one merged time-ordered
    view of a request's trace spans, bus events and step records
    (`GET /api/v1/requests/{rid}/timeline`).
  * `obs.slo` — SLO attainment + goodput accounting (`--slo-targets`):
    rolling per-class attainment gauges, burn-rate counters, and
    goodput (tokens from requests that met their class SLO) feeding
    the autotune controller's quality signals.
  * `obs.sentinel` — the online performance-regression sentinel
    (`--sentinel`): rolling-window anomaly detectors with hysteresis
    over the live signal stream (step-time p95 vs self-calibrated
    baseline, recompile/spill/shed storms, attainment collapse,
    router replica skew), emitting typed `anomaly` events,
    `cake_anomaly_*` metrics and `GET /api/v1/anomalies`.
  * `obs.jsonl` — the shared append-only JSONL writer (fsync on close)
    and corrupt-tail-tolerant reader all three event logs use.
  * `obs.federation` — fleet-scope telemetry federation: each
    non-coordinator process runs a `TelemetryExporter` shipping its
    metrics/events/step summaries/applied control seq to the
    coordinator's `TelemetryCollector` (token-gated length-prefixed
    JSON frames with a clock sample), powering `GET /api/v1/fleet`,
    `?host=` event filters, host-labeled federated `/metrics`
    families, and cross-host request timelines.
"""

from cake_tpu.obs.events import EVENT_TYPES, Event, EventBus  # noqa: F401
from cake_tpu.obs.federation import (  # noqa: F401
    TelemetryCollector, TelemetryExporter,
)
from cake_tpu.obs.jsonl import JsonlAppender, read_jsonl  # noqa: F401
from cake_tpu.obs.metrics import (  # noqa: F401
    REGISTRY, Counter, Gauge, Histogram, Registry, counter, gauge,
    histogram,
)
from cake_tpu.obs.sentinel import (  # noqa: F401
    BaselineDetector, Sentinel, ThresholdDetector,
    attach_engine_sentinel, attach_router_sentinel,
)
from cake_tpu.obs.slo import (  # noqa: F401
    DEFAULT_TARGETS, SLOAccountant, SLOTarget, parse_slo_targets,
)
from cake_tpu.obs.timeline import (  # noqa: F401
    build_timeline, merge_router_timeline,
)
from cake_tpu.obs.tracing import RequestTracer, TraceRecord  # noqa: F401
