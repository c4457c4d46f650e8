"""dmlc_tpu.telemetry: spans, histograms, exporters, cluster aggregation.

The observability subsystem (successor of the flat ``dmlc_tpu.metrics``
counters, which remains as a compatible shim over this package):

  * ``core``       counters / gauges / fixed-bucket histograms with
                   p50/p90/p99 summaries, plus a nested thread-aware
                   span tracer in a bounded ring buffer
  * ``exporters``  Chrome trace-event JSON (Perfetto-loadable),
                   Prometheus text exposition, JSON snapshot embedding
  * ``heartbeat``  worker heartbeats over the rendezvous protocol,
                   tracker-side aggregation, /metrics + /healthz +
                   /trace HTTP, straggler flagging
  * ``clock``      NTP-style per-rank clock-offset estimation (one
                   cluster timeline from N uncorrected wall clocks)
  * ``flight``     tracker-side flight recorder: per-rank span store,
                   clock-corrected merged Chrome trace (/trace)
  * ``events``     bounded structured event log (retries, faults,
                   restarts, declared-dead, barrier entries)
  * ``postmortem`` crash dumps (snapshot + open/last spans + event
                   tail) to DMLC_POSTMORTEM_DIR on signals/fatals
  * ``steps``      per-step performance ledger: wall-time attribution
                   (feed-wait / host-collective / device-compute),
                   goodput tokens/s and MFU per step, shipped with
                   heartbeats
  * ``anomaly``    tracker-side online watchdog over shipped step
                   records (stragglers, regressions, feed-stall
                   dominance, goodput collapse) behind /anomalies
  * ``requests``   serving request ledger: per-request lifecycle
                   (queue/prefill/TTFT/TBT/preempt/finish-with-reason)
                   with per-request /trace rows and a decode-iteration
                   ring behind the serving /requests endpoint
  * ``slo``        declarative serving SLOs (DMLC_SLO_*) evaluated as
                   multi-window burn rates behind /slo; violations
                   flow into the watchdog's anomaly surface
  * ``compute``    compute observability: profiled_jit compile ledger
                   (hit/trace/recompile counting, storm detection),
                   XLA cost/roofline accounting, per-device HBM
                   gauges, decode phase decomposition behind /compute
  * ``tracecontext`` fleet-wide distributed tracing: X-DMLC-Trace
                   context propagation (trace ids deterministic from
                   idempotency request_ids), the cluster-brain
                   decision audit log behind the router's /decisions,
                   and cross-process trace assembly (/trace,
                   /trace/<id>, /traces) behind DMLC_TRACE_FLEET=1
  * ``goodput``    job-level goodput/badput ledger: the entire wall
                   clock partitioned into productive vs. named badput
                   buckets (startup/compile/feed/checkpoint/resize/
                   rollback/preempted), cluster aggregation behind
                   /goodput, and the serving availability twin
  * ``forensics``  incident reports: badput episodes joined with the
                   decision log, events and anomaly flags into
                   postmortem timelines behind /incidents
  * ``metric_names`` the checked-in metric-name contract registry
                   (scripts/lint.py enforces it)

Typical use::

    from dmlc_tpu import telemetry

    telemetry.step_begin()
    ...train step...
    telemetry.step_end(tokens=batch * seq)
    telemetry.snapshot()["histograms"]["feed"]["producer_stall_secs"]["p90"]
    open("trace.json", "w").write(telemetry.to_chrome_trace_json())
"""

from . import (  # noqa: F401
    anomaly,
    clock,
    compute,
    core,
    events,
    exporters,
    flight,
    forensics,
    goodput,
    heartbeat,
    metric_names,
    postmortem,
    requests,
    slo,
    steps,
    tracecontext,
)
from .anomaly import Watchdog  # noqa: F401
from .clock import ClockOffsetEstimator  # noqa: F401
from .core import (  # noqa: F401
    DEFAULT_BOUNDS,
    Histogram,
    anchor_epoch,
    annotate,
    counters_snapshot,
    inc,
    observe,
    observe_duration,
    open_spans,
    record_span,
    reset,
    set_gauge,
    snapshot,
    span,
    spans,
    spans_since,
    timed,
)
from .events import (  # noqa: F401
    events_tail,
    record_event,
    reset_events,
)
from .flight import FlightRecorder  # noqa: F401
from .tracecontext import (  # noqa: F401
    DecisionLog,
    FleetTraceStore,
    decision_log,
    record_decision,
)
from .requests import RequestLedger  # noqa: F401
from .slo import SLOMonitor  # noqa: F401
from .exporters import (  # noqa: F401
    export_json,
    to_chrome_trace,
    to_chrome_trace_json,
    to_prometheus_text,
)
from .heartbeat import (  # noqa: F401
    DEFAULT_STRAGGLER_KEYS,
    HeartbeatSender,
    TelemetryAggregator,
    TelemetryHTTPServer,
)
from .compute import (  # noqa: F401
    profiled_jit,
    reset_compute,
)
from .goodput import (  # noqa: F401
    AvailabilityLedger,
    GoodputAggregator,
    GoodputLedger,
    reset_goodput,
)
from .forensics import (  # noqa: F401
    IncidentReporter,
    build_incidents,
)
from .steps import (  # noqa: F401
    StepLedger,
    declare_dtype,
    declare_flops_per_token,
    declare_peak_flops,
    detect_peak_flops,
    detect_peaks,
    ledger,
    reset_steps,
    step_begin,
    step_end,
)

__all__ = [
    "AvailabilityLedger",
    "ClockOffsetEstimator",
    "DEFAULT_BOUNDS",
    "DEFAULT_STRAGGLER_KEYS",
    "DecisionLog",
    "FleetTraceStore",
    "FlightRecorder",
    "GoodputAggregator",
    "GoodputLedger",
    "Histogram",
    "HeartbeatSender",
    "IncidentReporter",
    "RequestLedger",
    "SLOMonitor",
    "StepLedger",
    "TelemetryAggregator",
    "TelemetryHTTPServer",
    "Watchdog",
    "anchor_epoch",
    "annotate",
    "build_incidents",
    "counters_snapshot",
    "decision_log",
    "declare_dtype",
    "declare_flops_per_token",
    "declare_peak_flops",
    "detect_peak_flops",
    "detect_peaks",
    "events_tail",
    "export_json",
    "inc",
    "ledger",
    "observe",
    "observe_duration",
    "open_spans",
    "profiled_jit",
    "record_decision",
    "record_event",
    "record_span",
    "reset",
    "reset_compute",
    "reset_events",
    "reset_goodput",
    "reset_steps",
    "set_gauge",
    "snapshot",
    "span",
    "spans",
    "spans_since",
    "step_begin",
    "step_end",
    "timed",
    "to_chrome_trace",
    "to_chrome_trace_json",
    "to_prometheus_text",
]
