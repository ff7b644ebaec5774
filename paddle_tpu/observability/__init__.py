"""Unified runtime telemetry: metrics registry, trace spans, exporters.

The one pipe every subsystem reports through (reference analog:
platform/monitor.h STATS_INT + the host profiler, fused):

  * ``metrics`` — process-wide Counter / Gauge / Histogram registry with
    labeled series; counters ride the C++ stat tier when available.
  * ``tracing`` — nested, context-propagated spans that feed the
    profiler's chrome-trace recorder, span-duration histograms and, as
    ``jax.profiler.TraceAnnotation``s, the JAX profiler's trace: the
    phase spans of the serving and train steps lie there on the clock of
    the device events (read one by hand with
    ``python -m chipbench.phases <trace>``).
  * ``export`` — Prometheus text format + JSONL snapshots
    (``tools/telemetry_dump.py`` is the CLI over these).
  * ``fleet`` — rank-sharded telemetry spools under
    ``PADDLE_TELEMETRY_DIR`` + cross-rank aggregation with typed
    straggler/desync/missing-rank findings (``telemetry_dump --fleet``).
  * ``flight`` — crash-surviving per-rank binary ring journal, replayed
    by ``tools/blackbox.py postmortem``.
  * ``waterfall`` / ``ledger`` / ``anomaly`` — the attribution layer:
    per-request critical-path waterfalls reconstructed from recorded
    spans, the fleet goodput ledger (chip-seconds by tenant/rung/phase
    with typed waste categories), and streaming EWMA/MAD detectors over
    per-replica TTFT/TPOT/queue-depth emitting ``FleetFinding``s
    (``tools/trace_analyze.py`` is the CLI over all three).
  * ``opprof`` — compiled-program cost profiles: per-op/per-fusion
    FLOPs and bytes parsed from the optimized HLO of every warm
    executable (TrainStep, serving prefill/decode), a shared op-class
    taxonomy, per-op-class
    MFU-gap attribution, and ``OPPROF_r*.json`` artifacts with a
    ``diff()`` that names recompiles and fusion regressions
    (``tools/profile_report.py`` is the CLI; the bench_guard
    ``opprof:`` lane is the gate).

Instrumented out of the box: serving batchers (queue depth, admissions,
preemptions, TTFT / per-token latency), the multi-replica serving
gateway (``gateway.*``: routing affinity hits, per-tenant sheds,
requeues off dead replicas, end-to-end TTFT/TPOT — dump with
``tools/telemetry_dump.py --prefix gateway.``), collectives
(bytes/count/latency per op), the hapi training loop (step time,
tokens/sec, MFU), the Pallas flash-attention autotune cache, and the
static-analysis passes (``analysis.findings{rule=...}`` — every DF/SH/MEM
diagnostic pass counts its findings by rule here).
"""
from __future__ import annotations

from . import (anomaly, export, fleet, flight, ledger, metrics, opprof,
               roofline_attr, slo, trace_context, tracing, waterfall)
from .opprof import OpProfile, classify_op
from .anomaly import AnomalyDetector, GatewayProbe
from .export import load_jsonl, render_prometheus, write_jsonl
from .fleet import (FleetAggregator, FleetFinding, ProcessIdentity,
                    TelemetrySpool, get_spool, process_identity)
from .ledger import GoodputLedger, ledger_from_waterfalls
from .flight import (FlightRecorder, build_postmortem, flight_record,
                     get_flight, read_ring)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      get_registry)
from .slo import (SLO, Alert, BurnWindow, Resolved, SLOMonitor,
                  default_gateway_slos)
from .trace_context import (TraceContext, TraceRecorder, TraceSpan,
                            get_recorder, new_trace)
from .tracing import (Span, attach_context, capture_context, current_span,
                      span, span_path, traced)
from .waterfall import (Waterfall, build_waterfalls,
                        critical_path_summary, render_waterfall,
                        waterfalls_from_fleet, waterfalls_from_recorder)

__all__ = [
    "metrics", "tracing", "export", "trace_context", "roofline_attr",
    "slo", "fleet", "flight", "waterfall", "ledger", "anomaly",
    "opprof", "OpProfile", "classify_op",
    "Waterfall", "build_waterfalls", "waterfalls_from_recorder",
    "waterfalls_from_fleet", "critical_path_summary", "render_waterfall",
    "GoodputLedger", "ledger_from_waterfalls",
    "AnomalyDetector", "GatewayProbe",
    "FleetAggregator", "FleetFinding", "ProcessIdentity",
    "TelemetrySpool", "get_spool", "process_identity",
    "FlightRecorder", "build_postmortem", "flight_record", "get_flight",
    "read_ring",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "Span", "span", "current_span", "span_path", "capture_context",
    "attach_context", "traced",
    "TraceContext", "TraceSpan", "TraceRecorder", "get_recorder",
    "new_trace",
    "SLO", "Alert", "BurnWindow", "Resolved", "SLOMonitor",
    "default_gateway_slos",
    "render_prometheus", "write_jsonl", "load_jsonl",
]
