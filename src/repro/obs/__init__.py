"""Observability plane: flight recorder, CMP protection gauges, exporters
(DESIGN.md §13).

Zero-added-atomics tracing and metrics over the whole fabric: per-replica
event rings with deterministic head-sampling (``trace_rate``), gauges read
from the domain counters the system already maintains, exporters for
Chrome/Perfetto traces and Prometheus text exposition, and profiler spans
over the served step's phases on the recorder's clock.
Wired end-to-end via ``FabricConfig(obs=ObsConfig(...))``; the
:class:`MetricsHub` rolling window is the future autoscaler's sensor
input (ROADMAP: closed-loop control plane).
"""

from repro.obs.export import (format_class_lines, perfetto_trace,
                              prometheus_text, stage_breakdown)
from repro.obs.gauges import (flatten_gauges, sample_admission_ring,
                              sample_class_shards, sample_cmp_shard,
                              sample_fabric_gauges, sample_transport)
from repro.obs.hub import MetricsHub
from repro.obs.recorder import (CLAIM_BLOCK, COMPLETE, CONTROL,
                                CONTROL_EVENTS, DECODE, DRAIN, FLUSH,
                                LANE_PREFILL, LIFECYCLE_STAGES,
                                PRODUCER_RID, REQUEUE, RESCUE, SEAT,
                                SHARD_ENQUEUE, SPAN_NAMES, STEAL, SUBMIT,
                                WINDOW_ADMIT, FlightRecorder, GcSpans,
                                ObsConfig, sample_stride, span)

__all__ = [
    "ObsConfig", "FlightRecorder", "MetricsHub", "sample_stride",
    "span", "GcSpans", "SPAN_NAMES",
    "LIFECYCLE_STAGES", "CONTROL_EVENTS", "PRODUCER_RID",
    "SUBMIT", "WINDOW_ADMIT", "SHARD_ENQUEUE", "DRAIN", "SEAT",
    "LANE_PREFILL", "DECODE", "COMPLETE",
    "STEAL", "REQUEUE", "RESCUE", "CLAIM_BLOCK", "FLUSH", "CONTROL",
    "perfetto_trace", "prometheus_text", "stage_breakdown",
    "format_class_lines",
    "sample_cmp_shard", "sample_class_shards", "sample_admission_ring",
    "sample_transport", "sample_fabric_gauges", "flatten_gauges",
]
