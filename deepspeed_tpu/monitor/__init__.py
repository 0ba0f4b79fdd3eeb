"""Monitoring backends (parity: ``deepspeed/monitor/``), the per-subsystem
pipeline counters (``serving.PipelineStats`` / ``training.*Stats``), the
span tracer (``trace.tracer`` — the Perfetto-exportable timeline the counters
are per-window aggregations of; docs/OBSERVABILITY.md), and the live
Prometheus-text telemetry exporter (``export.PrometheusExporter``)."""

from deepspeed_tpu.monitor.export import (PrometheusExporter, TelemetryPump,
                                          sanitize_metric_name)
from deepspeed_tpu.monitor.monitor import (CsvMonitor, Monitor, MonitorMaster,
                                           TensorBoardMonitor, WandbMonitor)
from deepspeed_tpu.monitor.serving import PipelineStats
from deepspeed_tpu.monitor.trace import Capture, Tracer, tracer
from deepspeed_tpu.monitor.training import (CheckpointStats,
                                            OffloadPipelineStats,
                                            RolloutStats,
                                            TrainPipelineStats)

__all__ = ["Monitor", "MonitorMaster", "TensorBoardMonitor", "WandbMonitor",
           "CsvMonitor", "PrometheusExporter", "TelemetryPump",
           "sanitize_metric_name", "PipelineStats", "TrainPipelineStats",
           "OffloadPipelineStats", "CheckpointStats", "RolloutStats",
           "Capture", "Tracer", "tracer"]
