"""Metric log pipeline (reference: ``core:node/metric/`` — SURVEY.md §2.1
"Metric log pipeline", §3.5): per-second aggregation of every resource to a
rotating log + index, and range reads for the ops plane.
"""

from sentinel_tpu.metrics.metric_node import MetricNode
from sentinel_tpu.metrics.profiling import StepTimer
from sentinel_tpu.metrics.searcher import MetricSearcher
from sentinel_tpu.metrics.timer import MetricTimerListener
from sentinel_tpu.metrics.writer import MetricWriter

__all__ = ["MetricNode", "MetricSearcher", "MetricTimerListener",
           "MetricWriter", "StepTimer"]
