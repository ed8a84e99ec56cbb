from repro.metrics import trace
from repro.metrics.logger import MetricsLogger, read_metrics

__all__ = ["MetricsLogger", "read_metrics", "trace"]
