from repro.runtime.knn_server import KnnServer, QueryResult, ServerStats
from repro.runtime.metrics import MetricLogger, StepWatchdog

__all__ = ["KnnServer", "QueryResult", "ServerStats", "MetricLogger",
           "StepWatchdog"]
