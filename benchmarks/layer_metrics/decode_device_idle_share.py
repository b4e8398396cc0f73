"""``device_idle_share`` for a cell that serves: it moves ``serve_ops_per_s``
there, and a metric names one end-to-end metric. The reader is the same."""

from benchmarks.layer_metrics.device_idle_share import read  # noqa: F401
