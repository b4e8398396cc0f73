"""``peak_hbm_gb`` for a cell that serves: it moves ``serve_ops_per_s``
there. The reader is the same."""

from benchmarks.layer_metrics.peak_hbm_gb import read  # noqa: F401
