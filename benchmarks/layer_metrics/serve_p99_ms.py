"""99th percentile of the client-side latency samples (the same samples as
``serve_p50_ms``). Source: host clock, client side."""

from benchmarks.harness.stats import percentile


def read(run):
    lat = run.result.get("latencies_s")
    return None if lat is None or not len(lat) else 1e3 * percentile(lat, 99.0)
