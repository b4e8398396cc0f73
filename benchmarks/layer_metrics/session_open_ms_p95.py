"""95th percentile of ``decoder.open()`` as the client sees it (slot claim
plus ``_reset_slot``'s host round trip under the net lock). Source: host
clock, client side."""

from benchmarks.harness.stats import percentile


def read(run):
    opens = run.result.get("open_seconds")
    return None if opens is None or not len(opens) \
        else 1e3 * percentile(opens, 95.0)
