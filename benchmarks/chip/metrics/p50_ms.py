"""Median latency of every query due in the window, from due to ids on
the host (open loop)."""
from chipbench.stats import percentile


def read(rec):
    lat = rec.get("latencies_s")
    if lat is None or len(lat) == 0:
        return None
    return 1e3 * percentile(lat, 50), "ms"
