"""95th percentile of time per output token above the knee, taken as the
end-to-end tpot_p85_ms is, at the 95th percentile.  Recorded, not
judged.  Layer: serving scheduler."""
from benchmarks.chip.common import percentile


def read(r):
    xs = r.latency.get("tpot")
    return percentile(xs, 95) if xs else None
