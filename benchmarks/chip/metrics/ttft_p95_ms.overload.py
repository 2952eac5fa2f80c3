"""95th percentile of time to first token above the knee, taken as the
end-to-end ttft_p85_ms is, at the 95th percentile.  Above the knee
the queue grows all through the window, so this swings with the smallest
change: recorded, not judged.  Layer: serving scheduler."""
from benchmarks.chip.common import percentile


def read(r):
    xs = r.latency.get("ttft")
    return percentile(xs, 95) if xs else None
