"""95th percentile, over every request that arrived in the window, of the
time from when it was due to the start of the engine.step that admitted
it (host clock).  A request not admitted by the window's end counts at its
wait so far.  Layer: serving scheduler."""
from benchmarks.chip.common import percentile


def read(r):
    waits = r.latency.get("queue_wait")
    return percentile(waits, 95) if waits else None
