"""95th percentile, over every request submitted in the window, of the
engine's own queue wait: from ``Request.t_submit`` (stamped by
``Engine.submit``) to ``Request.t_admit`` (stamped as its prefill starts),
so the wait behind earlier prefills of the same admission counts.  A
request not admitted by the window's end counts at its wait so far.  None
where the program stamps no request.  Layer: serving scheduler."""
from benchmarks.chip.common import percentile


def read(r):
    w = r.window
    waits = [((r_.req.t_admit or w.t_stop) - r_.req.t_submit) * 1e3
             for r_ in w.recs if r_.req is not None and not r_.req.rejected
             and getattr(r_.req, "t_submit", None) is not None]
    return percentile(waits, 95) if waits else None
