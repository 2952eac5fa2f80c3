"""Self time of the ``serve.retire`` spans (the per-slot host reads of each
new token and position, and retiring) per ``serve.decode`` span in the
traced span, in ms.  Layer: serving scheduler."""


def read(r):
    t = r.trace or {}
    n = t.get("span_n", {}).get("serve.decode")
    retire = t.get("span_self_s", {}).get("serve.retire")
    return retire * 1e3 / n if n and retire is not None else None
