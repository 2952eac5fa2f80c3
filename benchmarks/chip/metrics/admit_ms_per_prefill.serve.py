"""Host time in ``serve.admit`` spans per ``serve.prefill`` span in the
traced span, in ms: what one admission costs the engine's loop, prefill,
first-token read and splice together.  Layer: serving scheduler."""


def read(r):
    t = r.trace or {}
    n = t.get("span_n", {}).get("serve.prefill")
    admit = t.get("span_total_s", {}).get("serve.admit")
    return admit * 1e3 / n if n and admit is not None else None
