"""Device busy time per engine iteration, in ms: the union of the device's
operation intervals over the traced window (which holds the prefills and
cache splices of admissions too) divided by the engine.step calls in it.
Layer: model step and admission on device."""


def read(r):
    if r.trace is None or not r.trace["op_count"] or not r.trace["steps"]:
        return None
    return r.trace["busy_s"] * 1e3 / r.trace["steps"]
