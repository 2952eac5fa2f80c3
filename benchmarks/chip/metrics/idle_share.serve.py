"""Share of the traced window in which no operation ran on the device, in
percent.  Layer: device."""


def read(r):
    if r.trace is None or not r.trace["op_count"]:
        return None
    return 100.0 * r.trace["idle_share"]
