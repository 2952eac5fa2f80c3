"""Active slots per decode step: the change of ``Engine.stats()
["slot_steps"]`` over that of ``"decode_steps"`` in the record's span.
Layer: serving scheduler."""


def read(r):
    e = getattr(r, "engine", None) or {}
    steps = e.get("decode_steps")
    return e["slot_steps"] / steps if steps and "slot_steps" in e else None
