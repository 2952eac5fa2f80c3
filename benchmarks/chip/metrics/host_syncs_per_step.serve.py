"""Device-to-host reads of the engine per decode step: the change of
``Engine.stats()["host_syncs"]`` over that of ``"decode_steps"`` in the
record's span.  Layer: serving scheduler."""


def read(r):
    e = getattr(r, "engine", None) or {}
    steps = e.get("decode_steps")
    return e["host_syncs"] / steps if steps and "host_syncs" in e else None
