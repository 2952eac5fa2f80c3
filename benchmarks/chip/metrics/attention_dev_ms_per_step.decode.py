"""Device self time of the operations in the ``attention`` scope of
``jit_serve_decode`` (its ``kv_update`` included) per ``serve.decode`` span
in the traced span, in ms.  The scan's slicing and write-back of the
stacked cache lie in no scope and do not count.  Layer: model step and
admission on device."""


def read(r):
    t = r.trace or {}
    n = t.get("span_n", {}).get("serve.decode")
    scopes = t.get("scope_s", {})
    att = [v for k, v in scopes.items()
           if k == "attention" or k.startswith("attention/")]
    return sum(att) * 1e3 / n if n and att else None
