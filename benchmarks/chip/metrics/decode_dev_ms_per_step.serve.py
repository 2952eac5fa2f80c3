"""Device time of the ``jit_serve_decode`` program per ``serve.decode``
span in the traced span, in ms.  Layer: model step and admission on
device."""


def read(r):
    t = r.trace or {}
    n = t.get("span_n", {}).get("serve.decode")
    dev = t.get("module_s", {}).get("jit_serve_decode")
    return dev * 1e3 / n if n and dev is not None else None
