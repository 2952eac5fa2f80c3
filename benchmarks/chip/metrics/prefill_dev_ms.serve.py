"""Device time of the ``jit_serve_prefill`` program per ``serve.prefill``
span in the traced span, in ms.  Layer: model step and admission on
device."""


def read(r):
    t = r.trace or {}
    n = t.get("span_n", {}).get("serve.prefill")
    dev = t.get("module_s", {}).get("jit_serve_prefill")
    return dev * 1e3 / n if n and dev is not None else None
