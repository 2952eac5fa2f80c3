"""Device self time of the operations of ``jit_serve_decode`` that lie in
no named scope per ``serve.decode`` span in the traced span, in ms: the
layer scan's slicing and write-back of the stacked cache, and whatever
else the decode runs outside its layers' scopes.  Layer: model step and
admission on device."""
from benchmarks.chip.tracing import NO_SCOPE


def read(r):
    t = r.trace or {}
    n = t.get("span_n", {}).get("serve.decode")
    stack = t.get("scope_s", {}).get(NO_SCOPE)
    return stack * 1e3 / n if n and stack is not None else None
