"""How a Pallas kernel is lowered: natively for the TPU, or interpreted.

Every kernel wrapper resolves its ``interpret`` argument here, so the
rule lives in one place.  Callers on the chip path pass
``interpret=False`` explicitly: the kernel then compiles with Mosaic or
the call fails, and never falls back to the interpreter.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` interprets exactly when the default backend is not a TPU
    (tests on a CPU host); an explicit bool is kept as given."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
