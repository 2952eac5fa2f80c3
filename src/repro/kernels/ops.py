"""jit'd public wrappers for the Pallas kernels.

On a TPU backend the kernels compile natively (Mosaic); on a CPU host
they execute in interpret mode — same kernel body, Python-evaluated —
which is how tests validate them against the ref.py oracles.  The choice
is made in one place, ``mode.resolve_interpret``; chip-path callers pass
``interpret=False`` so a kernel there can never be interpreted.  Model code
selects kernels with ``cfg.use_pallas`` / ``cfg.attn_impl``; the dry-run
path stays pure-JAX (a TPU custom-call cannot lower for the CPU target).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .rmsnorm import rmsnorm
from .ssd import ssd_chunk_kernel
from .stencil import stencil2d
from .bitonic import bitonic_stage
from . import ref

__all__ = ["flash_attention", "rmsnorm", "ssd_chunk_kernel", "stencil2d",
           "bitonic_stage", "ssd_pallas", "ref"]


def ssd_pallas(x, dt, A, Bm, Cm, chunk: int = 256, interpret: bool = None):
    """Drop-in for models.ssm.ssd_reference using the Pallas intra-chunk
    kernel + the jnp inter-chunk recurrence.

    x (B,L,H,P); dt (B,L,H) (post-softplus); A (H,); Bm/Cm (B,L,G=1,N).
    """
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    C = L // Q
    # arrange (R=B*C, H, Q, ...) for the kernel
    xr = x.reshape(B, C, Q, H, P).transpose(0, 1, 3, 2, 4) \
        .reshape(B * C, H, Q, P)
    dtr = dt.reshape(B, C, Q, H).transpose(0, 1, 3, 2).reshape(B * C, H, Q)
    dA = dtr * A[None, :, None].astype(dtr.dtype)
    cs = jnp.cumsum(dA, axis=-1)
    G = Bm.shape[2]
    hpg = H // G
    Br = jnp.repeat(Bm, hpg, axis=2).reshape(B, C, Q, H, N) \
        .transpose(0, 1, 3, 2, 4).reshape(B * C, H, Q, N)
    Cr = jnp.repeat(Cm, hpg, axis=2).reshape(B, C, Q, H, N) \
        .transpose(0, 1, 3, 2, 4).reshape(B * C, H, Q, N)
    y_diag, states = ssd_chunk_kernel(xr, dtr, cs, Br, Cr,
                                      interpret=interpret)
    # ---- inter-chunk recurrence (jnp; tiny) ----
    y_diag = y_diag.reshape(B, C, H, Q, P)
    states = states.reshape(B, C, H, N, P)
    cs_b = cs.reshape(B, C, H, Q)
    chunk_decay = jnp.exp(cs_b[..., -1])                 # (B,C,H)
    s0 = jnp.zeros((B, H, N, P), jnp.float32)

    def step(s, inp):
        d, snew = inp
        return d[:, :, None, None] * s + snew, s

    _, s_in = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0),
                   jnp.moveaxis(states, 1, 0)))
    s_in = jnp.moveaxis(s_in, 0, 1)                      # (B,C,H,N,P)
    Cr_b = Cr.reshape(B, C, H, Q, N)
    y_off = jnp.einsum("bchqn,bchnp,bchq->bchqp", Cr_b, s_in,
                       jnp.exp(cs_b))
    y = (y_diag + y_off).transpose(0, 1, 3, 2, 4).reshape(B, L, H, P)
    return y
