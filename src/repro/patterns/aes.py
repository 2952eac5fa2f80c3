"""AES-256 (ECB over blocks) — Partitioned Data pattern.

The paper's compute-intensive no-communication workload: plaintext is
chunked across devices, every device encrypts its chunk, zero cross-
device traffic.  Full AES-256 in JAX: SubBytes via table gather,
ShiftRows via fixed gather, MixColumns in GF(2^8) with uint8 bit ops —
validated against the FIPS-197 C.3 test vector
(tests/test_patterns.py::test_aes_fips_vector).

Key expansion runs on the host (numpy) — it is sequential and tiny,
exactly like the paper's host-side setup.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

PATTERN = "partitioned"


# --------------------------------------------------------------------------
# tables (generated, not typed in)
# --------------------------------------------------------------------------

def _gf_mul(a: int, b: int) -> int:
    r = 0
    for _ in range(8):
        if b & 1:
            r ^= a
        hi = a & 0x80
        a = (a << 1) & 0xFF
        if hi:
            a ^= 0x1B
        b >>= 1
    return r


@functools.lru_cache(None)
def sbox() -> np.ndarray:
    # multiplicative inverse in GF(2^8) + affine transform (FIPS-197 5.1.1)
    inv = np.zeros(256, np.uint8)
    for a in range(1, 256):
        for b in range(1, 256):
            if _gf_mul(a, b) == 1:
                inv[a] = b
                break
    out = np.zeros(256, np.uint8)
    for i in range(256):
        x = int(inv[i])
        y = x
        for _ in range(4):
            x = ((x << 1) | (x >> 7)) & 0xFF
            y ^= x
        out[i] = y ^ 0x63
    return out


_RCON = np.array([0x01, 0x02, 0x04, 0x08, 0x10, 0x20, 0x40, 0x80,
                  0x1B, 0x36, 0x6C, 0xD8, 0xAB, 0x4D], np.uint8)


def expand_key(key: np.ndarray) -> np.ndarray:
    """key (32,) uint8 -> round keys (15, 16) uint8 (AES-256, Nk=8)."""
    S = sbox()
    w = [key[4 * i:4 * i + 4].copy() for i in range(8)]
    for i in range(8, 60):
        t = w[i - 1].copy()
        if i % 8 == 0:
            t = np.roll(t, -1)
            t = S[t]
            t[0] ^= _RCON[i // 8 - 1]
        elif i % 8 == 4:
            t = S[t]
        w.append(w[i - 8] ^ t)
    return np.concatenate(w).reshape(15, 16)


# --------------------------------------------------------------------------
# the cipher (vectorized over blocks)
# --------------------------------------------------------------------------

# ShiftRows on column-major state bytes b[r + 4c]: byte i moves to
# position (i*5 mod 16) inverse; precompute the gather indices.
_SHIFT_IDX = np.array([(i + 4 * (i % 4)) % 16 for i in range(16)])


def _xtime(a):
    return ((a << 1) ^ jnp.where(a & 0x80, jnp.uint8(0x1B),
                                 jnp.uint8(0))).astype(jnp.uint8)


def encrypt_blocks(blocks, round_keys, sbox_table):
    """blocks (N,16) uint8, round_keys (15,16), sbox (256,) -> (N,16)."""
    st = blocks ^ round_keys[0]

    def sub_shift(st):
        st = jnp.take(sbox_table, st.astype(jnp.int32), axis=0)
        return st[:, _SHIFT_IDX]

    def mix(st):
        s = st.reshape(-1, 4, 4)                    # columns (N, col, row)
        a0, a1, a2, a3 = s[:, :, 0], s[:, :, 1], s[:, :, 2], s[:, :, 3]
        x0, x1, x2, x3 = _xtime(a0), _xtime(a1), _xtime(a2), _xtime(a3)
        b0 = x0 ^ (x1 ^ a1) ^ a2 ^ a3
        b1 = a0 ^ x1 ^ (x2 ^ a2) ^ a3
        b2 = a0 ^ a1 ^ x2 ^ (x3 ^ a3)
        b3 = (x0 ^ a0) ^ a1 ^ a2 ^ x3
        return jnp.stack([b0, b1, b2, b3], axis=2).reshape(-1, 16)

    for rnd in range(1, 14):
        st = mix(sub_shift(st)) ^ round_keys[rnd]
    return sub_shift(st) ^ round_keys[14]


# --------------------------------------------------------------------------
# oracle + modes
# --------------------------------------------------------------------------

def reference(plain: np.ndarray, key: np.ndarray) -> np.ndarray:
    """Pure-numpy oracle (independent of the jnp path)."""
    S = sbox()
    rk = expand_key(key)
    st = plain.reshape(-1, 16) ^ rk[0]

    def mix_np(st):
        s = st.reshape(-1, 4, 4).astype(np.uint8)
        out = np.empty_like(s)
        for c in range(4):
            a = s[:, c, :]
            x = ((a << 1) ^ np.where(a & 0x80, 0x1B, 0)).astype(np.uint8)
            out[:, c, 0] = x[:, 0] ^ (x[:, 1] ^ a[:, 1]) ^ a[:, 2] ^ a[:, 3]
            out[:, c, 1] = a[:, 0] ^ x[:, 1] ^ (x[:, 2] ^ a[:, 2]) ^ a[:, 3]
            out[:, c, 2] = a[:, 0] ^ a[:, 1] ^ x[:, 2] ^ (x[:, 3] ^ a[:, 3])
            out[:, c, 3] = (x[:, 0] ^ a[:, 0]) ^ a[:, 1] ^ a[:, 2] ^ x[:, 3]
        return out.reshape(-1, 16)

    for rnd in range(1, 14):
        st = mix_np(S[st][:, _SHIFT_IDX]) ^ rk[rnd]
    return (S[st][:, _SHIFT_IDX] ^ rk[14]).reshape(plain.shape)


def default_size(n_devices: int) -> int:
    return 256 * 1024 * max(1, n_devices // 1)      # Table 2: 256KB x devs


def make_umode(mesh):
    sh = NamedSharding(mesh, P("dev", None))

    def fn(blocks, rk, sb):
        blocks = jax.lax.with_sharding_constraint(blocks, sh)
        return encrypt_blocks(blocks, rk, sb)
    return jax.jit(fn, out_shardings=sh)


def make_dmode(mesh):
    def local(blocks, rk, sb):                       # no collectives at all
        return encrypt_blocks(blocks, rk, sb)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("dev", None), P(None, None), P(None)),
                       out_specs=P("dev", None), check_vma=False)
    return jax.jit(fn)


def make_args(size_bytes: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    plain = rng.integers(0, 256, (size_bytes // 16, 16), dtype=np.uint8)
    key = rng.integers(0, 256, 32, dtype=np.uint8)
    return plain, key, expand_key(key), sbox()
