"""MGMark-TPU workloads: oracles + U-mode/D-mode on a 4-device mesh."""
import numpy as np
import pytest

from conftest import run_with_devices
from repro.patterns import aes


def test_aes_fips_197_vector():
    """FIPS-197 appendix C.3 AES-256 known-answer test."""
    key = np.arange(32, dtype=np.uint8)
    pt = np.frombuffer(bytes.fromhex("00112233445566778899aabbccddeeff"),
                       np.uint8).copy()
    ct = aes.reference(pt[None].copy(), key)
    assert ct.tobytes() == bytes.fromhex(
        "8ea2b7ca516745bfeafc49904b496089")


def test_aes_jnp_matches_numpy_oracle():
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    plain = rng.integers(0, 256, (64, 16), dtype=np.uint8)
    key = rng.integers(0, 256, 32, dtype=np.uint8)
    want = aes.reference(plain, key)
    got = np.asarray(aes.encrypt_blocks(
        jnp.asarray(plain), jnp.asarray(aes.expand_key(key)),
        jnp.asarray(aes.sbox())))
    np.testing.assert_array_equal(got, want)


_PATTERN_SCRIPT = """
mesh = make_mesh((4,), ("dev",))
from repro.patterns import run_workload
sizes = {{"aes": 8192, "km": 2048, "fir": 8192, "sc": 128, "gd": 2048,
         "mt": 128, "bs": 2048}}
name = "{name}"
for rep in run_workload(name, mesh, sizes[name]):
    assert rep.correct, (name, rep.mode, rep.max_err)
    print(rep.mode, "coll_bytes", rep.collective_bytes)
print("PATTERN_OK")
"""


@pytest.mark.parametrize("name", ["aes", "km", "fir", "sc", "gd", "mt",
                                  "bs"])
def test_pattern_both_modes(name):
    out = run_with_devices(4, _PATTERN_SCRIPT.format(name=name))
    assert "PATTERN_OK" in out


def test_partitioned_patterns_have_near_zero_traffic():
    """The paper's core claim for Partitioned Data: no cross-device bytes
    (KM allows the tiny centroid partial-sum reduction)."""
    out = run_with_devices(4, _PATTERN_SCRIPT.format(name="aes"))
    lines = [l for l in out.splitlines() if "coll_bytes" in l]
    for line in lines:
        assert float(line.split()[-1]) == 0.0
