"""The plain references against the program's own forward pass at a tiny
size in float32, on weights the references make: the two implement the
same model, so every logit agrees to float32 round-off."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[3]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import chipbench_tiny  # noqa: E402
from benchmarks.chip import common  # noqa: E402
from repro.models import api  # noqa: E402

CHIP = REPO / "benchmarks" / "chip"


@pytest.mark.parametrize("c", [chipbench_tiny.QWEN,
                               chipbench_tiny.QWEN_UNTIED],
                         ids=["dense_lm", "dense_lm_untied"])
def test_reference_matches_program_forward(c):
    ref = common.load_module(CHIP / "refs" / f"{c['reference']}.py")
    serve = common.load_module(CHIP / "drivers" / "serve.py")
    cfg = serve.program_config(common.Cell(
        name="t", chips=1, config=c, traffic={}, limits={}, end_to_end=[],
        per_layer=[], root=CHIP))
    for field, want in ref.program_fields(c).items():
        assert getattr(cfg, field) == want, field
    assert cfg.tie_embeddings == c["tie_word_embeddings"]
    params = ref.init(common.jax_key(2**33 + 1), c)
    want = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), want) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), params)
    S = 40
    tokens = jax.random.randint(jax.random.key(3), (S,), 0, c["vocab_size"])
    got, _ = api.forward(params, cfg, {"tokens": tokens[None]})
    got = np.asarray(got[0, :, :c["vocab_size"]])
    ref_logits = np.asarray(ref.logits_at(params, c, tokens, jnp.arange(S)))
    scale = np.max(np.abs(ref_logits))
    assert np.max(np.abs(got - ref_logits)) <= 1e-4 * scale


def test_fp8_control_rounds_to_three_mantissa_bits():
    from benchmarks.chip.refs_common import fp8
    x = jnp.array([[1.0, 1.1, 448.0, -3.3]])
    y = np.asarray(fp8(x, -1))
    assert y[0, 2] == 448.0                       # the scale's own maximum
    # e4m3 keeps 3 fraction bits: relative error at most 2^-4
    assert np.all(np.abs(y - np.asarray(x)) <= 2.0 ** -4 * np.abs(np.asarray(x)))
    assert not np.allclose(y, np.asarray(x))
