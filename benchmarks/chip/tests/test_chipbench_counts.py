"""Operation and byte counts from shapes, against sums worked by hand from
the published sizes."""
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))
from benchmarks.chip import common  # noqa: E402

CHIP = pathlib.Path(__file__).resolve().parents[1]


def load(name):
    return (json.loads((CHIP / "configs" / f"{name}.json").read_text()),
            common.load_module(CHIP / "counts" / f"{name}.py"))


def test_qwen2_1_5b_counts():
    c, n = load("qwen2-1.5b")
    # per layer: q 1536x1536, k and v 1536x256 each, o 1536x1536, and the
    # SwiGLU's three 1536x8960 matrices
    layer = 2_359_296 + 786_432 + 2_359_296 + 41_287_680
    assert n.layer_matmul_params(c) == layer == 46_792_704
    head = 2 * 1536 * 151_936                               # 466,747,392
    # a decode token at position 0 sees one key: 4 * 12 heads * 128 * 28
    assert n.decode_flops(c, 0) == 2 * layer * 28 + 172_032 + head \
        == 3_087_310_848
    assert n.decode_flops(c, 99) == n.decode_flops(c, 0) + 99 * 172_032
    # 512 prompt tokens see 512 * 513 / 2 = 131,328 keys in all
    assert n.prefill_flops(c, 512) == \
        2 * layer * 28 * 512 + 172_032 * 131_328 + head
    # bf16 weights: 28 layers plus the embedding, which is also the head
    assert n.weight_bytes(c) == 2 * (layer * 28 + 151_936 * 1536) \
        == 3_087_138_816
    # keys and values: 28 layers x 2 heads x 128 x 2 (k, v) x 2 bytes
    assert n.kv_bytes_per_token(c) == 28_672
    # a decode step reads every weight: the tied head is the whole table
    assert n.decode_step_bytes(c, [0, 9]) == \
        n.weight_bytes(c) + 28_672 * (1 + 10)
    untied = dict(c, tie_word_embeddings=False)
    assert n.weight_bytes(untied) == n.weight_bytes(c) + 2 * 151_936 * 1536
    assert n.decode_step_bytes(untied, [0]) == \
        n.weight_bytes(c) + 28_672
