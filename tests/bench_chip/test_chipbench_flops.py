"""flops/lm.py against a count made by hand at a reduced size, and the
published sizes' counts that PERF.md quotes."""
import pytest

from chipbench_paths import BENCH

import chip_harness

LM = chip_harness.load_module(BENCH / "flops" / "lm.py")


def test_by_hand_at_a_reduced_size():
    s = {"d_model": 8, "n_heads": 2, "n_kv": 1, "head_dim": 4, "d_ff": 16,
         "vocab": 32, "n_layers": 2}
    # per layer: q and o 8*2*4 each, k and v 8*1*4 each, MLP 3*8*16
    layer = 64 + 64 + 32 + 32 + 384
    weights = 2 * layer + 32 * 8              # two layers and the tied head
    assert LM.matmul_weights(s) == weights == 1408
    # attention: per layer 2 heads * 4 dims * 2 (scores, values) FLOPs per
    # key over seq / 2 keys, i.e. 2 * 2 * 4 * seq in all
    attn = 2 * (2 * 2 * 4 * 10)
    assert LM.flops_per_token(s, 10) == 3 * (2 * 1408 + attn) == 9408


@pytest.mark.parametrize("sizes, seq, want", [
    # smollm-135m: 30 layers of 3,538,944 weights, a 49152 x 576 head
    (dict(d_model=576, n_heads=9, n_kv=3, head_dim=64, d_ff=1536,
          vocab=49152, n_layers=30), 2048, 1019215872),
    # mistral-nemo-12b.2L: 2 layers of 272,629,760 weights, 16384 x 5120
    (dict(d_model=5120, n_heads=32, n_kv=8, head_dim=128, d_ff=14336,
          vocab=16384, n_layers=2), 4096, 3976200192),
])
def test_published_sizes(sizes, seq, want):
    assert LM.flops_per_token(sizes, seq) == want
