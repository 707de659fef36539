"""FLOPs that one token of training needs in a decoder-only LM of the
``lm`` family, from the configuration's shapes.

Forward, a token costs 2 FLOPs per weight of every matrix it meets (the
query, key, value and output projections, the three MLP matrices and the
tied output head), plus attention: 2 * heads * head_dim FLOPs per key
for the scores and as many for the weighted values, over the seq / 2
keys a causal token sees on average.  Backward costs twice the forward.
Recomputation (remat) is not counted; neither are norms, softmax and
the embedding lookup.
"""


def matmul_weights(s: dict) -> int:
    d, h, kv, hd, f = (s["d_model"], s["n_heads"], s["n_kv"],
                       s["head_dim"], s["d_ff"])
    layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    return s["n_layers"] * layer + s["vocab"] * d


def flops_per_token(s: dict, seq: int) -> float:
    forward = 2.0 * matmul_weights(s) \
        + s["n_layers"] * 2.0 * s["n_heads"] * s["head_dim"] * seq
    return 3.0 * forward
