"""FLOPs per token of BERT pretraining, forward and backward.

6 x the matrix parameters a token passes through (2 forward, 4 backward),
plus attention's two S x S products, 12·S·d per layer. The MLM head (dense
transform and the tied vocabulary projection) counts only at the 15% of
positions the loss reads, as the published recipe computes it; the pooler
and NSP head once per sequence. Embedding lookups, norms and elementwise
work count nothing, and neither does work recomputed for the backward pass.
"""

MASKED_SHARE = 0.15


def flops_per_token(model: dict, seq: int) -> float:
    L, d, f, V = model["n_layers"], model["d_model"], model["d_ff"], model["vocab"]
    encoder = L * (4 * d * d + 2 * d * f)
    mlm_head = MASKED_SHARE * (d * d + V * d)
    nsp_head = (d * d + 2 * d) / seq
    attention = 12 * L * seq * d
    return 6 * (encoder + mlm_head + nsp_head) + attention
