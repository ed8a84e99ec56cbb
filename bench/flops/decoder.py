"""FLOPs per token of decoder-only LM training, forward and backward.

6 x the matrix parameters a token passes through: the q, k, v and output
projections, the router, `top_k` experts (gated: three matrices each) and
the tied vocabulary head at every position. Causal attention adds half of
12·S·(heads x head_dim) per layer. Capacity padding, dispatch and combine,
norms, elementwise work and recomputation count nothing.
"""


def flops_per_token(model: dict, seq: int) -> float:
    L, d, f, V = model["n_layers"], model["d_model"], model["d_ff"], model["vocab"]
    h, kv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    E, K = model["n_experts"], model["top_k"]
    attn_proj = d * (h + 2 * kv) * hd + h * hd * d
    experts = K * (3 if model.get("gated_mlp", True) else 2) * d * f
    per_layer = attn_proj + d * E + experts
    attention = 12 * seq * h * hd / 2
    return 6 * (L * per_layer + V * d) + L * attention
