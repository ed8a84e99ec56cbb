"""FLOPs of training a DeepSeek-V3 block (Moonlight) on this chip's share.

`flops_per_token`: 6 x the matrix parameters a token passes through here:
in every layer q, the latent down-projection W_kv_a (latent and rotary key),
its up-projection W_kv_b and the output projection; the dense layers' gated
MLP; in the expert layers the router, the shared experts and top_k x
n_experts / router_width gated experts (the share of a token's choices the
held experts take on average: 0.75 of an expert for 8 of 64 at top 6); the
untied head. Causal attention adds half of 6·S·heads·(key + value width)
per layer. Capacity padding, dispatch and combine, norms, elementwise work
and recomputation count nothing.

`kernel_flops_per_step`: the causal FLOPs a step of the training attention
kernel (kernels/flash_attention_kernel.py) at these shapes, each product
counted once over the causal half of its S x S scores (S^2 x width): the
forward's QK^T and PV, the same again recomputed for the backward pass,
and the backward's QK^T, dP = dO V^T, dV, dK and dQ. So S^2 (5 x key width
+ 4 x value width) a head, layer and sequence, whatever implements it.
"""


def _widths(model: dict) -> tuple:
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return qk, model["v_head_dim"]


def flops_per_token(model: dict, seq: int) -> float:
    d, h, r, V = (model["d_model"], model["n_heads"], model["kv_lora_rank"],
                  model["vocab"])
    qk, vd = _widths(model)
    E, R, K = model["n_experts"], model["router_width"], model["top_k"]
    f, lead, L = model["moe_d_ff"], model["first_k_dense"], model["n_layers"]
    mla = (d * h * qk + d * (r + model["qk_rope_head_dim"])
           + r * h * (model["qk_nope_head_dim"] + vd) + h * vd * d)
    dense = 3 * d * model["d_ff"]
    experts = (d * R + 3 * d * model["n_shared_experts"] * f
               + K * E / R * 3 * d * f)
    params = L * mla + lead * dense + (L - lead) * experts + V * d
    attention = 6 * seq * h * (qk + vd) / 2
    return 6 * params + L * attention


def kernel_flops_per_step(model: dict, seq: int, batch: int) -> float:
    qk, vd = _widths(model)
    return (batch * model["n_layers"] * model["n_heads"] * seq * seq
            * (5 * qk + 4 * vd))
