"""moonlight-16b-a3b [moe, mla] — 27L d_model=2048, MLA 16H (no q-LoRA,
kv_lora_rank 512, qk 128+64, v 128), 1 dense layer (d_ff 11264) then 26 MoE
layers of 64 routed experts (1408 wide) top-6 by sigmoid with 2 shared,
vocab=163840 untied.  [hf:moonshotai/Moonlight-16B-A3B, model_type
deepseek_v3]"""
from repro.configs.base import Arch
from repro.models.decoder import DecoderConfig

CONFIG = DecoderConfig(
    name="moonlight-16b-a3b",
    n_layers=27,
    first_k_dense=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    moe_d_ff=1408,
    vocab=163840,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50000.0,
    rms_norm_eps=1e-5,
    n_experts=64,
    router_width=64,
    top_k=6,
    n_shared_experts=2,
    router="sigmoid",
    routed_scaling=2.446,
    seq_aux=True,
    aux_weight=1e-4,
    activation="silu",
    gated_mlp=True,
    tie_embeddings=False,
    superblock=(("mla", "moe"),),
    max_seq=8192,
)

ARCH = Arch(
    name="moonlight-16b-a3b",
    kind="decoder",
    cfg=CONFIG,
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json",
    notes="n_group = topk_group = 1, so group-limited routing is a no-op; "
          "the selection bias is held at zero; aux_weight 1e-4 is assumed.",
)
