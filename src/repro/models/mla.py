"""Multi-head latent attention (MLA), the attention of DeepSeek-V2/V3 and
Moonlight, for training (arXiv:2405.04434 section 2.1; the published
`modeling_deepseek.py`, without a query LoRA).

Per token x (d_model):

    q          = W_q x, per head [q_nope (nope) | q_rope (rope)]
    [c, k_r]   = W_kv_a x: the latent c (kv_lora_rank) and one rotary key
                 k_r (rope) that every head shares
    c          = RMSNorm(c)
    [k_nope|v] = W_kv_b c, per head (nope + v_head_dim)
    q_rope, k_r rotated by RoPE with their pairs laid out as the published
                 code lays them out (de-interleaved, then rotate-half)
    k          = [k_nope | k_r], q = [q_nope | q_rope]: nope + rope wide
    out        = W_o softmax(q k^T / sqrt(nope + rope), causal) v

Keys and queries are nope + rope (192) wide and values v_head_dim (128).
`attention.attend` attends them and chooses how, as for every other
training attention: the training kernel, which takes the two widths
(kernels/flash_attention_kernel.py), where it fits, else the XLA path.
W_kv_a, its norm and W_kv_b run in the named scope `mla.latent`, inside
`attention`. There is no decode cache: the mixer trains only.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.models.attention import attend
from repro.models.common import (apply_rope, dense_apply, dense_init,
                                 rmsnorm_apply, rmsnorm_init)

# The published code builds the latent's RMSNorm with its default epsilon,
# whatever the config's rms_norm_eps.
LATENT_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class MlaConfig:
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def mla_init(rng, cfg: MlaConfig, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    return {
        "wq": dense_init(ks[0], d, h * cfg.qk_head_dim, dtype=dtype),
        "wkv_a": dense_init(ks[1], d, r + cfg.qk_rope_head_dim, dtype=dtype),
        "kv_norm": rmsnorm_init(r, dtype),
        "wkv_b": dense_init(ks[2], r, h * (cfg.qk_nope_head_dim
                                           + cfg.v_head_dim), dtype=dtype),
        "wo": dense_init(ks[3], h * cfg.v_head_dim, d, dtype=dtype),
    }


def _rope_deinterleaved(x, positions, theta):
    """RoPE of x (..., S, heads, w) as the published code applies it: the
    pairs (x0, x1), (x2, x3), ... are first laid out as [x0, x2, ... |
    x1, x3, ...], then rotated in rotate-half form."""
    *lead, w = x.shape
    x = x.reshape(*lead, w // 2, 2).swapaxes(-1, -2).reshape(*lead, w)
    return apply_rope(x, positions, theta)


@jax.named_scope("attention")
def mla_apply(p, cfg: MlaConfig, x, *, positions=None, cache=None,
              compute_dtype=jnp.bfloat16):
    """x (B, S, d) -> (out (B, S, d), None); causal self-attention."""
    if cache is not None:
        raise NotImplementedError("latent attention has no decode cache")
    B, S, _ = x.shape
    h, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    vd, r = cfg.v_head_dim, cfg.kv_lora_rank
    if positions is None:
        positions = jnp.arange(S)

    q = dense_apply(p["wq"], x, compute_dtype).reshape(B, S, h, nope + rope)
    with jax.named_scope("mla.latent"):
        ckv = dense_apply(p["wkv_a"], x, compute_dtype)
        c = rmsnorm_apply(p["kv_norm"], ckv[..., :r], LATENT_EPS)
        kv = dense_apply(p["wkv_b"], c, compute_dtype).reshape(
            B, S, h, nope + vd)
    q_rope = _rope_deinterleaved(q[..., nope:], positions, cfg.rope_theta)
    k_rope = _rope_deinterleaved(ckv[..., None, r:], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, h, rope))], axis=-1)
    v = kv[..., nope:]

    out = attend(q, k, v, positions, causal=True, compute_dtype=compute_dtype,
                 scale=1.0 / math.sqrt(nope + rope))
    return dense_apply(p["wo"], out.reshape(B, S, h * vd), compute_dtype), None
