"""BERT — the paper's own pretraining workload (Devlin et al.).

Faithful to the original: post-LN encoder, learned positional + token-type
embeddings, GELU MLP with biases, MLM head (transform -> tied decoder +
output bias) and NSP head over the [CLS] pooler. The pretraining loss is
MLM cross-entropy + NSP cross-entropy, exactly what LAMB/LANS optimize.

bert_large: 24L / 1024d / 16H / ff 4096 / vocab 30522 / max_pos 512.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.attention import AttnConfig, attn_apply, attn_init
from repro.models.common import (
    dense_apply,
    dense_init,
    embed_apply,
    embed_init,
    gelu,
    layernorm_apply,
    layernorm_init,
    mlp_init,
)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    name: str = "bert_large"
    n_layers: int = 24
    d_model: int = 1024
    n_heads: int = 16
    d_ff: int = 4096
    vocab: int = 30522
    max_pos: int = 512
    type_vocab: int = 2
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def attn_cfg(self) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_heads, head_dim=self.head_dim,
            qkv_bias=True, rope=False, causal=False)


def _layer_init(rng, cfg: BertConfig):
    ks = jax.random.split(rng, 2)
    return {
        "attn": attn_init(ks[0], cfg.attn_cfg(), dtype=cfg.param_dtype),
        "attn_ln": layernorm_init(cfg.d_model, cfg.param_dtype),
        "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff, gated=False,
                        use_bias=True, dtype=cfg.param_dtype),
        "mlp_ln": layernorm_init(cfg.d_model, cfg.param_dtype),
    }


def bert_init(rng, cfg: BertConfig):
    ks = jax.random.split(rng, 7)
    layer_rngs = jax.random.split(ks[0], cfg.n_layers)
    return {
        "tok_embed": embed_init(ks[1], cfg.vocab, cfg.d_model, cfg.param_dtype),
        "pos_embed": (jax.random.normal(ks[2], (cfg.max_pos, cfg.d_model))
                      * 0.02).astype(cfg.param_dtype),
        "type_embed": (jax.random.normal(ks[3], (cfg.type_vocab, cfg.d_model))
                       * 0.02).astype(cfg.param_dtype),
        "embed_ln": layernorm_init(cfg.d_model, cfg.param_dtype),
        "layers": jax.vmap(lambda r: _layer_init(r, cfg))(layer_rngs),
        "mlm_transform": dense_init(ks[4], cfg.d_model, cfg.d_model,
                                    use_bias=True, dtype=cfg.param_dtype),
        "mlm_ln": layernorm_init(cfg.d_model, cfg.param_dtype),
        "mlm_bias": jnp.zeros((cfg.vocab,), cfg.param_dtype),
        "pooler": dense_init(ks[5], cfg.d_model, cfg.d_model,
                             use_bias=True, dtype=cfg.param_dtype),
        "nsp_head": dense_init(ks[6], cfg.d_model, 2, use_bias=True,
                               dtype=cfg.param_dtype),
    }


def bert_encode(params, cfg: BertConfig, tokens, token_types=None,
                attn_valid_len=None, positions=None):
    """tokens (B, S) -> hidden states (B, S, d). Post-LN residual stack.

    positions (B, S) gives each token its LOCAL position (left-padded
    serving batches: pads carry pos < 0) — the position embedding is
    looked up per token and padded columns are masked out of the
    bidirectional attention, so a left-padded row's valid columns are
    bitwise the unpadded run of the same tokens at the same S. The
    default (None) keeps the training path's contiguous 0..S-1 layout.
    """
    B, S = tokens.shape
    with jax.named_scope("embed"):
        x = embed_apply(params["tok_embed"], tokens, cfg.compute_dtype)
        if positions is None:
            x = x + params["pos_embed"].astype(cfg.compute_dtype)[None, :S]
        else:
            pos_ids = jnp.clip(positions, 0, cfg.max_pos - 1)
            x = x + jnp.take(params["pos_embed"].astype(cfg.compute_dtype),
                             pos_ids, axis=0)
        if token_types is None:
            token_types = jnp.zeros_like(tokens)
        x = x + jnp.take(params["type_embed"].astype(cfg.compute_dtype),
                         token_types, axis=0)
        x = layernorm_apply(params["embed_ln"], x)

    def layer(x, lp):
        h, _ = attn_apply(lp["attn"], cfg.attn_cfg(), x,
                          positions=positions,
                          kv_valid_len=None, compute_dtype=cfg.compute_dtype)
        x = layernorm_apply(lp["attn_ln"], x + h)
        with jax.named_scope("ffn"):
            up = dense_apply(lp["mlp"]["up"], x, cfg.compute_dtype)
            h = dense_apply(lp["mlp"]["down"], gelu(up), cfg.compute_dtype)
        x = layernorm_apply(lp["mlp_ln"], x + h)
        return x, None

    layer = jax.checkpoint(layer,
                           policy=jax.checkpoint_policies.nothing_saveable)
    with jax.named_scope("layers"):
        x, _ = jax.lax.scan(layer, x, params["layers"])
    return x


def bert_pretrain_logits(params, cfg: BertConfig, tokens, token_types=None):
    """Returns (mlm_logits (B,S,V), nsp_logits (B,2))."""
    h = bert_encode(params, cfg, tokens, token_types)
    with jax.named_scope("head"):
        t = dense_apply(params["mlm_transform"], h, cfg.compute_dtype)
        t = layernorm_apply(params["mlm_ln"], gelu(t))
        mlm = jnp.einsum(
            "bsd,vd->bsv", t.astype(cfg.compute_dtype),
            params["tok_embed"]["embedding"].astype(cfg.compute_dtype))
        mlm = mlm.astype(jnp.float32) + params["mlm_bias"].astype(jnp.float32)
        cls = jnp.tanh(dense_apply(params["pooler"], h[:, 0],
                                   cfg.compute_dtype))
        nsp = dense_apply(params["nsp_head"], cls,
                          cfg.compute_dtype).astype(jnp.float32)
    return mlm, nsp


def bert_serve_outputs(params, cfg: BertConfig, tokens, positions):
    """Scoring/embedding forward for the serving engine.

    tokens/positions (B, S) LEFT-padded (pads carry pos < 0, the same
    convention as decoder serving prefill). Returns
      mlm_ids (B, S) int32 — greedy masked-LM argmax per column (pad
        columns produce garbage ids; the engine slices the valid tail),
      pooled (B, d) float32 — tanh-pooled [CLS] embedding, where [CLS]
        is each row's FIRST valid column (position 0).
    One fixed-shape forward, no KV cache: a scoring slot's only state is
    its output, freed at completion.
    """
    B, S = tokens.shape
    h = bert_encode(params, cfg, tokens, positions=positions)
    t = dense_apply(params["mlm_transform"], h, cfg.compute_dtype)
    t = layernorm_apply(params["mlm_ln"], gelu(t))
    mlm = jnp.einsum("bsd,vd->bsv", t.astype(cfg.compute_dtype),
                     params["tok_embed"]["embedding"].astype(cfg.compute_dtype))
    mlm = mlm.astype(jnp.float32) + params["mlm_bias"].astype(jnp.float32)
    mlm_ids = jnp.argmax(mlm, axis=-1).astype(jnp.int32)
    # first valid column per row: argmax of the (pos >= 0) indicator
    cls_col = jnp.argmax((positions >= 0).astype(jnp.int32), axis=1)
    cls_h = h[jnp.arange(B), cls_col]
    pooled = jnp.tanh(dense_apply(params["pooler"], cls_h,
                                  cfg.compute_dtype)).astype(jnp.float32)
    return mlm_ids, pooled


def bert_pretrain_loss(params, cfg: BertConfig, batch):
    """batch: tokens, token_types, mlm_labels (-100 = unmasked), nsp_labels."""
    mlm_logits, nsp_logits = bert_pretrain_logits(
        params, cfg, batch["tokens"], batch.get("token_types"))
    with jax.named_scope("loss"):
        labels = batch["mlm_labels"]
        valid = labels != -100
        safe = jnp.where(valid, labels, 0)
        logp = jax.nn.log_softmax(mlm_logits, axis=-1)
        ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        mlm_loss = -jnp.sum(ll * valid) / jnp.maximum(jnp.sum(valid), 1)

        nsp_logp = jax.nn.log_softmax(nsp_logits, axis=-1)
        nsp_loss = -jnp.mean(jnp.take_along_axis(
            nsp_logp, batch["nsp_labels"][:, None], axis=-1))
    return mlm_loss + nsp_loss, {"mlm_loss": mlm_loss, "nsp_loss": nsp_loss}
