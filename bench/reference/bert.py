"""BERT pretraining loss, written plainly from Devlin et al. (arXiv:1810.04805).

Post-LN encoder: token + position + segment embeddings, LayerNorm, then per
layer self-attention (bidirectional, biases on q/k/v, none on the output
projection) and a GELU MLP, each followed by residual and LayerNorm. The MLM
head is dense, GELU, LayerNorm, the tied embedding and an output bias; NSP is
a tanh pooler over [CLS] and a two-way classifier. The loss is the mean
cross entropy over masked positions plus the mean NSP cross entropy.

Departures, each as the configuration states it: GELU is the tanh form of
the original TensorFlow code; no position is masked out of attention (the
training feed packs each row to its length but one padding token).

`model` is the configuration file's "model" dict. The parameter tree uses
the program's key names so that the two can be compared leaf by leaf.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def param_spec(model: dict) -> dict:
    L, d, f = model["n_layers"], model["d_model"], model["d_ff"]
    V, P, T = model["vocab"], model["max_pos"], model["type_vocab"]
    n = lambda *s: ("normal", s)
    z = lambda *s: ("zeros", s)
    ln = lambda *s: {"scale": ("ones", s), "bias": ("zeros", s)}
    return {
        "tok_embed": {"embedding": n(V, d)},
        "pos_embed": n(P, d),
        "type_embed": n(T, d),
        "embed_ln": ln(d),
        "layers": {
            "attn": {"wq": {"kernel": n(L, d, d), "bias": z(L, d)},
                     "wk": {"kernel": n(L, d, d), "bias": z(L, d)},
                     "wv": {"kernel": n(L, d, d), "bias": z(L, d)},
                     "wo": {"kernel": n(L, d, d)}},
            "attn_ln": ln(L, d),
            "mlp": {"up": {"kernel": n(L, d, f), "bias": z(L, f)},
                    "down": {"kernel": n(L, f, d), "bias": z(L, d)}},
            "mlp_ln": ln(L, d),
        },
        "mlm_transform": {"kernel": n(d, d), "bias": z(d)},
        "mlm_ln": ln(d),
        "mlm_bias": z(V),
        "pooler": {"kernel": n(d, d), "bias": z(d)},
        "nsp_head": {"kernel": n(d, 2), "bias": z(2)},
    }


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def objective(params, model: dict, batch: dict, mm, n_masked, n_rows):
    """MLM cross entropy summed over this block's masked positions over
    `n_masked`, plus NSP cross entropy summed over its rows over `n_rows`.
    With the whole batch as one block and its own counts, this is the loss;
    over blocks of rows, the blocks' values and gradients add up to it."""
    eps = model["layer_norm_eps"]
    H = model["n_heads"]
    tok = batch["tokens"]
    B, S = tok.shape
    d = model["d_model"]
    hd = d // H

    def dense(p, x):
        y = mm("...i,io->...o", x, p["kernel"])
        return y + p["bias"] if "bias" in p else y

    x = (params["tok_embed"]["embedding"][tok] + params["pos_embed"][:S][None]
         + params["type_embed"][batch["token_types"]])
    x = _layer_norm(x, params["embed_ln"], eps)

    def layer(x, p):
        a = p["attn"]
        q = dense(a["wq"], x).reshape(B, S, H, hd)
        k = dense(a["wk"], x).reshape(B, S, H, hd)
        v = dense(a["wv"], x).reshape(B, S, H, hd)
        probs = jax.nn.softmax(mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd), -1)
        o = mm("bhqk,bkhd->bqhd", probs, v).reshape(B, S, d)
        x = _layer_norm(x + dense(a["wo"], o), p["attn_ln"], eps)
        h = dense(p["mlp"]["down"], _gelu(dense(p["mlp"]["up"], x)))
        return _layer_norm(x + h, p["mlp_ln"], eps), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])

    t = _layer_norm(_gelu(dense(params["mlm_transform"], x)),
                    params["mlm_ln"], eps)
    logits = mm("bsd,vd->bsv", t, params["tok_embed"]["embedding"])
    logits = logits + params["mlm_bias"]
    labels = batch["mlm_labels"]
    picked = labels != -100
    logp = jax.nn.log_softmax(logits, -1)
    ll = jnp.take_along_axis(logp, jnp.where(picked, labels, 0)[..., None],
                             -1)[..., 0]
    mlm = -jnp.sum(jnp.where(picked, ll, 0.0)) / n_masked

    cls = jnp.tanh(dense(params["pooler"], x[:, 0]))
    nsp_logp = jax.nn.log_softmax(dense(params["nsp_head"], cls), -1)
    nsp = -jnp.sum(jnp.take_along_axis(
        nsp_logp, batch["nsp_labels"][:, None], -1)) / n_rows
    return mlm + nsp


def totals(batch: dict) -> tuple:
    """The normalisers of `objective` for a whole batch (host arrays)."""
    return (max(int((batch["mlm_labels"] != -100).sum()), 1),
            batch["tokens"].shape[0])


BLOCK_ROWS = 64   # rows per gradient block, so a large batch fits
