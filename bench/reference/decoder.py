"""Decoder-only LM loss with a top-k mixture of experts, written plainly.

Each layer: RMSNorm, causal grouped-query attention with rotary embeddings
(rotate-half form, q head i reading kv head i // (heads / kv_heads)),
residual; RMSNorm, a mixture of gated-SiLU experts, residual. A final
RMSNorm and the tied embedding give the logits; the loss is the mean next
token cross entropy over the labelled positions plus `aux_weight` times the load-balance loss summed
over layers.

The experts follow the configuration's routing: softmax router in float32,
the top `top_k` experts per token with their probabilities renormalised,
and at most `int(capacity_factor * T * top_k / n_experts)` (token, choice)
pairs per expert, taken in token-major, rank-minor order; pairs past it add
nothing. Here every expert is computed for every token and the routing is a
weighted sum, so nothing is gathered or scattered. Attention runs over
blocks of query rows, recomputed in the backward pass, so that a long
sequence fits.

RMSNorm weights are stored as an offset from 1, as the configuration states.
`model` is the configuration file's "model" dict; the parameter tree uses
the program's key names.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 1024     # query rows per attention block, so a long row fits
BLOCK_ROWS = None  # the load-balance loss is over the whole batch: one block


def param_spec(model: dict) -> dict:
    L, d, f = model["n_layers"], model["d_model"], model["d_ff"]
    V, E = model["vocab"], model["n_experts"]
    h, kv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    n = lambda *s: ("normal", s)
    return {
        "embed": {"embedding": n(V, d)},
        "final_norm": {"scale": ("zeros", (d,))},
        "slot0": {
            "pre_mixer_norm": {"scale": ("zeros", (L, d))},
            "pre_ffn_norm": {"scale": ("zeros", (L, d))},
            "mixer": {"wq": {"kernel": n(L, d, h * hd)},
                      "wk": {"kernel": n(L, d, kv * hd)},
                      "wv": {"kernel": n(L, d, kv * hd)},
                      "wo": {"kernel": n(L, h * hd, d)}},
            "ffn": {"router": {"kernel": n(L, d, E)},
                    "up": n(L, E, d, f), "gate": n(L, E, d, f),
                    "down": n(L, E, f, d)},
        },
    }


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope(x, theta):
    """x (B, S, heads, hd); rotate-half rotary embedding at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _experts(p, x, model, mm):
    """x (T, d) -> (out (T, d), load-balance loss)."""
    T = x.shape[0]
    E, K = model["n_experts"], model["top_k"]
    cap = max(1, int(model["capacity_factor"] * T * K / E))
    probs = jax.nn.softmax(mm("td,de->te", x, p["router"]["kernel"]), -1)
    top_p, top_e = jax.lax.top_k(probs, K)                   # (T, K)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    chosen = jax.nn.one_hot(top_e, E, dtype=jnp.int32)       # (T, K, E)
    # rank of each (token, choice) among the pairs sent to its expert
    rank = jnp.cumsum(chosen.reshape(T * K, E), 0).reshape(T, K, E) - 1
    kept = (chosen * (rank < cap)).astype(jnp.float32)
    weight = jnp.einsum("tk,tke->te", top_p, kept)           # (T, E)

    up = mm("td,edf->etf", x, p["up"])
    gate = mm("td,edf->etf", x, p["gate"])
    y = mm("etf,efd->etd", jax.nn.silu(gate) * up, p["down"])
    out = jnp.einsum("te,etd->td", weight, y)

    density = jnp.mean(jnp.max(chosen, 1).astype(jnp.float32), 0)
    aux = jnp.sum(density * jnp.mean(probs, 0)) * (E / K)
    return out, aux


def objective(params, model: dict, batch: dict, mm):
    """The loss of one batch (tokens and labels (B, S); label -100 is not
    predicted)."""
    eps = model["rms_norm_eps"]
    tok, labels = batch["tokens"], batch["labels"]
    B, S = tok.shape
    h, kv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]

    x = params["embed"]["embedding"][tok]

    def layer(x, p):
        a = p["mixer"]
        y = _rms_norm(x, p["pre_mixer_norm"]["scale"], eps)
        q = _rope(mm("bsd,de->bse", y, a["wq"]["kernel"]).reshape(B, S, h, hd),
                  model["rope_theta"])
        k = _rope(mm("bsd,de->bse", y, a["wk"]["kernel"]).reshape(B, S, kv, hd),
                  model["rope_theta"])
        v = mm("bsd,de->bse", y, a["wv"]["kernel"]).reshape(B, S, kv, hd)
        n = min(S, Q_BLOCK)
        q = q.reshape(B, S // n, n, kv, h // kv, hd).swapaxes(0, 1)

        @jax.checkpoint
        def attend(args):
            qb, first = args
            s = mm("bqkgd,bskd->bkgqs", qb, k) / math.sqrt(hd)
            seen = first + jnp.arange(n)[:, None] >= jnp.arange(S)[None, :]
            probs = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
            return mm("bkgqs,bskd->bqkgd", probs, v)

        o = jax.lax.map(attend, (q, jnp.arange(0, S, n)))
        o = o.swapaxes(0, 1).reshape(B, S, h * hd)
        x = x + mm("bse,ed->bsd", o, a["wo"]["kernel"])
        y = _rms_norm(x, p["pre_ffn_norm"]["scale"], eps)
        out, aux = _experts(p["ffn"], y.reshape(B * S, -1), model, mm)
        return x + out.reshape(x.shape), aux

    x, aux = jax.lax.scan(jax.checkpoint(layer), x, params["slot0"])
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    logits = mm("bsd,vd->bsv", x, params["embed"]["embedding"])
    logp = jax.nn.log_softmax(logits, -1)
    valid = labels >= 0
    ll = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                             -1)[..., 0]
    ce = -jnp.sum(jnp.where(valid, ll, 0.0)) / jnp.maximum(jnp.sum(valid), 1)
    return ce + model["aux_weight"] * jnp.sum(aux)


def totals(batch: dict) -> tuple:
    """`objective` needs no normaliser from outside its one block."""
    return ()
