"""Causal LM loss of a DeepSeek-V3 block (Moonlight-16B-A3B), written plainly.

From the published `modeling_deepseek.py` (model_type deepseek_v3) and
DeepSeek-V3 (arXiv:2412.19437 section 2.1):

- the first `first_k_dense` layers: RMSNorm, latent attention, residual;
  RMSNorm, a gated-SiLU MLP of width `d_ff`, residual;
- every later layer: the same attention, then RMSNorm and a mixture of
  gated-SiLU experts of width `moe_d_ff` plus `n_shared_experts` shared
  experts (one gated MLP n_shared_experts x moe_d_ff wide), residual;
- a final RMSNorm and an untied head; the loss is the mean next-token cross
  entropy over the labelled positions plus `aux_weight` times the
  sequence-wise balance loss summed over the expert layers.

Latent attention (MLA), no query LoRA: q = W_q x, per head [q_nope | q_pe];
[c | k_pe] = W_kv_a x; [k_nope | v] = W_kv_b RMSNorm(c) per head; q_pe and
the one k_pe that all heads share are rotated pair by pair, pair i of
(x_2i, x_2i+1) by position / rope_theta^(2i / rope), and laid out as the
published code leaves them (the rotated first members, then the rotated
second members); scores q.k / sqrt(nope + rope), causal, float32 softmax.
Attention runs over blocks of query rows, recomputed in the backward pass,
so that a long sequence fits.

The router: sigmoid scores s of all `router_width` experts in float32; the
top `top_k` of s + bias chosen; their s renormalised to sum to one, times
`routed_scaling_factor`. Each (token, choice) pair takes a place at its
expert in token-major, choice-minor order, and pairs past
`int(capacity_factor * T * top_k / router_width)` add nothing. Here every
held expert is computed for every token and the routing is a weighted sum,
so nothing is gathered or scattered. The balance loss, per sequence of S
tokens: sum_i f_i P_i, f_i = router_width / (top_k S) times the tokens
that chose expert i, P_i the mean over the tokens of s_i / sum_j s_j; the
mean over the sequences.

Departures from the published model, each as the configuration states it:

- the layer holds `n_experts` of its `router_width` experts, from router
  index `first_expert` on (one chip's share of an expert-parallel group):
  pairs routed to the others add nothing here, with no stand-in;
- the vocabulary is a slice of the published one;
- the capacity rule above, with drops (the published model is dropless);
- the selection bias is held at zero (the published model moves it by a
  rule outside the gradient, which this reference's step does not take);
- the balance weight `aux_weight` (alpha) is assumed;
- RMSNorm weights are stored as an offset from 1.

`model` is the configuration file's "model" dict; the parameter tree uses
the program's key names.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 1024     # query rows per attention block, so a long row fits
BLOCK_ROWS = None  # the capacity and the balance loss see the whole batch
LATENT_EPS = 1e-6  # the published code's default for the latent's RMSNorm


def _mixer_spec(n: int, model: dict) -> dict:
    d, h, r = model["d_model"], model["n_heads"], model["kv_lora_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    vd = model["v_head_dim"]
    w = lambda *s: {"kernel": ("normal", (n,) + s)}
    return {"wq": w(d, h * (nope + rope)), "wkv_a": w(d, r + rope),
            "kv_norm": {"scale": ("zeros", (n, r))},
            "wkv_b": w(r, h * (nope + vd)), "wo": w(h * vd, d)}


def _mlp_spec(n: int, d: int, f: int) -> dict:
    w = lambda *s: {"kernel": ("normal", (n,) + s)}
    return {"up": w(d, f), "gate": w(d, f), "down": w(f, d)}


def param_spec(model: dict) -> dict:
    d, V = model["d_model"], model["vocab"]
    lead = model["first_k_dense"]
    L = model["n_layers"] - lead
    E, R, f = model["n_experts"], model["router_width"], model["moe_d_ff"]
    norms = lambda n: {"pre_mixer_norm": {"scale": ("zeros", (n, d))},
                       "pre_ffn_norm": {"scale": ("zeros", (n, d))}}
    n = lambda *s: ("normal", s)
    spec = {
        "embed": {"embedding": n(V, d)},
        "final_norm": {"scale": ("zeros", (d,))},
        "lm_head": {"kernel": n(d, V)},
        "slot0": dict(norms(L), mixer=_mixer_spec(L, model), ffn={
            "router": {"kernel": n(L, d, R), "bias": ("zeros", (L, R))},
            "up": n(L, E, d, f), "gate": n(L, E, d, f), "down": n(L, E, f, d),
            "shared": _mlp_spec(L, d, model["n_shared_experts"] * f)}),
    }
    if lead:
        spec["lead"] = dict(norms(lead), mixer=_mixer_spec(lead, model),
                            ffn=_mlp_spec(lead, d, model["d_ff"]))
    return spec


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope_pairs(x, theta):
    """x (B, S, heads, w): pair i (x_2i, x_2i+1) rotated at positions
    0..S-1, returned as [rotated x_2i for all i | rotated x_2i+1]."""
    S, w = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, w, 2, dtype=jnp.float32) / w)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv       # (S, w/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _mla(a, y, model, mm):
    """Latent attention of y (B, S, d) -> (B, S, d)."""
    B, S, _ = y.shape
    h, r = model["n_heads"], model["kv_lora_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    vd, theta = model["v_head_dim"], model["rope_theta"]
    q = mm("bsd,de->bse", y, a["wq"]["kernel"]).reshape(B, S, h, nope + rope)
    ckv = mm("bsd,de->bse", y, a["wkv_a"]["kernel"])
    c = _rms_norm(ckv[..., :r], a["kv_norm"]["scale"], LATENT_EPS)
    kv = mm("bsr,re->bse", c, a["wkv_b"]["kernel"]).reshape(B, S, h,
                                                            nope + vd)
    k_pe = _rope_pairs(ckv[:, :, None, r:], theta)            # (B, S, 1, rope)
    q = jnp.concatenate([q[..., :nope], _rope_pairs(q[..., nope:], theta)],
                        -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (B, S, h, rope))], -1)
    v = kv[..., nope:]
    n = min(S, Q_BLOCK)
    qs = q.reshape(B, S // n, n, h, nope + rope).swapaxes(0, 1)

    @jax.checkpoint
    def attend(args):
        qb, first = args
        s = mm("bqhd,bshd->bhqs", qb, k) / math.sqrt(nope + rope)
        seen = first + jnp.arange(n)[:, None] >= jnp.arange(S)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return mm("bhqs,bshd->bqhd", probs, v)

    o = jax.lax.map(attend, (qs, jnp.arange(0, S, n)))
    o = o.swapaxes(0, 1).reshape(B, S, h * vd)
    return mm("bse,ed->bsd", o, a["wo"]["kernel"])


def _mlp(p, x, mm):
    up = mm("td,df->tf", x, p["up"]["kernel"])
    gate = mm("td,df->tf", x, p["gate"]["kernel"])
    return mm("tf,fd->td", jax.nn.silu(gate) * up, p["down"]["kernel"])


def _experts(p, x, B, model, mm):
    """x (T, d), T = B sequences -> (out (T, d), balance loss)."""
    T = x.shape[0]
    E, R, K = model["n_experts"], model["router_width"], model["top_k"]
    cap = max(1, int(model["capacity_factor"] * T * K / R))
    scores = jax.nn.sigmoid(mm("td,de->te", x, p["router"]["kernel"]))
    _, top_e = jax.lax.top_k(scores + p["router"]["bias"], K)   # (T, K)
    top_s = jnp.take_along_axis(scores, top_e, -1)
    top_s = top_s / jnp.sum(top_s, -1, keepdims=True) * model["routed_scaling"]
    # held experts only: one_hot of an index outside 0..E-1 is all zero
    chosen = jax.nn.one_hot(top_e - model["first_expert"], E,
                            dtype=jnp.int32)                     # (T, K, E)
    rank = jnp.cumsum(chosen.reshape(T * K, E), 0).reshape(T, K, E) - 1
    kept = (chosen * (rank < cap)).astype(jnp.float32)
    weight = jnp.einsum("tk,tke->te", top_s, kept)             # (T, E)

    up = mm("td,edf->etf", x, p["up"])
    gate = mm("td,edf->etf", x, p["gate"])
    y = mm("etf,efd->etd", jax.nn.silu(gate) * up, p["down"])
    out = jnp.einsum("te,etd->td", weight, y) + _mlp(p["shared"], x, mm)

    S = T // B
    picked = jax.nn.one_hot(top_e, R, dtype=jnp.float32).reshape(B, S, K, R)
    f = jnp.sum(picked, (1, 2)) * R / (K * S)                  # (B, R)
    share = (scores / jnp.sum(scores, -1, keepdims=True)).reshape(B, S, R)
    balance = jnp.mean(jnp.sum(f * jnp.mean(share, 1), -1))
    return out, balance


def objective(params, model: dict, batch: dict, mm):
    """The loss of one batch (tokens and labels (B, S); label -100 is not
    predicted)."""
    eps = model["rms_norm_eps"]
    tok, labels = batch["tokens"], batch["labels"]
    B, S = tok.shape

    x = params["embed"]["embedding"][tok]

    def attention(x, p):
        y = _rms_norm(x, p["pre_mixer_norm"]["scale"], eps)
        x = x + _mla(p["mixer"], y, model, mm)
        return x, _rms_norm(x, p["pre_ffn_norm"]["scale"], eps).reshape(
            B * S, -1)

    def dense_layer(x, p):
        x, y = attention(x, p)
        return x + _mlp(p["ffn"], y, mm).reshape(x.shape), None

    def expert_layer(x, p):
        x, y = attention(x, p)
        out, balance = _experts(p["ffn"], y, B, model, mm)
        return x + out.reshape(x.shape), balance

    if "lead" in params:
        x, _ = jax.lax.scan(jax.checkpoint(dense_layer), x, params["lead"])
    x, balance = jax.lax.scan(jax.checkpoint(expert_layer), x,
                              params["slot0"])
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    logits = mm("bsd,dv->bsv", x, params["lm_head"]["kernel"])
    logp = jax.nn.log_softmax(logits, -1)
    valid = labels >= 0
    ll = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                             -1)[..., 0]
    ce = -jnp.sum(jnp.where(valid, ll, 0.0)) / jnp.maximum(jnp.sum(valid), 1)
    return ce + model["aux_weight"] * jnp.sum(balance)


def totals(batch: dict) -> tuple:
    """`objective` needs no normaliser from outside its one block."""
    return ()
