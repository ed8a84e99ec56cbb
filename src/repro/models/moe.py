"""Mixture-of-Experts FFN with top-k routing (grok-1, granite-moe, jamba).

Capacity-bounded dispatch: each (token, k) pair routed to an expert takes
the next of that expert's `cap` slots in token order, or is dropped when
they are full. One map between pairs and slots moves the tokens: dispatch
gathers each slot's token row into dense (E, cap, d) expert buffers,
combine gathers each pair's expert output back and weights it by its
router probability. No scatter moves a row, forward or backward: the map
is a bijection between kept pairs and filled slots, so each adjoint is a
gather through the inverse map. On the production mesh the expert
dimension is sharded over the "model" axis (expert parallelism). Expert
layers are where LANS's per-block trust ratios matter most (router blocks
see very different gradient scales than expert FFN blocks).

Includes the standard auxiliary load-balancing loss (Shazeer et al.) exposed
to the training loop.

Two routers: softmax (granite, grok, jamba) and DeepSeek-V3's sigmoid
(`noaux_tc`: a selection bias added to the scores for choosing only, the
chosen scores renormalised and times `routed_scaling`, and the
sequence-wise balance loss of arXiv:2412.19437 section 2.1.2), with
optional shared experts that every token passes through. An expert layer
may hold only some of the experts its router scores, as one chip of an
expert-parallel group does: `n_experts` held, starting at router index
`first_expert`, of `router_width`. It routes over all of them and
computes only the held experts' part of the output; the other experts'
part is left out.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import (ACTIVATIONS, ambient_axis_size, dense_init,
                                 maybe_constrain, mlp_apply, mlp_init)


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    activation: str = "silu"
    gated: bool = True
    router: str = "softmax"              # "softmax" | "sigmoid"
    routed_scaling: float = 1.0          # times the renormalised gates
    router_width: Optional[int] = None   # experts scored; None: n_experts
    first_expert: int = 0                # router index of the first held
    n_shared: int = 0                    # shared experts, d_ff wide each
    seq_aux: bool = False                # sequence-wise balance loss

    @property
    def n_routed(self) -> int:
        return self.router_width or self.n_experts


def moe_init(rng, cfg: MoeConfig, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff

    def expert_stack(k, din, dout):
        # (E, din, dout) — one slab per expert, sharded over E on the mesh.
        scale = 1.0 / jnp.sqrt(din)
        return (jax.random.normal(k, (e, din, dout)) * scale).astype(dtype)

    p = {
        "router": dense_init(ks[0], d, cfg.n_routed, use_bias=False,
                             dtype=jnp.float32),
        "up": expert_stack(ks[1], d, f),
        "down": expert_stack(ks[2], f, d),
    }
    if cfg.gated:
        p["gate"] = expert_stack(ks[3], d, f)
    if cfg.router == "sigmoid":
        # the selection bias, held at zero: no gradient reaches it
        p["router"]["bias"] = jnp.zeros((cfg.n_routed,), jnp.float32)
    if cfg.n_shared:
        p["shared"] = mlp_init(jax.random.fold_in(rng, 1), d,
                               cfg.n_shared * f, gated=cfg.gated, dtype=dtype)
    return p


def moe_apply(p, cfg: MoeConfig, x, *, valid=None, compute_dtype=jnp.bfloat16):
    """x: (B, S, d). Returns (out, aux) with aux = load-balance loss terms.

    valid: optional (B, S) bool — False tokens (left-padding in a batched
    serving prefill) are excluded from routing: they consume no expert
    capacity (their dispatch one-hots are zeroed before the position
    cumsum), produce zero output, and drop out of the load-balance stats,
    so real tokens route identically to a pad-free run.

    GROUP-LOCAL GATHER DISPATCH. The classic GShard one-hot dispatch
    materializes a (T, E, C) tensor — O(T^2 K / E) memory/FLOPs, which blew
    the granite-40e configs to 5.5 TB at prefill_32k. Instead:
      1. tokens are split into G groups (G = ambient "data" axis size) and
         routed group-locally — each group enforces its own capacity, which
         is exactly what per-device routing does in production MoE systems;
      2. one map links pairs and slots: pair -> slot from the cumsum
         position, slot -> pair from a stable sort of the pairs by expert
         (token order within an expert, as the position counts);
      3. dispatch gathers each slot's token row into (G, E, C_local, d)
         expert buffers, combine gathers each pair's output row; the
         adjoint of each gather is a gather through the inverse map
         (`_move`) — O(E*C_local*d + T*K*d), no TEC tensor, no scatter.
    Expert compute stays dense einsum (MXU): experts over "model" when
    divisible (jamba 16e), otherwise the ff dim (grok 8e, granite 40e).
    """
    B, S, d = x.shape
    E, K, R = cfg.n_experts, cfg.top_k, cfg.n_routed
    partial = R != E or cfg.first_expert != 0
    T = B * S
    # Groups span the FULL data-parallel extent (pod x data): using "data"
    # alone replicated all expert compute across pods (pod2 dry-run showed
    # identical per-chip FLOPs to pod1 for every MoE arch — §Perf iter 5).
    dp_axes = tuple(a for a in ("pod", "data") if ambient_axis_size(a) > 1)
    G = max(1, ambient_axis_size("pod") * ambient_axis_size("data"))
    while T % G != 0:  # tiny test shapes: fall back to fewer groups
        G //= 2
    G = max(G, 1)
    Tl = T // G
    cap = max(1, int(cfg.capacity_factor * Tl * K / R))

    xg = x.reshape(G, Tl, d)
    xg = maybe_constrain(xg, dp_axes or None, None, None)

    with jax.named_scope("moe.router"):
        router_logits = jnp.einsum(
            "gtd,de->gte", xg.astype(jnp.float32), p["router"]["kernel"])
        if cfg.router == "sigmoid":
            probs = jax.nn.sigmoid(router_logits)            # (G, Tl, R)
            _, idx_k = jax.lax.top_k(probs + p["router"]["bias"], K)
            gates_k = jnp.take_along_axis(probs, idx_k, axis=-1)
        else:
            probs = jax.nn.softmax(router_logits, axis=-1)   # (G, Tl, R)
            gates_k, idx_k = jax.lax.top_k(probs, K)         # (G, Tl, K)
        chosen = idx_k                                       # router index
        gates_k = gates_k / jnp.maximum(
            gates_k.sum(-1, keepdims=True), 1e-9)
        if cfg.routed_scaling != 1.0:
            gates_k = gates_k * cfg.routed_scaling

        # Held experts' index of each (token, k); a pair sent to an expert
        # held elsewhere gets no one-hot, so it takes no place below.
        if partial:
            local = idx_k - cfg.first_expert
            idx_k = jnp.where((local >= 0) & (local < E), local, -1)
        # Position of each (token, k) within its expert's buffer, per group.
        sel = jax.nn.one_hot(idx_k, E, dtype=jnp.int32)      # (G, Tl, K, E)
        if partial:
            idx_k = jnp.maximum(idx_k, 0)
        if valid is not None:
            vg = valid.reshape(G, Tl)
            sel = sel * vg[..., None, None].astype(sel.dtype)  # pads: nowhere
        sel_flat = sel.reshape(G, Tl * K, E)
        placed = sel_flat.sum(axis=-1) > 0           # pairs that take a place
        position = jnp.cumsum(sel_flat, axis=1) - 1          # (G, TlK, E)
        flat_e = idx_k.reshape(G, Tl * K)
        pos_k = jnp.take_along_axis(
            position, flat_e[..., None], axis=-1)[..., 0]
        keep = placed & (pos_k < cap)                        # (G, TlK)

        # The pair <-> slot map; an index past the end stands for none.
        # pair -> slot: row e * cap + position of the (E * cap, d) buffer.
        slot_of = jnp.where(keep, flat_e * cap + pos_k, E * cap)
        # slot -> pair: a stable sort by expert lists each expert's placed
        # pairs in token order, so slot (e, c) holds the c-th of them.
        order = jnp.argsort(jnp.where(placed, flat_e, E), axis=1,
                            stable=True)
        count = sel_flat.sum(axis=1)                         # (G, E)
        c = jnp.arange(cap)
        at = (jnp.cumsum(count, axis=1) - count)[..., None] + c
        filled = c < count[..., None]                        # (G, E, cap)
        pair_of = jnp.take_along_axis(
            order, jnp.minimum(at, Tl * K - 1).reshape(G, E * cap), axis=1)
        pair_of = jnp.where(filled.reshape(G, E * cap), pair_of, Tl * K)

    # Dispatch: each slot's token row, zero where the slot is empty.
    with jax.named_scope("moe.dispatch"):
        xin = jax.vmap(_move)(xg.astype(compute_dtype), pair_of // K,
                              slot_of.reshape(G, Tl, K))
        xin = xin.reshape(G, E, cap, d)
        ep = E % max(ambient_axis_size("model"), 1) == 0 \
            and ambient_axis_size("model") > 1
        e_ax = "model" if ep else None
        ff_ax = None if ep else "model"
        xin = maybe_constrain(xin, dp_axes or None, e_ax, None, None)

    with jax.named_scope("moe.experts"):
        act = ACTIVATIONS[cfg.activation]
        up = jnp.einsum("gecd,edf->gecf", xin, p["up"].astype(compute_dtype))
        up = maybe_constrain(up, dp_axes or None, e_ax, None, ff_ax)
        if cfg.gated:
            g = jnp.einsum("gecd,edf->gecf", xin,
                           p["gate"].astype(compute_dtype))
            g = maybe_constrain(g, dp_axes or None, e_ax, None, ff_ax)
            up = act(g) * up
        else:
            up = act(up)
        yout = jnp.einsum("gecf,efd->gecd", up,
                          p["down"].astype(compute_dtype))
        yout = maybe_constrain(yout, dp_axes or None, e_ax, None, None)

    # Combine: gather each (token, k)'s expert output, weight, sum over K.
    with jax.named_scope("moe.combine"):
        y_tk = jax.vmap(_move)(yout.reshape(G, E * cap, d), slot_of,
                               pair_of[..., None])
        w = (gates_k.reshape(G, Tl * K).astype(compute_dtype)
             * keep.astype(compute_dtype))
        out = (y_tk * w[..., None]).reshape(G, Tl, K, d).sum(axis=2)
        out = out.reshape(B, S, d)

    if cfg.n_shared:
        with jax.named_scope("ffn"), jax.named_scope("moe.shared"):
            out = out + mlp_apply(p["shared"], x, activation=cfg.activation,
                                  compute_dtype=compute_dtype)

    with jax.named_scope("moe.router"):
        if cfg.seq_aux:
            aux_loss = _sequence_balance(probs, chosen.reshape(B, S, K),
                                         R, K)
            probs = probs / probs.sum(-1, keepdims=True)
        else:
            # mean gate fraction * mean dispatch fraction over the batch
            if partial:
                topk_mask = jax.nn.one_hot(chosen, R, dtype=jnp.int32
                                           ).sum(axis=2) > 0
            else:
                topk_mask = sel.sum(axis=2) > 0              # (G, Tl, R)
            density = topk_mask.astype(jnp.float32).mean(axis=(0, 1))
            density_proxy = probs.mean(axis=(0, 1))
            aux_loss = jnp.sum(density * density_proxy) * (R / K)
        entropy = -(probs * jnp.log(probs + 1e-9)).sum(-1).mean()
    return out.astype(x.dtype), {"moe_aux_loss": aux_loss,
                                 "router_entropy": entropy}


@jax.custom_vjp
def _move(src, idx, inv):
    """Rows of src (N, d) by idx (n,): out[i] = src[idx[i]], zero where
    idx[i] >= N. inv (N, m) is the inverse map: out's rows that take src
    row r, >= n for none. The adjoint sums d(out) over inv's rows, a
    gather where autodiff of the gather would scatter."""
    return jnp.take(src, idx, axis=0, mode="fill", fill_value=0)


def _move_fwd(src, idx, inv):
    return _move(src, idx, inv), inv


def _move_bwd(inv, d_out):
    d_src = jnp.take(d_out, inv, axis=0, mode="fill", fill_value=0)
    return d_src.sum(axis=1), None, None


_move.defvjp(_move_fwd, _move_bwd)


def _sequence_balance(scores, chosen, R: int, K: int):
    """DeepSeek-V3's sequence-wise balance loss (arXiv:2412.19437 eq. 17-20)
    without its weight, over every expert the router scores: per sequence,
    sum_i f_i P_i with f_i = R / (K S) times the tokens that chose expert i
    and P_i the mean of its score normalised over the experts; the mean
    over the sequences. chosen: (B, S, K) router indices."""
    B, S, _ = chosen.shape
    scores = scores.reshape(B, S, R)
    f = jax.nn.one_hot(chosen, R, dtype=jnp.float32).sum(axis=(1, 2))
    f = f * (R / (K * S))                                    # (B, R)
    P = (scores / scores.sum(-1, keepdims=True)).mean(axis=1)
    return jnp.mean(jnp.sum(f * P, axis=-1))
