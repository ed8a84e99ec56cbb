"""Mixture-of-Experts FFN with top-k routing (grok-1, granite-moe, jamba).

GShard/Mesh-TF style dense dispatch: tokens are routed to experts through a
capacity-bounded one-hot dispatch tensor and combined back with router
probabilities. On the production mesh the expert dimension is sharded over
the "model" axis (expert parallelism) so the two dispatch einsums lower to
all-to-all-like collectives — exactly the communication pattern MoE papers
optimize, and the place LANS's per-block trust ratios matter most (router
blocks see very different gradient scales than expert FFN blocks).

Includes the standard auxiliary load-balancing loss (Shazeer et al.) exposed
to the training loop.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import (ACTIVATIONS, ambient_axis_size, dense_apply,
                                 dense_init, maybe_constrain)


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    activation: str = "silu"
    gated: bool = True


def moe_init(rng, cfg: MoeConfig, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff

    def expert_stack(k, din, dout):
        # (E, din, dout) — one slab per expert, sharded over E on the mesh.
        scale = 1.0 / jnp.sqrt(din)
        return (jax.random.normal(k, (e, din, dout)) * scale).astype(dtype)

    p = {
        "router": dense_init(ks[0], d, e, use_bias=False, dtype=jnp.float32),
        "up": expert_stack(ks[1], d, f),
        "down": expert_stack(ks[2], f, d),
    }
    if cfg.gated:
        p["gate"] = expert_stack(ks[3], d, f)
    return p


def _top_k_mask(probs: jnp.ndarray, k: int):
    """(T, E) probs -> (T, E) bool mask of the per-token top-k experts."""
    _, idx = jax.lax.top_k(probs, k)  # (T, k)
    return jax.nn.one_hot(idx, probs.shape[-1], dtype=bool).any(axis=-2)


def moe_apply(p, cfg: MoeConfig, x, *, valid=None, compute_dtype=jnp.bfloat16):
    """x: (B, S, d). Returns (out, aux) with aux = load-balance loss terms.

    valid: optional (B, S) bool — False tokens (left-padding in a batched
    serving prefill) are excluded from routing: they consume no expert
    capacity (their dispatch one-hots are zeroed before the position
    cumsum), produce zero output, and drop out of the load-balance stats,
    so real tokens route identically to a pad-free run.

    GROUP-LOCAL SCATTER DISPATCH. The classic GShard one-hot dispatch
    materializes a (T, E, C) tensor — O(T^2 K / E) memory/FLOPs, which blew
    the granite-40e configs to 5.5 TB at prefill_32k (EXPERIMENTS.md §Perf
    iteration 1). Instead:
      1. tokens are split into G groups (G = ambient "data" axis size) and
         routed group-locally — each group enforces its own capacity, which
         is exactly what per-device routing does in production MoE systems;
      2. dispatch is a scatter-add into (G, E, C_local, d) expert buffers
         and combine is a gather — O(T*K*d + G*E*C_local*d), no TEC tensor.
    Expert compute stays dense einsum (MXU): experts over "model" when
    divisible (jamba 16e), otherwise the ff dim (grok 8e, granite 40e).
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
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
    cap = max(1, int(cfg.capacity_factor * Tl * K / E))

    xg = x.reshape(G, Tl, d)
    xg = maybe_constrain(xg, dp_axes or None, None, None)

    with jax.named_scope("moe.router"):
        router_logits = jnp.einsum(
            "gtd,de->gte", xg.astype(jnp.float32), p["router"]["kernel"])
        probs = jax.nn.softmax(router_logits, axis=-1)       # (G, Tl, E)

        gates_k, idx_k = jax.lax.top_k(probs, K)             # (G, Tl, K)
        gates_k = gates_k / jnp.maximum(
            gates_k.sum(-1, keepdims=True), 1e-9)

        # Position of each (token, k) within its expert's buffer, per group.
        sel = jax.nn.one_hot(idx_k, E, dtype=jnp.int32)      # (G, Tl, K, E)
        vflat = None
        if valid is not None:
            vg = valid.reshape(G, Tl)
            sel = sel * vg[..., None, None].astype(sel.dtype)  # pads: nowhere
            vflat = jnp.repeat(vg, K, axis=1)                # (G, TlK)
        sel_flat = sel.reshape(G, Tl * K, E)
        position = jnp.cumsum(sel_flat, axis=1) - 1          # (G, TlK, E)
        pos_k = jnp.take_along_axis(
            position, idx_k.reshape(G, Tl * K)[..., None], axis=-1)[..., 0]
        keep = pos_k < cap                                   # (G, TlK)
        if vflat is not None:
            keep = keep & vflat
        pos_clipped = jnp.where(keep, pos_k, cap)            # overflow bucket

    # Scatter dispatch: (G, E, cap+1, d), drop the overflow bucket after.
    with jax.named_scope("moe.dispatch"):
        flat_e = idx_k.reshape(G, Tl * K)
        x_rep = jnp.repeat(xg.astype(compute_dtype), K, axis=1)  # (G, TlK, d)

        def scatter_group(xr, e_idx, p_idx):
            buf = jnp.zeros((E, cap + 1, d), compute_dtype)
            return buf.at[e_idx, p_idx].add(xr)

        xin = jax.vmap(scatter_group)(x_rep, flat_e, pos_clipped)[:, :, :cap]
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
        yflat = yout.reshape(G, E * cap, d)
        gather_idx = jnp.minimum(
            flat_e * cap + jnp.minimum(pos_clipped, cap - 1), E * cap - 1)
        y_tk = jnp.take_along_axis(yflat, gather_idx[..., None], axis=1)
        w = (gates_k.reshape(G, Tl * K).astype(compute_dtype)
             * keep.astype(compute_dtype))
        out = (y_tk * w[..., None]).reshape(G, Tl, K, d).sum(axis=2)
        out = out.reshape(B, S, d)

    # Aux load-balancing loss (mean gate fraction * mean dispatch fraction).
    with jax.named_scope("moe.router"):
        topk_mask = sel.sum(axis=2) > 0                      # (G, Tl, E)
        density = topk_mask.astype(jnp.float32).mean(axis=(0, 1))
        density_proxy = probs.mean(axis=(0, 1))
        aux_loss = jnp.sum(density * density_proxy) * (E / K)
        entropy = -(probs * jnp.log(probs + 1e-9)).sum(-1).mean()
    return out.astype(x.dtype), {"moe_aux_loss": aux_loss,
                                 "router_entropy": entropy}
