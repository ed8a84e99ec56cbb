"""Kernel-backed LANS/LAMB: the Pallas fused step as a GradientTransformation.

Drop-in replacement for `lans(...)` / `lamb(...)` that routes every block
through the 3-phase Pallas pipeline (repro.kernels.ops). This is the TPU
analogue of the paper's `fused_lans` apex optimizer. The kernels compile on a
TPU and run in interpret mode elsewhere (`interpret=None`, see
`repro.kernels.default_interpret`).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.optim.base import (
    GradientTransformation,
    WeightDecayMask,
    tree_paths,
)
from repro.kernels import ops


class FusedState(NamedTuple):
    count: jnp.ndarray
    mu: jnp.ndarray
    nu: jnp.ndarray


def _make_fused(step_fn, needs_clip: bool):
    def factory(
        learning_rate,
        beta1: float = 0.9,
        beta2: float = 0.999,
        eps: float = 1e-6,
        weight_decay: float = 0.01,
        decay_mask: Optional[Callable[[str], bool]] = None,
        grad_clip_norm: Optional[float] = 1.0,
        interpret=None,
    ) -> GradientTransformation:
        mask_fn = decay_mask or WeightDecayMask()
        sched = learning_rate if callable(learning_rate) else (
            lambda _: jnp.asarray(learning_rate, jnp.float32))

        def init_fn(params):
            zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
            return FusedState(
                count=jnp.zeros([], jnp.int32),
                mu=jax.tree.map(zeros, params),
                nu=jax.tree.map(zeros, params),
            )

        def update_fn(updates, state, params):
            if params is None:
                raise ValueError("fused optimizers require params")
            paths = tree_paths(params)
            masks = jax.tree.map(lambda pth: bool(mask_fn(pth)), paths)
            t = state.count + 1
            eta = sched(state.count)

            clip_kw = {}
            if needs_clip:
                if grad_clip_norm is not None:
                    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree_util.tree_leaves(updates))
                    gnorm = jnp.sqrt(sq)
                    clip_kw["clip"] = jnp.minimum(
                        1.0, grad_clip_norm / jnp.maximum(gnorm, 1e-12))
                else:
                    clip_kw["clip"] = jnp.float32(1.0)

            flat_g, treedef = jax.tree_util.tree_flatten(updates)
            outs = []
            for g, m, v, x, dm in zip(
                flat_g,
                treedef.flatten_up_to(state.mu),
                treedef.flatten_up_to(state.nu),
                treedef.flatten_up_to(params),
                treedef.flatten_up_to(masks),
            ):
                o = step_fn(
                    g, m, v, x, eta=eta, step=t,
                    beta1=beta1, beta2=beta2, eps=eps,
                    lam=weight_decay if dm else 0.0,
                    apply_trust=bool(dm),
                    interpret=interpret, **clip_kw)
                # Express as an additive update: delta = x_new - x.
                outs.append(((o.x - x).astype(x.dtype), o.m, o.v))
            new_d = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
            new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
            new_v = jax.tree_util.tree_unflatten(treedef, [o[2] for o in outs])
            return new_d, FusedState(count=t, mu=new_m, nu=new_v)

        return GradientTransformation(init_fn, update_fn)

    return factory


fused_lans = _make_fused(ops.fused_lans_step, needs_clip=False)
fused_lamb = _make_fused(ops.fused_lamb_step, needs_clip=True)
