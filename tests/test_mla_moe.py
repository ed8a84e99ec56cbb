"""Latent attention and the DeepSeek-V3 expert layer (moonlight-16b-a3b)
against the plain reference `bench/reference/mla_moe.py`, in float32 at
small sizes with seeded random weights.

Program and reference compute the same functions in float32 at
`Precision.HIGHEST`, in different orders (fused projections against
per-head products, a scatter and gather against a weighted sum over every
expert), so they agree to float32 rounding: relative 1e-5 on a loss, and
5e-4 with an absolute floor of 1e-6 on a gradient element, whose sums over
a few hundred terms cancel (the bench's own reference tests use the same).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.check import paths_of
from bench.reference import mla_moe as ref
from bench.reference.numerics import init_params, matmul
from repro.configs import reduced_arch
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib

LOSS_RTOL = 1e-5
GRAD_RTOL, GRAD_ATOL = 5e-4, 1e-6
MM = matmul("fp32")

MLA = dict(d_model=32, n_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
           qk_rope_head_dim=4, v_head_dim=8, rope_theta=50000.0)


def _close(got, want):
    got, want = paths_of(got), paths_of(want)
    assert got.keys() == want.keys()
    for p in want:
        np.testing.assert_allclose(np.asarray(got[p]), np.asarray(want[p]),
                                   rtol=GRAD_RTOL, atol=GRAD_ATOL, err_msg=p)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def test_mla_forward_and_grads_match_reference():
    """One latent-attention layer (the XLA path off a TPU): its output and
    the gradients of a weighted sum of it by every weight and the input."""
    cfg = mla_lib.MlaConfig(**MLA)
    spec = ref._mixer_spec(1, dict(MLA, n_layers=1))
    p = jax.tree.map(lambda a: a[0], _f32(init_params(spec, 7, 0.2)))
    p["kv_norm"]["scale"] = 0.1 * jnp.arange(16, dtype=jnp.float32)
    assert jax.tree.structure(p) == jax.tree.structure(
        jax.eval_shape(lambda k: mla_lib.mla_init(k, cfg),
                       jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32))

    def program(p, x):
        return mla_lib.mla_apply(p, cfg, x, compute_dtype=jnp.float32)[0]

    def reference(p, x):
        return ref._mla(p, x, MLA, MM)

    with jax.default_matmul_precision("highest"):
        outs = [jax.jit(f)(p, x) for f in (program, reference)]
        grads = [jax.jit(jax.grad(lambda p, x: jnp.sum(f(p, x) * w),
                                  argnums=(0, 1)))(p, x)
                 for f in (program, reference)]
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)
    _close(grads[0], grads[1])


def _moe(R=16, E=16, first=0, K=3, capacity=1.25, n_shared=2):
    return moe_lib.MoeConfig(
        d_model=32, d_ff=16, n_experts=E, top_k=K, capacity_factor=capacity,
        router="sigmoid", routed_scaling=2.446, router_width=R,
        first_expert=first, n_shared=n_shared, seq_aux=True)


def _moe_params(cfg, seed=3):
    p = jax.eval_shape(lambda k: moe_lib.moe_init(k, cfg),
                       jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    return jax.tree.map(
        lambda s: 0.3 * jax.random.normal(next(keys), s.shape), p)


def _expert(p, e, x):
    up = x @ p["up"][e]
    return (jax.nn.silu(x @ p["gate"][e]) * up) @ p["down"][e]


def test_sigmoid_router_selection_renormalisation_and_scaling():
    """The selection bias chooses the experts but does not weight them: a
    token's output is sum over its top-k of s + bias of s / sum(s) x 2.446
    times the expert's output, s the sigmoid of its router logits."""
    cfg = _moe(capacity=100.0, n_shared=0)     # no pair dropped
    p = _moe_params(cfg)
    bias = jnp.zeros(16).at[5].set(2.0).at[9].set(-2.0)
    p["router"]["bias"] = bias
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 12, 32))
    with jax.default_matmul_precision("highest"):
        out, _ = moe_lib.moe_apply(p, cfg, x, compute_dtype=jnp.float32)
        s = jax.nn.sigmoid(x[0] @ p["router"]["kernel"])
        _, top = jax.lax.top_k(s + bias, 3)
        ys = jnp.stack([_expert(p, e, x[0]) for e in range(16)], 1)
    assert bool(jnp.all(jnp.any(top == 5, -1)))       # bias picks 5 always
    assert not bool(jnp.any(top == 9))                # and never 9
    gates = jnp.take_along_axis(s, top, -1)
    gates = gates / gates.sum(-1, keepdims=True) * 2.446
    want = jnp.einsum("tk,tkd->td", gates,
                      jnp.take_along_axis(ys, top[..., None], 1))
    # outputs of order 1 summed over three experts' products of 32 and 16
    # terms: float32 rounding is some 1e-6 absolute
    np.testing.assert_allclose(out[0], want, rtol=1e-5, atol=1e-5)


def test_expert_shares_sum_to_the_whole_layer():
    """`model-configs` section 4: the 8 shares of a 64-expert layer (8 held
    each, the router over all 64), with the shared experts every share
    computes counted once, add up to the whole layer; and the whole layer
    is the reference's. Some pairs are dropped past capacity, alike in the
    shares and the whole."""
    whole = _moe(R=64, E=64, K=6)
    p = _moe_params(whole)
    p["router"]["kernel"] = p["router"]["kernel"].at[:, 8:11].add(0.5)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 32))
    with jax.default_matmul_precision("highest"):
        want, want_aux = moe_lib.moe_apply(p, whole, x,
                                           compute_dtype=jnp.float32)
        parts, auxes = [], []
        for i in range(8):
            share = dataclasses.replace(whole, n_experts=8, first_expert=8 * i)
            held = dict(p, **{k: p[k][8 * i:8 * i + 8]
                              for k in ("up", "gate", "down")})
            out, aux = moe_lib.moe_apply(held, share, x,
                                         compute_dtype=jnp.float32)
            parts.append(out)
            auxes.append(aux["moe_aux_loss"])
        shared = moe_lib.mlp_apply(p["shared"], x, compute_dtype=jnp.float32)
        model = dict(n_experts=64, router_width=64, top_k=6, first_expert=0,
                     capacity_factor=1.25, routed_scaling=2.446)
        ref_out, ref_aux = ref._experts(p, x.reshape(64, 32), 2, model, MM)
    # outputs of order 1, each a sum of up to 6 experts' products and the
    # shared MLP's: float32 rounding is some 1e-6 absolute
    np.testing.assert_allclose(sum(parts) - 7 * shared, want, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(want.reshape(64, 32), ref_out, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(auxes, [want_aux["moe_aux_loss"]] * 8,
                               rtol=1e-6)
    np.testing.assert_allclose(want_aux["moe_aux_loss"], ref_aux, rtol=1e-5)
    # the held experts' part of one share is not the whole routed part
    assert not np.allclose(parts[1] - shared, want - shared, atol=1e-3)


MODEL_KEYS = ("n_layers", "first_k_dense", "d_model", "n_heads", "d_ff",
              "moe_d_ff", "vocab", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "rope_theta", "rms_norm_eps",
              "n_experts", "router_width", "first_expert", "top_k",
              "n_shared_experts", "routed_scaling", "aux_weight",
              "capacity_factor")


@pytest.mark.parametrize("aux_weight", [1e-4, 0.1])
def test_small_moonlight_loss_and_grads_match_reference(aux_weight):
    """The registered arch's reduced variant (a dense layer and two expert
    layers at small widths), through its own loss, against the reference
    on the same weights: at the published balance weight and at one large
    enough that the balance loss's gradient shows."""
    arch = reduced_arch("moonlight-16b-a3b")
    cfg = dataclasses.replace(arch.cfg, compute_dtype=jnp.float32,
                              aux_weight=aux_weight)
    arch = dataclasses.replace(arch, cfg=cfg)
    model = {k: getattr(cfg, k) for k in MODEL_KEYS}
    params = _f32(init_params(ref.param_spec(model), 6, 0.02))
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(arch.init, jax.random.PRNGKey(0)))
    toks = np.random.default_rng(3).integers(0, cfg.vocab, (2, 65))
    batch = {"tokens": toks[:, :-1].astype(np.int32),
             "labels": toks[:, 1:].astype(np.int32)}
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p: arch.loss_fn(p, batch)[0]))(params)
        got, got_g = jax.jit(jax.value_and_grad(
            lambda p: ref.objective(p, model, batch, MM)))(params)
    np.testing.assert_allclose(got, want, rtol=LOSS_RTOL)
    _close(got_g, want_g)
