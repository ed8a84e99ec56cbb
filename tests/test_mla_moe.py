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
import json
import os
import pathlib
import re
import subprocess
import sys

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
from repro.models.common import (ACTIVATIONS, ambient_axis_size,
                                 maybe_constrain, mlp_apply)

ROOT = pathlib.Path(__file__).resolve().parents[1]

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


def _scatter_moe_apply(p, cfg, x, *, valid=None, compute_dtype=jnp.bfloat16):
    """The expert layer as it was before dispatch and combine became gathers
    through a pair <-> slot map: every token repeated K times and
    scatter-added into (G, E, cap + 1, d) buffers, dropped pairs into the
    last row, and combine a gather whose adjoint is a scatter. Returns
    (out, aux, groups, dropped pairs)."""
    B, S, d = x.shape
    E, K, R = cfg.n_experts, cfg.top_k, cfg.n_routed
    partial = R != E or cfg.first_expert != 0
    T = B * S
    dp_axes = tuple(a for a in ("pod", "data") if ambient_axis_size(a) > 1)
    G = max(1, ambient_axis_size("pod") * ambient_axis_size("data"))
    while T % G != 0:
        G //= 2
    G = max(G, 1)
    Tl = T // G
    cap = max(1, int(cfg.capacity_factor * Tl * K / R))
    xg = maybe_constrain(x.reshape(G, Tl, d), dp_axes or None, None, None)

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        p["router"]["kernel"])
    if cfg.router == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        _, idx_k = jax.lax.top_k(probs + p["router"]["bias"], K)
        gates_k = jnp.take_along_axis(probs, idx_k, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates_k, idx_k = jax.lax.top_k(probs, K)
    chosen = idx_k
    gates_k = gates_k / jnp.maximum(gates_k.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scaling != 1.0:
        gates_k = gates_k * cfg.routed_scaling
    held = None
    if partial:
        local = idx_k - cfg.first_expert
        held = (local >= 0) & (local < E)
        idx_k = jnp.where(held, local, -1)
    sel = jax.nn.one_hot(idx_k, E, dtype=jnp.int32)
    if partial:
        idx_k = jnp.maximum(idx_k, 0)
    vflat = None
    if valid is not None:
        vg = valid.reshape(G, Tl)
        sel = sel * vg[..., None, None].astype(sel.dtype)
        vflat = jnp.repeat(vg, K, axis=1)
    sel_flat = sel.reshape(G, Tl * K, E)
    position = jnp.cumsum(sel_flat, axis=1) - 1
    pos_k = jnp.take_along_axis(
        position, idx_k.reshape(G, Tl * K)[..., None], axis=-1)[..., 0]
    keep = pos_k < cap
    placed = jnp.ones_like(keep)
    if vflat is not None:
        placed = placed & vflat
    if held is not None:
        placed = placed & held.reshape(G, Tl * K)
    dropped = jnp.sum(placed & ~keep)
    keep = keep & placed
    pos_clipped = jnp.where(keep, pos_k, cap)

    flat_e = idx_k.reshape(G, Tl * K)
    x_rep = jnp.repeat(xg.astype(compute_dtype), K, axis=1)

    def scatter_group(xr, e_idx, p_idx):
        buf = jnp.zeros((E, cap + 1, d), compute_dtype)
        return buf.at[e_idx, p_idx].add(xr)

    xin = jax.vmap(scatter_group)(x_rep, flat_e, pos_clipped)[:, :, :cap]
    act = ACTIVATIONS[cfg.activation]
    up = jnp.einsum("gecd,edf->gecf", xin, p["up"].astype(compute_dtype))
    if cfg.gated:
        up = act(jnp.einsum("gecd,edf->gecf", xin,
                            p["gate"].astype(compute_dtype))) * up
    else:
        up = act(up)
    yout = jnp.einsum("gecf,efd->gecd", up, p["down"].astype(compute_dtype))
    yflat = yout.reshape(G, E * cap, d)
    gather_idx = jnp.minimum(
        flat_e * cap + jnp.minimum(pos_clipped, cap - 1), E * cap - 1)
    y_tk = jnp.take_along_axis(yflat, gather_idx[..., None], axis=1)
    w = (gates_k.reshape(G, Tl * K).astype(compute_dtype)
         * keep.astype(compute_dtype))
    out = (y_tk * w[..., None]).reshape(G, Tl, K, d).sum(axis=2)
    out = out.reshape(B, S, d)
    if cfg.n_shared:
        out = out + mlp_apply(p["shared"], x, activation=cfg.activation,
                              compute_dtype=compute_dtype)
    if cfg.seq_aux:
        aux_loss = moe_lib._sequence_balance(
            probs, chosen.reshape(B, S, K), R, K)
        probs = probs / probs.sum(-1, keepdims=True)
    else:
        if partial:
            topk_mask = jax.nn.one_hot(chosen, R, dtype=jnp.int32
                                       ).sum(axis=2) > 0
        else:
            topk_mask = sel.sum(axis=2) > 0
        density = topk_mask.astype(jnp.float32).mean(axis=(0, 1))
        aux_loss = jnp.sum(density * probs.mean(axis=(0, 1))) * (R / K)
    entropy = -(probs * jnp.log(probs + 1e-9)).sum(-1).mean()
    aux = {"moe_aux_loss": aux_loss, "router_entropy": entropy}
    return out.astype(x.dtype), aux, G, dropped


def _softmax_moe(E=16, K=8, capacity=0.5):
    return moe_lib.MoeConfig(d_model=32, d_ff=16, n_experts=E, top_k=K,
                             capacity_factor=capacity)


# name: (config, (B, S), left pads per sequence or None, data mesh size)
DISPATCH_CASES = {
    "softmax-top8-drops": (_softmax_moe(), (2, 24), None, 1),
    "sigmoid-top6-held-8-of-64": (_moe(R=64, E=8, first=16, K=6),
                                  (2, 24), None, 1),
    "valid-pads": (_softmax_moe(E=8, K=2, capacity=0.75), (3, 16),
                   (0, 5, 11), 1),
    "groups-2-data-mesh": (_softmax_moe(), (4, 12), (0, 3, 0, 7), 2),
}


def _dispatch_case(name):
    """The layer and the scatter reference on one case, in float32: both
    outputs, aux terms and gradients of a weighted sum of the output plus
    the aux terms by every weight and the input; the reference's groups and
    dropped pairs."""
    cfg, (B, S), pads, data = DISPATCH_CASES[name]
    p = _moe_params(cfg, seed=11)
    if cfg.router == "sigmoid":   # crowd the held experts, so some drop
        p["router"]["kernel"] = p["router"]["kernel"].at[:, 16:24].add(0.4)
    x = jax.random.normal(jax.random.PRNGKey(12), (B, S, 32))
    wt = jax.random.normal(jax.random.PRNGKey(13), (B, S, 32))
    valid = None
    if pads is not None:
        valid = jnp.arange(S)[None, :] >= jnp.asarray(pads)[:, None]

    def objective(f):
        def fn(p, x):
            out, aux = f(p, cfg, x, valid=valid,
                         compute_dtype=jnp.float32)[:2]
            return (jnp.sum(out * wt) + aux["moe_aux_loss"]
                    + aux["router_entropy"]), (out, aux)
        return jax.jit(jax.grad(fn, argnums=(0, 1), has_aux=True))

    from repro.launch.mesh import make_local_mesh
    with make_local_mesh(data=data, model=1), \
            jax.default_matmul_precision("highest"):
        got = objective(moe_lib.moe_apply)(p, x)
        want = objective(_scatter_moe_apply)(p, x)
        _, _, groups, dropped = jax.jit(
            lambda p, x: _scatter_moe_apply(p, cfg, x, valid=valid,
                                            compute_dtype=jnp.float32)
        )(p, x)
    return got, want, int(groups), int(dropped)


def _same(got, want):
    """Gathers move the very rows the scatters moved, and every sum runs
    over the same terms in the same order: the numbers are equal."""
    got, want = paths_of(got), paths_of(want)
    assert got.keys() == want.keys()
    for p in want:
        np.testing.assert_array_equal(np.asarray(got[p]),
                                      np.asarray(want[p]), err_msg=p)


@pytest.mark.parametrize("case", list(DISPATCH_CASES))
def test_gather_dispatch_matches_scatter_reference(case):
    """Dispatch and combine through the pair <-> slot map give the scatter
    formulation's output, aux terms and gradients: with pairs dropped past
    capacity, a held-expert share of a sigmoid router, left pads, and two
    groups on a two-device data mesh (a process of its own, its CPU split
    in two devices)."""
    data = DISPATCH_CASES[case][3]
    if data > 1 and jax.device_count() < data:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       filter(None, [str(ROOT / "src"), str(ROOT),
                                     os.environ.get("PYTHONPATH")])),
                   XLA_FLAGS=" ".join(filter(None, [
                       os.environ.get("XLA_FLAGS"),
                       f"--xla_force_host_platform_device_count={data}"])))
        proc = subprocess.run([sys.executable, __file__, case], env=env,
                              cwd=ROOT, capture_output=True, text=True,
                              timeout=600)
        assert proc.returncode == 0, proc.stderr[-4000:]
        groups, dropped = json.loads(proc.stdout.strip().splitlines()[-1])
    else:
        got, want, groups, dropped = _dispatch_case(case)
        _same(got, want)
    assert groups == data
    assert dropped > 0


@pytest.mark.parametrize("arch", ["granite-moe-3b-a800m", "moonlight-16b-a3b"])
def test_train_step_moves_expert_rows_without_scatter(arch):
    """The compiled train step of each expert config has no scatter under
    `moe.dispatch` or `moe.combine` in any pass (forward, recompute,
    backward), while both scopes still hold ops in each."""
    from bench import scopes
    from repro.launch import train

    args = train.build_parser().parse_args(
        ["--arch", arch, "--batch", "2", "--seq", "16", "--precision", "bf16"])
    tr = train.make_trainer(args)
    train.train_steps(tr, 1)
    hlo = tr.compiled.as_text()
    scatters = [line for line in hlo.splitlines()
                if re.search(r"=\s*\S+\s+scatter\(", line)
                and re.search(r'op_name="[^"]*moe\.(dispatch|combine)', line)]
    assert scatters == []
    found = set(scopes.scope_map(hlo).values())
    for s in ("moe.dispatch", "moe.combine"):
        assert {"forward", "recompute", "backward"} <= {
            k for t, k in found if t == s}


if __name__ == "__main__":
    got, want, groups, dropped = _dispatch_case(sys.argv[1])
    _same(got, want)
    print(json.dumps([groups, dropped]))
