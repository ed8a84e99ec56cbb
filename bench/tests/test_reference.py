"""The plain references against the program at reduced sizes, in float32.

The references import nothing of the program; here the program is run in
float32 on the same weights and batch, so the two must agree to float32
rounding: BERT's loss and gradients, the decoder's loss and gradients with
experts dropping pairs past capacity, and LANS over three steps.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.check import paths_of
from bench.reference import bert as ref_bert
from bench.reference import decoder as ref_decoder
from bench.reference import lans as ref_lans
from bench.reference.numerics import init_params, matmul

BERT = {"n_layers": 2, "d_model": 32, "n_heads": 4, "d_ff": 64, "vocab": 300,
        "max_pos": 16, "type_vocab": 2, "layer_norm_eps": 1e-6}
DECODER = {"n_layers": 2, "d_model": 32, "n_heads": 4, "n_kv_heads": 2,
           "head_dim": 8, "d_ff": 16, "vocab": 300, "n_experts": 8,
           "top_k": 2, "capacity_factor": 1.25, "activation": "silu",
           "gated_mlp": True, "rope_theta": 10000.0, "tie_embeddings": True,
           "rms_norm_eps": 1e-6, "aux_weight": 0.01}
OPT = {"beta1": 0.9, "beta2": 0.999, "eps": 1e-6, "weight_decay": 0.01,
       "no_decay": ["bias", "norm", "ln_"], "lr": 0.01}


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def close_trees(got, want, rtol, atol):
    got, want = paths_of(got), paths_of(want)
    assert got.keys() == want.keys()
    for p in want:
        np.testing.assert_allclose(np.asarray(got[p]), np.asarray(want[p]),
                                   rtol=rtol, atol=atol, err_msg=p)


def program_arch(name, model):
    from repro.configs import get_arch

    arch = get_arch(name)
    keys = {k: v for k, v in model.items() if hasattr(arch.cfg, k)}
    cfg = dataclasses.replace(arch.cfg, compute_dtype=jnp.float32,
                              param_dtype=jnp.float32, **keys)
    return dataclasses.replace(arch, cfg=cfg)


def bert_batch(rng, B, S, V):
    labels = np.where(rng.random((B, S)) < 0.2,
                      rng.integers(110, V, (B, S)), -100).astype(np.int32)
    return {"tokens": rng.integers(0, V, (B, S)).astype(np.int32),
            "token_types": (np.arange(S)[None] >= S // 2).repeat(B, 0)
            .astype(np.int32),
            "mlm_labels": labels,
            "nsp_labels": rng.integers(0, 2, B).astype(np.int32)}


def test_bert_loss_and_grads_match_the_program():
    arch = program_arch("bert-large", BERT)
    params = f32(init_params(ref_bert.param_spec(BERT), 3, 0.02))
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(arch.init, jax.random.PRNGKey(0)))
    batch = bert_batch(np.random.default_rng(0), 4, 16, BERT["vocab"])
    norms = ref_bert.totals(batch)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p: arch.loss_fn(p, batch)[0]))(params)
        ref = jax.jit(jax.value_and_grad(
            lambda p, b: ref_bert.objective(p, BERT, b, matmul("fp32"),
                                            *norms)))
        got, got_g = ref(params, batch)
        # two blocks of rows add up to the whole batch
        halves = [ref(params, {k: v[i:i + 2] for k, v in batch.items()})
                  for i in (0, 2)]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    close_trees(got_g, want_g, rtol=2e-4, atol=1e-7)
    np.testing.assert_allclose(halves[0][0] + halves[1][0], want, rtol=1e-5)
    close_trees(jax.tree.map(jnp.add, halves[0][1], halves[1][1]), want_g,
                rtol=2e-4, atol=1e-7)


def test_decoder_moe_loss_and_grads_match_the_program():
    arch = program_arch("granite-moe-3b-a800m", DECODER)
    params = f32(init_params(ref_decoder.param_spec(DECODER), 4, 0.02))
    # a router that prefers a few experts, so that some pairs are dropped
    router = params["slot0"]["ffn"]["router"]["kernel"]
    params["slot0"]["ffn"]["router"]["kernel"] = router.at[:, :, :2].add(0.3)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(arch.init, jax.random.PRNGKey(0)))
    toks = np.random.default_rng(1).integers(0, DECODER["vocab"], (1, 65))
    batch = {"tokens": toks[:, :-1].astype(np.int32),
             "labels": toks[:, 1:].astype(np.int32)}
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p: arch.loss_fn(p, batch)[0]))(params)
        got, got_g = jax.jit(jax.value_and_grad(
            lambda p: ref_decoder.objective(p, DECODER, batch,
                                            matmul("fp32"))))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    close_trees(got_g, want_g, rtol=5e-4, atol=1e-6)


@pytest.mark.parametrize("path", ["layers/attn/wq/kernel", "embed_ln/bias",
                                  "slot0/pre_ffn_norm/scale"])
def test_lans_matches_the_program(path):
    from repro.core.optim import lans

    rng = np.random.default_rng(2)
    x = {path: jnp.asarray(rng.normal(size=(3, 5)), jnp.float32)}
    tx = lans(OPT["lr"])
    state = tx.init(x)
    want = x
    m = v = jnp.zeros((3, 5), jnp.float32)
    got = x[path]
    for t in (1, 2, 3):
        g = {path: jnp.asarray(rng.normal(size=(3, 5)), jnp.float32)}
        upd, state = tx.update(g, state, want)
        want = jax.tree.map(jnp.add, want, upd)
        got, m, v = ref_lans.update(g[path], m, v, got, t, OPT,
                                    ref_lans.decays(path, OPT))
    np.testing.assert_allclose(got, want[path], rtol=1e-5, atol=1e-7)
