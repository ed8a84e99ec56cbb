"""The plain MLA and sigmoid-MoE reference against the program at a reduced
size, in float32, on the same weights and batch."""
import jax
import numpy as np

from bench.reference import mla_moe as ref_mla_moe
from bench.reference.numerics import init_params, matmul
from bench.tests.test_reference import close_trees, f32, program_arch

# a leading dense layer and two expert layers holding experts 4-7 of the
# router's 16, with shared experts and a balance weight large enough that
# its gradient shows
MLA_MOE = {"n_layers": 3, "first_k_dense": 1, "d_model": 32, "n_heads": 4,
           "n_kv_heads": 4, "d_ff": 48, "moe_d_ff": 16, "vocab": 300,
           "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
           "v_head_dim": 8, "rope_theta": 50000.0, "rms_norm_eps": 1e-5,
           "n_experts": 4, "router_width": 16, "first_expert": 4, "top_k": 3,
           "n_shared_experts": 2, "router": "sigmoid",
           "routed_scaling": 2.446, "seq_aux": True, "aux_weight": 0.1,
           "capacity_factor": 1.25, "activation": "silu", "gated_mlp": True,
           "tie_embeddings": False}


def test_mla_moe_loss_and_grads_match_the_program():
    """Latent attention, the sigmoid router over experts held elsewhere in
    part, shared experts and the sequence-wise balance loss, over two
    sequences, with some pairs dropped past capacity."""
    arch = program_arch("moonlight-16b-a3b", MLA_MOE)
    params = f32(init_params(ref_mla_moe.param_spec(MLA_MOE), 5, 0.02))
    # routers that prefer the held experts, so that some pairs are dropped
    router = params["slot0"]["ffn"]["router"]["kernel"]
    params["slot0"]["ffn"]["router"]["kernel"] = router.at[:, :, 4:6].add(0.3)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(arch.init, jax.random.PRNGKey(0)))
    toks = np.random.default_rng(2).integers(0, MLA_MOE["vocab"], (2, 33))
    batch = {"tokens": toks[:, :-1].astype(np.int32),
             "labels": toks[:, 1:].astype(np.int32)}
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p: arch.loss_fn(p, batch)[0]))(params)
        got, got_g = jax.jit(jax.value_and_grad(
            lambda p: ref_mla_moe.objective(p, MLA_MOE, batch,
                                            matmul("fp32"))))(params)
    # float32 on both sides: the loss to rounding, gradients through the
    # blocked softmax and capacity masks to the same tolerance as the
    # decoder reference's
    np.testing.assert_allclose(got, want, rtol=1e-5)
    close_trees(got_g, want_g, rtol=5e-4, atol=1e-6)
