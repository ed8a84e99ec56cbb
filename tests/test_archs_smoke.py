"""Per-arch smoke tests: reduced variant, one forward + one train step on
CPU, asserting output shapes and no NaNs (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, SHAPES, get_arch, reduced_arch
from repro.core.optim import apply_updates, lans

ALL = ASSIGNED + ["bert-large", "moonlight-16b-a3b"]


def _batch(arch, rng, B=2, S=32):
    cfg = arch.cfg
    if arch.kind == "bert":
        return {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab),
                "token_types": jnp.zeros((B, S), jnp.int32),
                "mlm_labels": jnp.where(
                    jax.random.bernoulli(rng, 0.15, (B, S)),
                    jax.random.randint(rng, (B, S), 0, cfg.vocab), -100),
                "nsp_labels": jnp.zeros((B,), jnp.int32)}
    if arch.kind == "encdec":
        return {"frames": jax.random.normal(rng, (B, cfg.n_frames, cfg.d_model)),
                "tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab),
                "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab)}
    if arch.embeds_input:
        return {"embeds": 0.02 * jax.random.normal(rng, (B, S, cfg.d_model)),
                "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab)}
    return {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab),
            "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab)}


@pytest.mark.parametrize("name", ALL)
def test_reduced_forward_and_train_step(name):
    arch = reduced_arch(name)
    rng = jax.random.PRNGKey(0)
    params = arch.init(rng)
    batch = _batch(arch, rng)

    loss, aux = arch.loss_fn(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), name

    tx = lans(1e-3)
    st = tx.init(params)

    @jax.jit
    def step(params, st, batch):
        (l, _), g = jax.value_and_grad(arch.loss_fn, has_aux=True)(params, batch)
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        upd, st = tx.update(g, st, params)
        return apply_updates(params, upd), st, l

    p2, st, l = step(params, st, batch)
    assert bool(jnp.isfinite(l)), name
    for leaf in jax.tree.leaves(p2):
        assert bool(jnp.all(jnp.isfinite(leaf))), name
    # params actually moved
    moved = any(bool(jnp.any(a != b))
                for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert moved, name


@pytest.mark.parametrize("name", [n for n in ASSIGNED
                                  if get_arch(n).kind != "bert"])
def test_reduced_decode_step(name):
    """prefill + 2 decode steps on the reduced variant; shapes + finiteness."""
    arch = reduced_arch(name)
    rng = jax.random.PRNGKey(1)
    params = arch.init(rng)
    B, S = 2, 16
    toks = jax.random.randint(rng, (B, S), 0, arch.cfg.vocab)

    if arch.kind == "encdec":
        frames = jax.random.normal(rng, (B, arch.cfg.n_frames, arch.cfg.d_model))
        logits, cache = arch.prefill(params, {"frames": frames, "tokens": toks},
                                     cache_len=S + 4)
        step_extra = {"memory": __import__("repro.models.encdec",
                                           fromlist=["encode"]).encode(
                                               params, arch.cfg, frames)}
    else:
        logits, cache = arch.prefill(params, {"tokens": toks}, cache_len=S + 4)
        step_extra = {}
    assert logits.shape[:2] == (B, 1)
    nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    for _ in range(2):
        batch = {"tokens": nxt[:, None], **step_extra}
        logits, cache = arch.decode_step(params, batch, cache)
        assert logits.shape == (B, 1, arch.cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits))), name
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)


@pytest.mark.parametrize("name", ALL)
def test_full_config_matches_assignment(name):
    """The FULL configs carry the exact assigned hyperparameters."""
    arch = get_arch(name)
    expected = {
        "grok-1-314b": dict(n_layers=64, d_model=6144, n_heads=48,
                            n_kv_heads=8, d_ff=32768, vocab=131072,
                            n_experts=8, top_k=2),
        "granite-moe-3b-a800m": dict(n_layers=32, d_model=1536, n_heads=24,
                                     n_kv_heads=8, d_ff=512, vocab=49155,
                                     n_experts=40, top_k=8),
        "qwen2.5-14b": dict(n_layers=48, d_model=5120, n_heads=40,
                            n_kv_heads=8, d_ff=13824, vocab=152064,
                            qkv_bias=True),
        "qwen2.5-32b": dict(n_layers=64, d_model=5120, n_heads=40,
                            n_kv_heads=8, d_ff=27648, vocab=152064,
                            qkv_bias=True),
        "chameleon-34b": dict(n_layers=48, d_model=8192, n_heads=64,
                              n_kv_heads=8, d_ff=22016, vocab=65536),
        "whisper-large-v3": dict(n_layers=32, d_model=1280, n_heads=20,
                                 d_ff=5120, vocab=51866),
        "mistral-nemo-12b": dict(n_layers=40, d_model=5120, n_heads=32,
                                 n_kv_heads=8, d_ff=14336, vocab=131072),
        "jamba-1.5-large-398b": dict(n_layers=72, d_model=8192, n_heads=64,
                                     n_kv_heads=8, d_ff=24576, vocab=65536,
                                     n_experts=16, top_k=2),
        "mamba2-130m": dict(n_layers=24, d_model=768, vocab=50280,
                            mamba_dstate=128),
        "gemma2-2b": dict(n_layers=26, d_model=2304, n_heads=8,
                          n_kv_heads=4, d_ff=9216, vocab=256000),
        "bert-large": dict(n_layers=24, d_model=1024, n_heads=16,
                           d_ff=4096, vocab=30522),
        "moonlight-16b-a3b": dict(n_layers=27, first_k_dense=1,
                                  d_model=2048, n_heads=16, d_ff=11264,
                                  moe_d_ff=1408, vocab=163840,
                                  kv_lora_rank=512, qk_nope_head_dim=128,
                                  qk_rope_head_dim=64, v_head_dim=128,
                                  n_experts=64, router_width=64, top_k=6,
                                  n_shared_experts=2, router="sigmoid",
                                  routed_scaling=2.446, rms_norm_eps=1e-5,
                                  rope_theta=50000.0, tie_embeddings=False),
    }[name]
    for k, v in expected.items():
        assert getattr(arch.cfg, k) == v, (name, k, getattr(arch.cfg, k), v)


def test_param_counts_match_assigned_sizes():
    """Total parameters land near the names on the tin."""
    sizes = {"grok-1-314b": 314e9, "qwen2.5-14b": 14e9, "qwen2.5-32b": 32e9,
             "chameleon-34b": 34e9, "mistral-nemo-12b": 12e9,
             "jamba-1.5-large-398b": 398e9, "mamba2-130m": 130e6,
             "gemma2-2b": 2.6e9, "moonlight-16b-a3b": 16e9}
    for name, want in sizes.items():
        got = get_arch(name).param_count()
        assert 0.8 * want <= got <= 1.25 * want, (name, got, want)


def test_long_500k_support_flags():
    """Sub-quadratic archs run long_500k; pure full-attention archs skip."""
    runs = {n for n in ASSIGNED if get_arch(n).supports("long_500k")}
    assert runs == {"mamba2-130m", "jamba-1.5-large-398b", "gemma2-2b",
                    "mistral-nemo-12b"}


def test_input_specs_cover_all_supported_shapes():
    for name in ASSIGNED:
        arch = get_arch(name)
        for shape in SHAPES:
            if not arch.supports(shape):
                continue
            specs = arch.input_specs(shape)
            assert specs, (name, shape)
            for k, v in specs.items():
                assert hasattr(v, "shape") and hasattr(v, "dtype"), (name, k)
