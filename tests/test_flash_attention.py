"""The training attention kernel (models/attention._flash_attend) against the
XLA path it replaces, and the rule in `attention.attend` that chooses
between them for attn_apply and mla_apply.

The kernel runs in Pallas interpret mode here (`_flash_attend` interprets
off a TPU); tests/test_tpu_compile.py compiles it for a described TPU.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention, mla
from repro.models.attention import (AttnConfig, attn_apply, attn_init,
                                    init_kv_cache)

pytestmark = pytest.mark.kernels

HD = 64
# The kernel rounds the output and each gradient to bfloat16 (half an ulp,
# 2^-9 relative), and the probabilities and the score gradients to
# bfloat16 before the products that take them, 2^-9 each, summed over keys
# whose rounding errors partly cancel. Four bfloat16 epsilons (4 * 2^-8)
# of the largest reference value bound the sum.
TOL = 4 * 2.0 ** -8


# B, S, heads, kv heads, causal, block, shared key part
CASES = {
    # BERT-like: bidirectional MHA, the shape rule's block (one per axis:
    # the backward's single kernel writes dQ too)
    "bidirectional_mha": (2, 256, 4, 4, False, attention.flash_block(256), 0),
    # granite-like: causal GQA, two blocks on each axis, so the online
    # softmax carries across key blocks, the block above the diagonal is
    # skipped and dQ has a kernel of its own
    "causal_gqa": (1, 512, 6, 2, True, 256, 0),
    # keys that share a large part across positions, as BERT's key bias
    # makes them: dQ is small beside the rounding of terms that carry it
    "shared_key_part": (2, 256, 4, 4, False, 256, 4),
    # latent attention (MLA): keys 192 wide, values 128, causal over two
    # blocks on each axis, so every kernel takes the two widths
    "mla_widths": (1, 512, 2, 2, True, 256, 0),
}
# (key width, value width) of a case where they differ from HD
WIDTHS = {"mla_widths": (192, 128)}


def _qkvw(B, S, h, kv, shared, dk=HD, dv=HD):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, S, h, dk)).astype(jnp.bfloat16)
    noise = 0.1 if shared else 1.0
    k = (shared * jax.random.normal(ks[4], (1, 1, kv, dk))
         + noise * jax.random.normal(ks[1], (B, S, kv, dk))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, kv, dv)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[3], (B, S, h, dv)).astype(jnp.bfloat16)
    return q, k, v, w


@pytest.mark.parametrize("case", sorted(CASES))
def test_flash_matches_xla_path(case):
    """Output and dQ, dK, dV of the kernel path and of `attend`'s XLA path
    on the same bfloat16 inputs, against the XLA path in float32: the
    kernel is within TOL of it, and no further from it than the XLA path
    is (plus one bfloat16 epsilon)."""
    B, S, h, kv, causal, block, shared = CASES[case]
    dk, dv = WIDTHS.get(case, (HD, HD))
    q, k, v, w = _qkvw(B, S, h, kv, shared, dk, dv)
    pos = jnp.arange(S)
    scale = dk ** -0.5

    def rep(a):  # GQA as attend does it: its transpose sums each group
        return jnp.repeat(a, h // kv, axis=2)

    def xla(q, k, v):  # off a TPU, attend takes the XLA path
        return attention.attend(q, k, v, pos, causal=causal, scale=scale,
                                compute_dtype=q.dtype)

    def flash(q, k, v):
        return attention._flash_attend(q, rep(k), rep(v), causal=causal,
                                       scale=scale, block=block)

    def run(fn, dtype=jnp.bfloat16):
        out, vjp = jax.vjp(fn, *(a.astype(dtype) for a in (q, k, v)))
        return [np.asarray(a, np.float32)
                for a in (out, *vjp(w.astype(dtype)))]

    exact = run(xla, jnp.float32)
    for name, got, ref, want in zip(("out", "dq", "dk", "dv"), run(flash),
                                    run(xla), exact):
        assert got.shape == want.shape, name
        err = np.abs(got - want).max() / np.abs(want).max()
        err_xla = np.abs(ref - want).max() / np.abs(want).max()
        assert err <= TOL and err <= err_xla + 2.0 ** -8, (name, err, err_xla)


D_MODEL, HEADS, KV_HEADS = 2 * HD, 2, 1
B = 2


def _cfg(**kw):
    return AttnConfig(d_model=D_MODEL, n_heads=HEADS, n_kv_heads=KV_HEADS,
                      head_dim=HD, **kw)


def _x(S):
    return jnp.zeros((B, S, D_MODEL), jnp.bfloat16)


def _attn(over=None, extra=lambda S: {}):
    """attn_apply at length S with cfg overrides and extra kwargs (by S)."""
    def build(S):
        cfg = _cfg(**(over or {}))
        params = attn_init(jax.random.PRNGKey(0), cfg)
        return functools.partial(attn_apply, params, cfg,
                                 compute_dtype=jnp.bfloat16, **extra(S))
    return build


def _mla(S):
    """mla_apply: keys 2 * HD wide (half of them rotary), values HD."""
    del S
    cfg = mla.MlaConfig(d_model=D_MODEL, n_heads=HEADS, kv_lora_rank=HD,
                        qk_nope_head_dim=HD, qk_rope_head_dim=HD,
                        v_head_dim=HD)
    params = mla.mla_init(jax.random.PRNGKey(0), cfg)
    return functools.partial(mla.mla_apply, params, cfg,
                             compute_dtype=jnp.bfloat16)


# name: (takes the kernel, S, the attention at length S); every case but
# the *cpu_backend ones runs with the backend patched to a TPU
SELECTION = {
    "training_self_attention": (True, 512, _attn()),
    "bidirectional": (True, 512, _attn({"causal": False})),
    "long_causal": (True, 2048, _attn()),
    "cache": (False, 512, _attn(extra=lambda S: {
        "cache": init_kv_cache(B, S, KV_HEADS, HD)})),
    "kv_x": (False, 512, _attn({"causal": False},
                               lambda S: {"kv_x": _x(S)})),
    "kv_cache": (False, 512, _attn({"causal": False}, lambda S: {
        "kv_cache": {"k": jnp.zeros((B, S, KV_HEADS, HD), jnp.bfloat16),
                     "v": jnp.zeros((B, S, KV_HEADS, HD), jnp.bfloat16)}})),
    "positions_2d": (False, 512, _attn(extra=lambda S: {
        "positions": jnp.broadcast_to(jnp.arange(S), (B, S))})),
    "softcap": (False, 512, _attn({"logit_softcap": 50.0})),
    "sliding_window": (False, 512, _attn({"sliding_window": 128})),
    "off_block_grid": (False, 1536, _attn()),
    "below_threshold": (False, attention.FLASH_MIN_SEQ // 2, _attn()),
    "cpu_backend": (False, 512, _attn()),
    # a ring cache shorter than the prompt (a sliding-window layer's
    # prefill): the in-flight keys are attended, through XLA
    "ring_prefill": (False, 512, _attn({"sliding_window": 128}, lambda S: {
        "cache": init_kv_cache(B, 128, KV_HEADS, HD)})),
    "mla": (True, 512, _mla),
    "mla_cpu_backend": (False, 512, _mla),
}


@pytest.mark.parametrize("case", sorted(SELECTION))
def test_flash_path_selection(case, monkeypatch):
    """attn_apply and mla_apply trace the kernel for training-shaped
    self-attention on a TPU, and the XLA path in every other case."""
    takes, S, build = SELECTION[case]
    if not case.endswith("cpu_backend"):
        monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    jaxpr = jax.make_jaxpr(build(S))(_x(S))
    assert ("pallas_call" in str(jaxpr)) == takes
