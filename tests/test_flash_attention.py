"""The training attention kernel (models/attention._flash_attend) against the
XLA path it replaces, and the rule that chooses between them.

The kernel runs in Pallas interpret mode here (`_flash_attend` interprets
off a TPU); tests/test_tpu_compile.py compiles it for a described TPU.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention
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
    """Output and dQ, dK, dV of the kernel path and of `_attend_block` on
    the same bfloat16 inputs, against `_attend_block` in float32: the
    kernel is within TOL of it, and no further from it than the XLA path
    is (plus one bfloat16 epsilon)."""
    B, S, h, kv, causal, block, shared = CASES[case]
    dk, dv = WIDTHS.get(case, (HD, HD))
    q, k, v, w = _qkvw(B, S, h, kv, shared, dk, dv)
    pos = jnp.arange(S)
    scale = dk ** -0.5

    def rep(a):  # GQA as attn_apply does it: its transpose sums each group
        return jnp.repeat(a, h // kv, axis=2)

    def xla(q, k, v):
        return attention._attend_block(
            q, pos, k=rep(k), v=rep(v), k_pos=pos, kv_len=None, scale=scale,
            causal=causal, window=None, logit_softcap=None, decode=False,
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


# name: (takes the kernel, cfg overrides, S, extra attn_apply kwargs (by S))
SELECTION = {
    "training_self_attention": (True, {}, 512, lambda S: {}),
    "bidirectional": (True, {"causal": False}, 512, lambda S: {}),
    "long_causal": (True, {}, 2048, lambda S: {}),
    "cache": (False, {}, 512, lambda S: {
        "cache": init_kv_cache(B, S, KV_HEADS, HD)}),
    "kv_x": (False, {"causal": False}, 512, lambda S: {"kv_x": _x(S)}),
    "kv_cache": (False, {"causal": False}, 512, lambda S: {"kv_cache": {
        "k": jnp.zeros((B, S, KV_HEADS, HD), jnp.bfloat16),
        "v": jnp.zeros((B, S, KV_HEADS, HD), jnp.bfloat16)}}),
    "positions_2d": (False, {}, 512, lambda S: {
        "positions": jnp.broadcast_to(jnp.arange(S), (B, S))}),
    "softcap": (False, {"logit_softcap": 50.0}, 512, lambda S: {}),
    "sliding_window": (False, {"sliding_window": 128}, 512, lambda S: {}),
    "off_block_grid": (False, {}, 1536, lambda S: {}),
    "below_threshold": (False, {}, attention.FLASH_MIN_SEQ // 2,
                        lambda S: {}),
    "cpu_backend": (False, {}, 512, lambda S: {}),
}


@pytest.mark.parametrize("case", sorted(SELECTION))
def test_flash_path_selection(case, monkeypatch):
    """attn_apply traces the kernel for training-shaped self-attention on a
    TPU, and the XLA path in every other case."""
    takes, over, S, extra = SELECTION[case]
    if case != "cpu_backend":
        monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = _cfg(**over)
    params = attn_init(jax.random.PRNGKey(0), cfg)
    jaxpr = jax.make_jaxpr(functools.partial(
        attn_apply, params, cfg, compute_dtype=jnp.bfloat16, **extra(S)))(
            _x(S))
    assert ("pallas_call" in str(jaxpr)) == takes
