"""AOT compiles of the Pallas kernels for a described TPU v5e.

Interpret mode executes any layout; the TPU compiler does not. These
tests hand the real compiler the kernels at published widths, against a
`v5e:2x2` topology that is described, not attached, so a block shape or a
store the chip would refuse fails here, with no chip. Nothing runs.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU library. Keep every test that needs it in
this one file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels import lamb_kernel, lans_kernel, ops
from repro.kernels.paged_attention_kernel import paged_attention_fused
from repro.models import attention, mla

pytestmark = pytest.mark.kernels

# bert-large FFN weight (1024 x 4096) as the optimizer kernels see it
BERT_FFN = (1024, 4096)
ROWS = BERT_FFN[0] * BERT_FFN[1] // lans_kernel.LANES
# qwen2.5-14b decode: 40 query heads over 8 K/V heads of 128, bf16 arenas
PAGED = dict(B=8, h=40, n_kv=8, hd=128, bs=16, nb=32, n_blocks=512)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32
TILE = (ROWS, lans_kernel.LANES)
SCAL = (1, 8)
HP = dict(beta1=0.9, beta2=0.999, eps=1e-6, interpret=False)

# (kernel, operand shapes): every phase of the fused LANS and LAMB steps
PHASES = {
    "lans_phase0_sq_norm": (
        functools.partial(lans_kernel.sq_norm, interpret=False),
        [(TILE, F32)]),
    "lans_phase1": (
        functools.partial(lans_kernel.lans_phase1, **HP),
        [(SCAL, F32)] + [(TILE, F32)] * 4),
    "lans_phase2": (
        functools.partial(lans_kernel.lans_phase2, **HP),
        [(SCAL, F32), (SCAL, F32)] + [(TILE, F32)] * 4),
    "lans_phase2_cast_bf16": (
        functools.partial(lans_kernel.lans_phase2_cast, lp_dtype=BF16, **HP),
        [(SCAL, F32), (SCAL, F32)] + [(TILE, F32)] * 4),
    "lamb_phase1": (
        functools.partial(lamb_kernel.lamb_phase1, **HP),
        [(SCAL, F32)] + [(TILE, F32)] * 4),
    "lamb_phase2": (
        functools.partial(lamb_kernel.lamb_phase2, **HP),
        [(SCAL, F32), (SCAL, F32)] + [(TILE, F32)] * 3),
}


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_optimizer_kernel_compiles(one_chip, no_cache, phase):
    fn, shapes = PHASES[phase]
    _compile(fn, *shapes, sharding=one_chip)


@pytest.mark.parametrize("mixed", [False, True])
def test_fused_lans_step_compiles(one_chip, no_cache, mixed):
    """The whole per-tensor step the fused optimizers call: padding,
    three kernels and the scalar plumbing between them."""
    def step(g, m, v, x, eta, t):
        if mixed:
            return ops.fused_lans_mixed_step(g, m, v, x, eta=eta, step=t,
                                             lp_dtype=BF16, interpret=False)
        return ops.fused_lans_step(g, m, v, x, eta=eta, step=t,
                                   interpret=False)
    _compile(step, *[(BERT_FFN, F32)] * 4, ((), F32), ((), I32),
             sharding=one_chip)


@pytest.mark.parametrize("S", [1, 4])
def test_paged_attention_fused_compiles(one_chip, no_cache, S):
    c = PAGED
    B, h, kv, hd, bs = c["B"], c["h"], c["n_kv"], c["hd"], c["bs"]
    arena = (c["n_blocks"], bs, kv, hd)

    def step(q, k_new, v_new, ka, va, pos, tbl, qpos, cur):
        return paged_attention_fused(q, k_new, v_new, ka, va, pos, tbl, qpos,
                                     cur, scale=hd ** -0.5, interpret=False)
    compiled = _compile(
        step, ((B, S, h, hd), BF16), ((B, S, kv, hd), BF16),
        ((B, S, kv, hd), BF16), (arena, BF16), (arena, BF16),
        ((c["n_blocks"], bs), I32), ((B, c["nb"]), I32), ((B, S), I32),
        ((B,), I32), sharding=one_chip)
    assert compiled.memory_analysis().argument_size_in_bytes > 0


# Training attention through the kernel path, at the sequence lengths of
# bert-large phase 2 (bidirectional MHA, S 512: one block, so one backward
# kernel) and granite (causal GQA 3:1, S 4096: dK/dV and dQ kernels) with
# few heads; the last case shards the batch over a data=4 mesh.
# (S, heads, kv heads, causal, batch, data-parallel devices, kernel calls:
# forward, recompute, backward)
FLASH = {
    "bidirectional_512": (512, 2, 2, False, 1, 1, 3),
    "causal_gqa_4096": (4096, 3, 1, True, 1, 1, 4),
    "bidirectional_512_data4": (512, 2, 2, False, 8, 4, 3),
}


@pytest.mark.parametrize("case", sorted(FLASH))
def test_training_attention_kernel_compiles(topo, one_chip, no_cache,
                                            monkeypatch, case):
    """One remat'd layer's attention, forward and backward: the kernel's
    forward, its recompute and its backward kernels, all in the
    `attention` scope and none behind an all-gather of q/k/v."""
    S, h, kv, causal, B, data, n_calls = FLASH[case]
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = attention.AttnConfig(d_model=h * 64, n_heads=h, n_kv_heads=kv,
                               head_dim=64, causal=causal, rope=causal)
    layer = jax.checkpoint(lambda p, x: attention.attn_apply(p, cfg, x)[0],
                           policy=jax.checkpoint_policies.nothing_saveable)

    def step(p, x):
        return jax.value_and_grad(
            lambda p, x: jnp.sum(layer(p, x).astype(F32)), argnums=(0, 1))(p, x)

    mesh = Mesh(np.array(topo.devices[:data]).reshape(data, 1),
                ("data", "model"))
    rep = NamedSharding(mesh, PartitionSpec())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(lambda: attention.attn_init(jax.random.PRNGKey(0), cfg)))
    x = jax.ShapeDtypeStruct((B, S, h * 64), BF16, sharding=NamedSharding(
        mesh, PartitionSpec("data")))
    with mesh:
        hlo = jax.jit(step).lower(params, x).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == n_calls
    assert all(re.search(r'op_name="[^"]*[/(]attention[/)]', line)
               for line in calls)
    assert "all-gather" not in hlo


def test_mla_attention_kernel_compiles(topo, one_chip, no_cache, monkeypatch):
    """One remat'd latent-attention layer of moonlight-16b-a3b at its
    published widths and 8,192 tokens, forward and backward: the kernel
    takes keys 192 wide and values 128 (forward, recompute, dK/dV, dQ),
    all in the `attention` scope, and no (S, S) array is left."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = mla.MlaConfig(d_model=2048, n_heads=16, kv_lora_rank=512,
                        qk_nope_head_dim=128, qk_rope_head_dim=64,
                        v_head_dim=128, rope_theta=50000.0)
    layer = jax.checkpoint(lambda p, x: mla.mla_apply(p, cfg, x)[0],
                           policy=jax.checkpoint_policies.nothing_saveable)

    def step(p, x):
        return jax.value_and_grad(
            lambda p, x: jnp.sum(layer(p, x).astype(F32)), argnums=(0, 1))(p, x)

    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, BF16, sharding=one_chip),
        jax.eval_shape(lambda: mla.mla_init(jax.random.PRNGKey(0), cfg)))
    x = jax.ShapeDtypeStruct((1, 8192, 2048), BF16, sharding=one_chip)
    hlo = jax.jit(step).lower(params, x).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 4
    assert all(re.search(r'op_name="[^"]*[/(]attention[/)]', line)
               for line in calls)
    assert not re.search(r"\[(1,)?16,8192,8192\]", hlo)
