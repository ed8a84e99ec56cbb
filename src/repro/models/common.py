"""Functional NN building blocks (no flax in this environment).

Params are nested dicts of jnp arrays. Every module is a pair of pure
functions: ``init_*(rng, ...) -> params`` and an apply function. Models store
master params in ``param_dtype`` (fp32 by default) and cast to
``compute_dtype`` (bf16 on TPU) at use — the mixed-precision policy the paper
trains BERT with.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def ambient_mesh():
    """The mesh of the enclosing `with mesh:` block (`.empty` outside one)."""
    from jax._src.mesh import thread_resources
    return thread_resources.env.physical_mesh


def maybe_constrain(x, *spec_axes):
    """with_sharding_constraint against the AMBIENT mesh, if any.

    Models stay mesh-agnostic: under the production mesh context the
    constraint pins activation sharding (e.g. MoE expert capacity over
    "data"); in local/unmeshed runs it is a no-op. Axes missing from the
    mesh or non-divisible dims degrade to None for that dim.
    """
    mesh = ambient_mesh()
    if mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, fit_spec(mesh, x.shape, spec_axes))


def fit_spec(mesh, shape, spec_axes):
    """PartitionSpec of `spec_axes` on `mesh` for an array of `shape`: an
    axis the mesh lacks, or that does not divide its dim, becomes None."""
    from jax.sharding import PartitionSpec

    names = set(mesh.axis_names)

    def ok(dim, axis):
        if axis is None:
            return None
        axes = axis if isinstance(axis, tuple) else (axis,)
        if not all(a in names for a in axes):
            return None
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        return axis if dim % size == 0 else None

    return PartitionSpec(*(ok(d, a) for d, a in zip(shape, spec_axes)))


def ambient_axis_size(name: str) -> int:
    """Size of a named axis in the ambient mesh (1 if absent/unmeshed)."""
    mesh = ambient_mesh()
    if mesh.empty or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def dense_init(rng, in_dim: int, out_dim: int, *, use_bias: bool = False,
               dtype=jnp.float32, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    p = {"kernel": (jax.random.normal(rng, (in_dim, out_dim)) * scale).astype(dtype)}
    if use_bias:
        p["bias"] = jnp.zeros((out_dim,), dtype)
    return p


def dense_apply(p, x, compute_dtype=jnp.bfloat16):
    y = jnp.einsum("...i,io->...o", x.astype(compute_dtype),
                   p["kernel"].astype(compute_dtype))
    if "bias" in p:
        y = y + p["bias"].astype(compute_dtype)
    return y


def embed_init(rng, vocab: int, dim: int, dtype=jnp.float32):
    return {"embedding": (jax.random.normal(rng, (vocab, dim)) * 0.02).astype(dtype)}


def embed_apply(p, ids, compute_dtype=jnp.bfloat16):
    return jnp.take(p["embedding"].astype(compute_dtype), ids, axis=0)


def embed_attend(p, x, compute_dtype=jnp.bfloat16):
    """Tied-readout logits: x @ E^T."""
    return jnp.einsum("...d,vd->...v", x.astype(compute_dtype),
                      p["embedding"].astype(compute_dtype))


def rmsnorm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.zeros((dim,), dtype)}  # gemma-style (1+scale)


def rmsnorm_apply(p, x, eps: float = 1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["scale"].astype(jnp.float32))).astype(dtype)


def layernorm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm_apply(p, x, eps: float = 1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dtype)


def softcap(x, cap: Optional[float]):
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# --- rotary position embeddings -------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    exps = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exps)  # (head_dim/2,)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0):
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --- activations ------------------------------------------------------------

def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def silu(x):
    return jax.nn.silu(x)


ACTIVATIONS = {"gelu": gelu, "silu": silu, "relu": jax.nn.relu}


# --- gated / plain MLP ------------------------------------------------------

def mlp_init(rng, d_model: int, d_ff: int, *, gated: bool = True,
             use_bias: bool = False, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    p = {"up": dense_init(ks[0], d_model, d_ff, use_bias=use_bias, dtype=dtype),
         "down": dense_init(ks[1], d_ff, d_model, use_bias=use_bias, dtype=dtype)}
    if gated:
        p["gate"] = dense_init(ks[2], d_model, d_ff, use_bias=use_bias, dtype=dtype)
    return p


def mlp_apply(p, x, *, activation: str = "silu", compute_dtype=jnp.bfloat16):
    act = ACTIVATIONS[activation]
    up = dense_apply(p["up"], x, compute_dtype)
    if "gate" in p:
        up = act(dense_apply(p["gate"], x, compute_dtype)) * up
    else:
        up = act(up)
    return dense_apply(p["down"], up, compute_dtype)
