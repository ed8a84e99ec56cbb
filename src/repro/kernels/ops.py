"""jit'd public wrappers over the fused optimizer kernels.

Handles the HBM layout contract for the kernels: every parameter tensor is
flattened, zero-padded to a multiple of (TILE_ROWS * 128) elements and viewed
as (rows, 128). Zero padding is exact for every phase (padded lanes carry
g = m = v = x = 0, contributing nothing to any norm and receiving a zero
update), so no masking pass is needed.

`interpret=None` resolves through `repro.kernels.default_interpret()`:
compiled on a TPU, interpreted on any other backend.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import lamb_kernel, lans_kernel
from repro.kernels.lans_kernel import LANES, TILE_ROWS
from repro.kernels.ref import StepOut

_CHUNK = TILE_ROWS * LANES


def _to_tiles(x: jnp.ndarray) -> tuple:
    """Flatten + zero-pad to (rows, 128); returns (tiles, orig_size)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    padded = (n + _CHUNK - 1) // _CHUNK * _CHUNK
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(-1, LANES), n


def _from_tiles(t2d: jnp.ndarray, n: int, shape, dtype) -> jnp.ndarray:
    return t2d.reshape(-1)[:n].reshape(shape).astype(dtype)


@functools.partial(
    jax.jit,
    static_argnames=("beta1", "beta2", "eps", "lam", "apply_trust", "interpret"),
)
def fused_lans_step(
    g, m, v, x, *, eta, step,
    beta1: float = 0.9, beta2: float = 0.999, eps: float = 1e-6,
    lam: float = 0.01, apply_trust: bool = True, interpret=None,
) -> StepOut:
    """One fused LANS update for a single parameter tensor (any shape/dtype).

    ``step`` is the 1-indexed iteration (traced ok); ``eta`` traced ok.
    Returns StepOut(x_new, m_new, v_new) with x_new in x.dtype, moments fp32.
    """
    g2d, n = _to_tiles(g)
    m2d, _ = _to_tiles(m)
    v2d, _ = _to_tiles(v)
    x2d, _ = _to_tiles(x)

    stepf = jnp.asarray(step, jnp.float32)
    bc1 = 1.0 - jnp.power(jnp.float32(beta1), stepf)
    bc2 = 1.0 - jnp.power(jnp.float32(beta2), stepf)

    g_sq = lans_kernel.sq_norm(g2d, interpret=interpret)

    scalars = jnp.zeros((1, 8), jnp.float32)
    scalars = scalars.at[0, 0].set(bc1)
    scalars = scalars.at[0, 1].set(bc2)
    scalars = scalars.at[0, 2].set(jnp.asarray(eta, jnp.float32))
    scalars = scalars.at[0, 3].set(jnp.float32(lam))
    scalars = scalars.at[0, 4].set(jnp.float32(1.0 if apply_trust else 0.0))
    scalars = scalars.at[0, 5].set(g_sq)

    m_new, v_new, partials = lans_kernel.lans_phase1(
        scalars, g2d, m2d, v2d, x2d,
        beta1=beta1, beta2=beta2, eps=eps, interpret=interpret)

    x_new2d = lans_kernel.lans_phase2(
        scalars, partials, g2d, m_new, v_new, x2d,
        beta1=beta1, beta2=beta2, eps=eps, interpret=interpret)

    return StepOut(
        _from_tiles(x_new2d, n, x.shape, x.dtype),
        _from_tiles(m_new, n, m.shape, jnp.float32),
        _from_tiles(v_new, n, v.shape, jnp.float32),
    )


class MixedStepOut(NamedTuple):
    """fused_lans_mixed_step result: fp32 master + low-precision copy."""

    x: jnp.ndarray     # new master weights, fp32
    m: jnp.ndarray     # new first moment, fp32
    v: jnp.ndarray     # new second moment, fp32
    x_lp: jnp.ndarray  # new model copy, lp_dtype (cast fused into phase 2)


@functools.partial(
    jax.jit,
    static_argnames=("lp_dtype", "beta1", "beta2", "eps", "lam",
                     "apply_trust", "interpret"),
)
def fused_lans_mixed_step(
    g, m, v, x, *, eta, step, lp_dtype,
    beta1: float = 0.9, beta2: float = 0.999, eps: float = 1e-6,
    lam: float = 0.01, apply_trust: bool = True, interpret=None,
) -> MixedStepOut:
    """Fused LANS step on fp32 master `x` that ALSO emits the lp_dtype model
    copy from the same phase-2 pass — the cast-and-apply path mixed-precision
    training runs every step (no separate cast kernel / extra HBM read)."""
    g2d, n = _to_tiles(g)
    m2d, _ = _to_tiles(m)
    v2d, _ = _to_tiles(v)
    x2d, _ = _to_tiles(x)

    stepf = jnp.asarray(step, jnp.float32)
    bc1 = 1.0 - jnp.power(jnp.float32(beta1), stepf)
    bc2 = 1.0 - jnp.power(jnp.float32(beta2), stepf)

    g_sq = lans_kernel.sq_norm(g2d, interpret=interpret)

    scalars = jnp.zeros((1, 8), jnp.float32)
    scalars = scalars.at[0, 0].set(bc1)
    scalars = scalars.at[0, 1].set(bc2)
    scalars = scalars.at[0, 2].set(jnp.asarray(eta, jnp.float32))
    scalars = scalars.at[0, 3].set(jnp.float32(lam))
    scalars = scalars.at[0, 4].set(jnp.float32(1.0 if apply_trust else 0.0))
    scalars = scalars.at[0, 5].set(g_sq)

    m_new, v_new, partials = lans_kernel.lans_phase1(
        scalars, g2d, m2d, v2d, x2d,
        beta1=beta1, beta2=beta2, eps=eps, interpret=interpret)

    x_new2d, x_lp2d = lans_kernel.lans_phase2_cast(
        scalars, partials, g2d, m_new, v_new, x2d,
        lp_dtype=lp_dtype, beta1=beta1, beta2=beta2, eps=eps,
        interpret=interpret)

    return MixedStepOut(
        _from_tiles(x_new2d, n, x.shape, jnp.float32),
        _from_tiles(m_new, n, m.shape, jnp.float32),
        _from_tiles(v_new, n, v.shape, jnp.float32),
        _from_tiles(x_lp2d, n, x.shape, lp_dtype),
    )


@functools.partial(
    jax.jit,
    static_argnames=("beta1", "beta2", "eps", "lam", "apply_trust", "interpret"),
)
def fused_lamb_step(
    g, m, v, x, *, eta, step, clip=1.0,
    beta1: float = 0.9, beta2: float = 0.999, eps: float = 1e-6,
    lam: float = 0.01, apply_trust: bool = True, interpret=None,
) -> StepOut:
    """One fused LAMB update; ``clip`` is the caller-computed global-clip factor."""
    g2d, n = _to_tiles(g)
    m2d, _ = _to_tiles(m)
    v2d, _ = _to_tiles(v)
    x2d, _ = _to_tiles(x)

    stepf = jnp.asarray(step, jnp.float32)
    bc1 = 1.0 - jnp.power(jnp.float32(beta1), stepf)
    bc2 = 1.0 - jnp.power(jnp.float32(beta2), stepf)

    scalars = jnp.zeros((1, 8), jnp.float32)
    scalars = scalars.at[0, 0].set(bc1)
    scalars = scalars.at[0, 1].set(bc2)
    scalars = scalars.at[0, 2].set(jnp.asarray(eta, jnp.float32))
    scalars = scalars.at[0, 3].set(jnp.float32(lam))
    scalars = scalars.at[0, 4].set(jnp.float32(1.0 if apply_trust else 0.0))
    scalars = scalars.at[0, 5].set(jnp.asarray(clip, jnp.float32))

    m_new, v_new, partials = lamb_kernel.lamb_phase1(
        scalars, g2d, m2d, v2d, x2d,
        beta1=beta1, beta2=beta2, eps=eps, interpret=interpret)

    x_new2d = lamb_kernel.lamb_phase2(
        scalars, partials, m_new, v_new, x2d,
        beta1=beta1, beta2=beta2, eps=eps, interpret=interpret)

    return StepOut(
        _from_tiles(x_new2d, n, x.shape, x.dtype),
        _from_tiles(m_new, n, m.shape, jnp.float32),
        _from_tiles(v_new, n, v.shape, jnp.float32),
    )


def block_sq_norm(x, *, interpret=None) -> jnp.ndarray:
    """Kernel-backed sum-of-squares for arbitrary-shape tensors."""
    x2d, _ = _to_tiles(x)
    return lans_kernel.sq_norm(x2d, interpret=interpret)
