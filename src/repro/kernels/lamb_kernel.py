"""Pallas TPU kernels for the fused LAMB baseline (Algorithm 1).

Two phases (LAMB needs no gradient-norm pre-pass — only the trust-ratio
norms, which depend on the *updated* moments):

  phase 1  lamb_phase1 : update m, v; emit partial sums-of-squares of
                         u = r + lam*x and of x
  phase 2  lamb_phase2 : trust = ||x|| / ||u||; x <- x - eta * trust * u

Global gradient clipping (a cross-block quantity) is the caller's job and is
folded into the scalar `clip` operand so the kernel stays single-block.
Scalars layout: [bc1, bc2, eta, lam, trust_flag, clip, 0, 0].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret
from repro.kernels.lans_kernel import (
    LANES, SCALARS, TILE_ROWS, _acc_shape, _acc_spec, _fold, _guarded_inv,
    sum_partials)


def _lamb_phase1_kernel(scal_ref, g_ref, m_ref, v_ref, x_ref,
                        m_out, v_out, part_out, *, beta1, beta2, eps):
    i = pl.program_id(0)
    bc1 = scal_ref[0, 0]
    bc2 = scal_ref[0, 1]
    lam = scal_ref[0, 3]
    clip = scal_ref[0, 5]

    g = g_ref[...].astype(jnp.float32) * clip
    m = m_ref[...]
    v = v_ref[...]
    x = x_ref[...].astype(jnp.float32)

    m_new = beta1 * m + (1.0 - beta1) * g
    v_new = beta2 * v + (1.0 - beta2) * (g * g)
    m_out[...] = m_new
    v_out[...] = v_new

    r = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    u = r + lam * x

    @pl.when(i == 0)
    def _init():
        part_out[...] = jnp.zeros_like(part_out)

    part_out[0] += _fold(u * u)
    part_out[1] += _fold(x * x)


def lamb_phase1(scalars, g2d, m2d, v2d, x2d, *, beta1, beta2, eps,
                interpret=None):
    """Returns (m_new, v_new, norms): norms is the (1, 8) row
    [||u||^2, ||x||^2, 0, ...]."""
    if interpret is None:
        interpret = default_interpret()
    rows, lanes = g2d.shape
    assert lanes == LANES and rows % TILE_ROWS == 0
    grid = (rows // TILE_ROWS,)
    tile = pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0))
    kern = functools.partial(_lamb_phase1_kernel, beta1=beta1, beta2=beta2, eps=eps)
    m_new, v_new, partials = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[SCALARS, tile, tile, tile, tile],
        out_specs=[tile, tile, _acc_spec(2)],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            _acc_shape(2),
        ],
        interpret=interpret,
    )(scalars, g2d, m2d, v2d, x2d)
    return m_new, v_new, sum_partials(partials)


def _lamb_phase2_kernel(scal_ref, norm_ref, m_ref, v_ref, x_ref, x_out,
                        *, beta1, beta2, eps):
    del beta1, beta2
    bc1 = scal_ref[0, 0]
    bc2 = scal_ref[0, 1]
    eta = scal_ref[0, 2]
    lam = scal_ref[0, 3]
    trust_flag = scal_ref[0, 4]

    u_sq = norm_ref[0, 0]
    x_sq = norm_ref[0, 1]

    m = m_ref[...]
    v = v_ref[...]
    x = x_ref[...].astype(jnp.float32)

    r = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    u = r + lam * x

    x_norm = jnp.sqrt(x_sq)
    trust = jnp.where(u_sq > 0.0, x_norm * _guarded_inv(u_sq), 1.0)
    trust = jnp.where(trust_flag > 0.0, trust, 1.0)

    x_out[...] = (x - eta * trust * u).astype(x_out.dtype)


def lamb_phase2(scalars, norms, m2d, v2d, x2d, *, beta1, beta2, eps,
                interpret=None):
    if interpret is None:
        interpret = default_interpret()
    rows, lanes = m2d.shape
    assert lanes == LANES and rows % TILE_ROWS == 0
    grid = (rows // TILE_ROWS,)
    tile = pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0))
    kern = functools.partial(_lamb_phase2_kernel, beta1=beta1, beta2=beta2, eps=eps)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[SCALARS, SCALARS, tile, tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), x2d.dtype),
        interpret=interpret,
    )(scalars, norms, m2d, v2d, x2d)
