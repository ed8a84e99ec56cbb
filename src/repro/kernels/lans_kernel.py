"""Pallas TPU kernels for the fused LANS optimizer step.

TPU adaptation of the paper's apex `fused_lans` CUDA kernel. A CUDA fused
optimizer interleaves block-wide reductions with elementwise math via
grid-wide synchronization; Pallas/TPU has no grid-wide barrier, so the step
is restructured into a 3-phase pipeline, each phase a `pl.pallas_call` tiled
for VMEM with (8,128)-aligned blocks:

  phase 0  sq_norm      : tiled sum-of-squares reduction  -> ||g||^2
  phase 1  lans_phase1  : g~ = g/||g||, update m,v; emit partial
                          sums-of-squares of (r+lam*x), (c+lam*x), x
  phase 2  lans_phase2  : given the three norms, form the convex-combination
                          direction d (paper eq. 7) and apply x <- x - eta*d

Reductions use the sequential-grid accumulation idiom (output block mapped to
block 0 for every grid step, initialised at i==0) and stay in vector form: each
tile folds into a lane-dense (8, 128) fp32 accumulator with elementwise adds,
and the wrapper sums that block outside the kernel. The TPU compiler refuses
scalar stores to VMEM, so no kernel writes a reduced scalar. All arithmetic is
fp32 in VREGs regardless of storage dtype; traced scalars (bias corrections,
eta, flags, norms) ride in a (1, 8) fp32 SMEM operand so the kernel needs no
retracing across steps.

Tile size: (256, 128) fp32 = 128 KiB; phase 1 holds 4 input + 2 output tiles
(~0.75 MiB), far under the ~16 MiB v5e VMEM budget, leaving room for
double-buffering by the pipeline emitter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

TILE_ROWS = 256
LANES = 128
SUBLANES = 8

# The (1, 8) fp32 scalar operands live in SMEM whole: read one element at a
# time, they need no vector load.
SCALARS = pl.BlockSpec(memory_space=pltpu.SMEM)


def _guarded_inv(sq: jnp.ndarray, eps_floor: float = 1e-38) -> jnp.ndarray:
    """1/sqrt(sq) with sq==0 -> 0 (normalizing an all-zero block)."""
    return jnp.where(sq > 0.0, jax.lax.rsqrt(jnp.maximum(sq, eps_floor)), 0.0)


def _guarded_scale(x: jnp.ndarray, sq: jnp.ndarray) -> jnp.ndarray:
    """x / sqrt(sq), selecting 0 when sq is 0 or non-finite.

    Select (not multiply): x * 0 would propagate NaN from a NaN gradient
    block, whereas the reference optimizer (safe_div) zeroes it — the two
    paths must agree bit-for-bit on NaN handling (tests/test_fused_integration).
    """
    inv = jax.lax.rsqrt(jnp.maximum(sq, 1e-38))
    return jnp.where(sq > 0.0, x * inv, jnp.zeros_like(x))


# ---------------------------------------------------------------------------
# phase 0: sum-of-squares reduction
# ---------------------------------------------------------------------------

def _fold(x: jnp.ndarray) -> jnp.ndarray:
    """(TILE_ROWS, 128) -> (8, 128) partial sums: vreg-wise adds only."""
    return x.reshape(-1, SUBLANES, LANES).sum(axis=0)


def _acc_spec(n: int) -> pl.BlockSpec:
    """n lane-dense (8, 128) accumulators, revisited by every grid step."""
    return pl.BlockSpec((n, SUBLANES, LANES), lambda i: (0, 0, 0))


def _acc_shape(n: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((n, SUBLANES, LANES), jnp.float32)


def _sq_norm_kernel(x_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)
    out_ref[0] += _fold(x * x)


def sq_norm(x2d: jnp.ndarray, *, interpret=None) -> jnp.ndarray:
    """Sum of squares of a (rows, 128) array, rows % TILE_ROWS == 0."""
    if interpret is None:
        interpret = default_interpret()
    rows, lanes = x2d.shape
    assert lanes == LANES and rows % TILE_ROWS == 0, x2d.shape
    grid = (rows // TILE_ROWS,)
    out = pl.pallas_call(
        _sq_norm_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0))],
        out_specs=_acc_spec(1),
        out_shape=_acc_shape(1),
        interpret=interpret,
    )(x2d)
    return jnp.sum(out)


# ---------------------------------------------------------------------------
# phase 1: moment update + partial norms
# scalars layout: [bc1, bc2, eta, lam, trust_flag, g_sq, 0, 0]
# ---------------------------------------------------------------------------

def _lans_phase1_kernel(scal_ref, g_ref, m_ref, v_ref, x_ref,
                        m_out, v_out, part_out, *, beta1, beta2, eps):
    i = pl.program_id(0)

    bc1 = scal_ref[0, 0]
    bc2 = scal_ref[0, 1]
    lam = scal_ref[0, 3]
    g_sq = scal_ref[0, 5]

    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    x = x_ref[...].astype(jnp.float32)

    g_t = _guarded_scale(g, g_sq)
    m_new = beta1 * m + (1.0 - beta1) * g_t
    v_new = beta2 * v + (1.0 - beta2) * (g_t * g_t)
    m_out[...] = m_new
    v_out[...] = v_new

    denom = jnp.sqrt(v_new / bc2) + eps
    r_full = (m_new / bc1) / denom + lam * x
    c_full = g_t / denom + lam * x

    @pl.when(i == 0)
    def _init():
        part_out[...] = jnp.zeros_like(part_out)

    part_out[0] += _fold(r_full * r_full)
    part_out[1] += _fold(c_full * c_full)
    part_out[2] += _fold(x * x)


def sum_partials(partials: jnp.ndarray) -> jnp.ndarray:
    """(n, 8, 128) accumulators -> the (1, 8) norms row phase 2 reads."""
    sums = jnp.sum(partials, axis=(1, 2))
    return jnp.zeros((1, 8), jnp.float32).at[0, :sums.shape[0]].set(sums)


def lans_phase1(scalars, g2d, m2d, v2d, x2d, *, beta1, beta2, eps,
                interpret=None):
    """Returns (m_new, v_new, norms): norms is the (1, 8) row
    [||r+lam*x||^2, ||c+lam*x||^2, ||x||^2, 0, ...]."""
    if interpret is None:
        interpret = default_interpret()
    rows, lanes = g2d.shape
    assert lanes == LANES and rows % TILE_ROWS == 0
    grid = (rows // TILE_ROWS,)
    tile = pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0))
    kern = functools.partial(_lans_phase1_kernel, beta1=beta1, beta2=beta2, eps=eps)
    m_new, v_new, partials = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[SCALARS, tile, tile, tile, tile],
        out_specs=[tile, tile, _acc_spec(3)],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            _acc_shape(3),
        ],
        interpret=interpret,
    )(scalars, g2d, m2d, v2d, x2d)
    return m_new, v_new, sum_partials(partials)


# ---------------------------------------------------------------------------
# phase 2: apply the update
# scalars layout: [bc1, bc2, eta, lam, trust_flag, g_sq, 0, 0]
# norms layout:   [r_sq, c_sq, x_sq, 0, 0, 0, 0, 0]
# ---------------------------------------------------------------------------

def _phase2_x_new(scal_ref, norm_ref, g_ref, m_ref, v_ref, x_ref,
                  *, beta1, eps):
    """Shared phase-2 body: returns the fp32 updated tile x - eta*d."""
    bc1 = scal_ref[0, 0]
    bc2 = scal_ref[0, 1]
    eta = scal_ref[0, 2]
    lam = scal_ref[0, 3]
    trust_flag = scal_ref[0, 4]
    g_sq = scal_ref[0, 5]

    r_sq = norm_ref[0, 0]
    c_sq = norm_ref[0, 1]
    x_sq = norm_ref[0, 2]

    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    x = x_ref[...].astype(jnp.float32)

    g_t = _guarded_scale(g, g_sq)
    denom = jnp.sqrt(v / bc2) + eps
    r_full = (m / bc1) / denom + lam * x
    c_full = g_t / denom + lam * x

    x_norm = jnp.sqrt(x_sq)
    sr = jnp.where(r_sq > 0.0, x_norm * _guarded_inv(r_sq), 1.0)
    sc = jnp.where(c_sq > 0.0, x_norm * _guarded_inv(c_sq), 1.0)
    sr = jnp.where(trust_flag > 0.0, sr, 1.0)
    sc = jnp.where(trust_flag > 0.0, sc, 1.0)

    d = beta1 * sr * r_full + (1.0 - beta1) * sc * c_full
    return x - eta * d


def _lans_phase2_kernel(scal_ref, norm_ref, g_ref, m_ref, v_ref, x_ref,
                        x_out, *, beta1, beta2, eps):
    del beta2
    x_new = _phase2_x_new(scal_ref, norm_ref, g_ref, m_ref, v_ref, x_ref,
                          beta1=beta1, eps=eps)
    x_out[...] = x_new.astype(x_out.dtype)


def _lans_phase2_cast_kernel(scal_ref, norm_ref, g_ref, m_ref, v_ref, x_ref,
                             x_out, lp_out, *, beta1, beta2, eps):
    """Mixed-precision phase 2: one pass writes BOTH the fp32 master update
    and its low-precision cast. Saves re-reading x_new from HBM for the
    model-copy cast that fp16/bf16 training needs every step."""
    del beta2
    x_new = _phase2_x_new(scal_ref, norm_ref, g_ref, m_ref, v_ref, x_ref,
                          beta1=beta1, eps=eps)
    x_out[...] = x_new.astype(x_out.dtype)
    lp_out[...] = x_new.astype(lp_out.dtype)


def lans_phase2(scalars, norms, g2d, m2d, v2d, x2d, *, beta1, beta2, eps,
                interpret=None):
    if interpret is None:
        interpret = default_interpret()
    rows, lanes = g2d.shape
    assert lanes == LANES and rows % TILE_ROWS == 0
    grid = (rows // TILE_ROWS,)
    tile = pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0))
    kern = functools.partial(_lans_phase2_kernel, beta1=beta1, beta2=beta2, eps=eps)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[SCALARS, SCALARS, tile, tile, tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), x2d.dtype),
        interpret=interpret,
    )(scalars, norms, g2d, m2d, v2d, x2d)


def lans_phase2_cast(scalars, norms, g2d, m2d, v2d, x2d, *, lp_dtype,
                     beta1, beta2, eps, interpret=None):
    """Phase 2 with fused low-precision cast: returns (x_new_f32, x_new_lp).

    TILE_ROWS=256 respects the (16,128) bf16 / fp16 minimum tile, so the
    same grid works for every lp_dtype.
    """
    if interpret is None:
        interpret = default_interpret()
    rows, lanes = g2d.shape
    assert lanes == LANES and rows % TILE_ROWS == 0
    grid = (rows // TILE_ROWS,)
    tile = pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0))
    kern = functools.partial(_lans_phase2_cast_kernel,
                             beta1=beta1, beta2=beta2, eps=eps)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[SCALARS, SCALARS, tile, tile, tile, tile],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), lp_dtype),
        ],
        interpret=interpret,
    )(scalars, norms, g2d, m2d, v2d, x2d)
