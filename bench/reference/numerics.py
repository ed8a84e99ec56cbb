"""Matrix products of the references, in one of two precisions.

"fp32" is the reference: float32 operands at `Precision.HIGHEST`, since a
TPU otherwise runs a float32 product as bfloat16 passes. "fp8" is the
control of `correct`: every operand of every product, forward and backward,
is rounded to float8_e4m3fn with one scale per tensor (amax to 448), the
next precision below the bfloat16 the configurations state. Everything
outside the products (softmax, norms, the optimizer) stays float32 in both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(eq, a, b):
    return _einsum(eq, _fp8(a), _fp8(b))


def _fwd(eq, a, b):
    return _einsum_fp8(eq, a, b), (a, b)


def _bwd(eq, res, g):
    a, b = res
    _, vjp = jax.vjp(functools.partial(_einsum, eq), _fp8(a), _fp8(b))
    return vjp(_fp8(g))


_einsum_fp8.defvjp(_fwd, _bwd)


def matmul(mode: str):
    """The product `(eq, a, b) -> einsum` of precision `mode`."""
    if mode == "fp32":
        return _einsum
    if mode == "fp8":
        return _einsum_fp8
    raise ValueError(f"unknown reference precision {mode!r}")


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], str)


def init_params(spec, seed: int, std: float):
    """Weights from `seed` in one jitted call on the device.

    `spec` is a nested dict whose leaves are (kind, shape): "normal" leaves
    are a normal of deviation `std` truncated at two deviations and stored
    in bfloat16, the type they are trained in; "zeros" and "ones" are
    float32. The references read them as float32, so both sides start from
    the same numbers."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_spec)

    def make(key):
        out = []
        for i, (kind, shape) in enumerate(leaves):
            if kind == "normal":
                w = std * jax.random.truncated_normal(
                    jax.random.fold_in(key, i), -2.0, 2.0, shape, jnp.float32)
                out.append(w.astype(jnp.bfloat16))
            elif kind == "zeros":
                out.append(jnp.zeros(shape, jnp.float32))
            elif kind == "ones":
                out.append(jnp.ones(shape, jnp.float32))
            else:
                raise ValueError(kind)
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed))


def shapes(spec):
    """The spec's tree of shapes, for checking the program's layout."""
    return jax.tree.map(lambda s: tuple(s[1]), spec, is_leaf=_is_spec)
