"""LANS, Algorithm 2 of Zheng et al., arXiv:2006.13484, in float32.

Per block (one parameter tensor) with gradient g, at step t:

    g~ = g / ||g||
    m  = b1 m + (1 - b1) g~          v = b2 v + (1 - b2) g~^2
    r  = (m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps)
    c  = g~ / (sqrt(v / (1 - b2^t)) + eps)
    x -= lr * (b1 ||x|| (r + wd x) / ||r + wd x|| + (1 - b1) ||x|| (c + wd x) / ||c + wd x||)

A block whose path names one of the configuration's `no_decay` words takes
neither weight decay nor the trust ratio: x -= lr * (b1 r + (1 - b1) c).
Moments start at zero; `opt` is the configuration file's "optimizer" dict.
"""
from __future__ import annotations

import jax.numpy as jnp


def decays(path: str, opt: dict) -> bool:
    low = path.lower()
    return not any(w in low for w in opt["no_decay"])


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x)))


def _unit(x):
    n = _norm(x)
    return jnp.where(n > 0, x / jnp.where(n > 0, n, 1.0), 0.0)


def update(g, m, v, x, t, opt: dict, decay: bool):
    """One step of one block; t counts from 1. Returns (x, m, v)."""
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
    g = _unit(g)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    den = jnp.sqrt(v / (1 - b2 ** t)) + eps
    r = m / (1 - b1 ** t) / den
    c = g / den
    if decay:
        wd = opt["weight_decay"] * x
        d = _norm(x) * (b1 * _unit(r + wd) + (1 - b1) * _unit(c + wd))
    else:
        d = b1 * r + (1 - b1) * c
    return x - opt["lr"] * d, m, v
