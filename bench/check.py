"""How `correct` is decided: the program's first steps against the reference.

The harness drives the program's own trainer through STEPS steps before the
window, on the feed's first batches, from weights the reference made from
the seed. It keeps three readings, and the reference then takes the same
steps from the same weights on the same batches in float32:

- the loss of each step;
- the first gradient as the optimizer got it. LANS keeps only its direction
  (its first moment after one step is (1 - beta1) g / ||g|| for each
  block), so the reading is that direction;
- the parameters after the STEPS steps (the float32 masters where the model
  copy is bfloat16), as the next step starts from them.

`compare` reduces them to the numbers held against the cell's limits in
bench/limits/<cell>.json, which PERF.md derives. Leaves whose
reference gradient is under a thousandth of the median leaf's (a key bias
under softmax has none) move by rounding alone and are left out of the
gradient and update numbers. Every function here imports nothing of the
program.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import statistics

import jax
import jax.numpy as jnp

from bench.reference import lans as ref_lans
from bench.reference.numerics import init_params, matmul

STEPS = 3
NEGLIGIBLE = 1e-3      # of the median leaf's reference gradient norm


def paths_of(tree) -> dict:
    """{'a/b/c': leaf} for a tree of dicts, tuples and named tuples."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    name = lambda k: str(getattr(k, "key", getattr(k, "name",
                                                   getattr(k, "idx", k))))
    return {"/".join(name(k) for k in kp): leaf for kp, leaf in flat}


def kind_module(config: dict):
    return importlib.import_module(f"bench.reference.{config['kind']}")


def initial_weights(config: dict, seed: int):
    """The weights both sides start from: bfloat16 matrices, float32 rest."""
    spec = kind_module(config).param_spec(config["model"])
    return init_params(spec, seed, config["init_std"])


@dataclasses.dataclass
class Readings:
    losses: list     # one float per step
    gdir: dict       # path -> direction of the first gradient
    params: dict     # path -> float32 parameters after the steps


def _rows(batch: dict, rows: slice) -> dict:
    return {k: v[rows] for k, v in batch.items()}


def _share(batch: dict, share: float) -> dict:
    """The first `share` of a batch: its first rows, or, in a batch of one
    row, the labels of its first tokens, the others ignored (-100)."""
    B, S = batch["tokens"].shape
    if share >= 1:
        return batch
    if B > 1:
        return _rows(batch, slice(0, max(1, int(B * share))))
    key = "labels" if "labels" in batch else "mlm_labels"
    labels = batch[key].copy()
    labels[:, int(S * share):] = -100
    return dict(batch, **{key: labels})


def reference_run(config: dict, optimizer: dict, seed: int, batches: list, *,
                  mode: str = "fp32", share: float = 1.0):
    """The reference's STEPS steps. Returns (Readings, first gradient norm
    of each leaf). `mode` "fp8" is the control; `share` < 1 is a fault
    that takes the mean over that first share of each batch only."""
    kind = kind_module(config)
    model, mm = config["model"], matmul(mode)
    x = jax.tree.map(lambda a: a.astype(jnp.float32),
                     initial_weights(config, seed))
    m = jax.tree.map(jnp.zeros_like, x)
    v = jax.tree.map(jnp.zeros_like, x)
    decay = {p: ref_lans.decays(p, optimizer) for p in paths_of(x)}
    treedef = jax.tree.structure(x)

    @jax.jit
    def grad(x, batch, norms):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(kind.objective)(x, model, batch, mm,
                                                      *norms)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(x, m, v, g, t):
        out = [ref_lans.update(gl, ml, vl, xl, t, optimizer, decay[p])
               for p, gl, ml, vl, xl in zip(
                   decay, *(jax.tree.leaves(a) for a in (g, m, v, x)))]
        return tuple(jax.tree.unflatten(treedef, [o[i] for o in out])
                     for i in range(3))

    losses, gdir, gnorm = [], None, None
    for t, batch in enumerate(batches[:STEPS], start=1):
        batch = _share(batch, share)
        norms = tuple(jnp.float32(n) for n in kind.totals(batch))
        B = batch["tokens"].shape[0]
        block = kind.BLOCK_ROWS or B
        loss, g = 0.0, None
        for lo in range(0, B, block):
            part = {k: jnp.asarray(a) for k, a in
                    _rows(batch, slice(lo, lo + block)).items()}
            lb, gb = grad(x, part, norms)
            loss = loss + lb
            g = gb if g is None else jax.tree.map(jnp.add, g, gb)
        losses.append(float(loss))
        if t == 1:
            gnorm = {p: float(jnp.linalg.norm(a)) for p, a in
                     paths_of(g).items()}
            gdir = jax.device_get({p: a / max(gnorm[p], 1e-38)
                                   for p, a in paths_of(g).items()})
        x, m, v = step(x, m, v, g, t)
        del g
    return Readings(losses, gdir, jax.device_get(paths_of(x))), gnorm


@jax.jit
def _norms(a, b, base):
    """||a - base||, ||b - base||, ||a - b|| in float32."""
    a, b, base = (jnp.asarray(u, jnp.float32) for u in (a, b, base))
    n = lambda u: jnp.sqrt(jnp.sum(jnp.square(u)))
    return n(a - base), n(b - base), n(a - b)


def compare(sut: Readings, ref: Readings, gnorm: dict, x0: dict) -> dict:
    """The numbers of `correct`, each the worst over steps or leaves.

    loss     |loss - ref| / |ref| of the first step. The later steps'
             (`loss_later`, reported) swing with the training itself: a
             bfloat16 model copy of the updated masters moves the loss
             of steps 2 and 3 by up to 0.7% on bert-large (PERF.md).
    grad     per leaf ||g - g_ref|| / max(||g_ref||, median leaf's), where
             g is the program's first gradient direction scaled by the
             reference gradient's norm (LANS keeps no other).
    update   per leaf ||u - u_ref|| / max(||u_ref||, median leaf's), u the
             change of the parameters over the steps.
    grad_norm and update_norm, the same with the gap between the two norms
    in place of the norm of the difference, and the three worst leaves of
    each number are reported beside them.
    """
    med = statistics.median(gnorm.values())
    kept = [p for p in gnorm if gnorm[p] >= NEGLIGIBLE * med]
    gaps = [abs(a - b) / abs(b) for a, b in zip(sut.losses, ref.losses)]
    out = {"loss": gaps[0], "loss_later": max(gaps[1:], default=0.0)}
    zero = jnp.zeros((), jnp.float32)
    for name, got, want, base, size in (
            ("grad", sut.gdir, ref.gdir, None, gnorm),
            ("update", sut.params, ref.params, x0, None)):
        rows = {}
        for p in kept:
            got_n, want_n, diff = (float(u) for u in _norms(
                got[p], want[p], zero if base is None else base[p]))
            w = size[p] if size else want_n   # the leaf's reference norm
            rows[p] = (abs(got_n - want_n) * w / max(want_n, 1e-38),
                       diff * w / max(want_n, 1e-38), w)
        scale = statistics.median(r[2] for r in rows.values())
        gaps = {p: (r[0] / max(r[2], scale), r[1] / max(r[2], scale))
                for p, r in rows.items()}
        out[name] = max(g[1] for g in gaps.values())
        out[name + "_norm"] = max(g[0] for g in gaps.values())
        worst = sorted(gaps, key=lambda p: -gaps[p][1])[:3]
        out[name + "_worst"] = [[p, gaps[p][1]] for p in worst]
    return out
