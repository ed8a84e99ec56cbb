#!/usr/bin/env python3
"""Bring-up check: the main path runs on a TPU, in this one process.

    python chip_smoke.py            # one chip: train, lans_kernel, paged_kernel
    python chip_smoke.py --chips 4  # four chips: data_parallel only

Phases (one JSON line each):

  train          bert-large at published widths (24L/1024d/16H, vocab
                 30522), seq 128, batch 16, LANS, bf16 policy: 5 steps
                 through repro.launch.train's own functions. Fails on a
                 non-finite loss or params that did not move.
  lans_kernel    two optimizer steps on the bert-large param tree with
                 the compiled Pallas fused LANS against the jnp LANS, and
                 the bf16 cast-and-apply variant against the generic
                 master-weight wrapper, from the same grads and state.
  paged_kernel   the compiled fused paged-attention kernel at qwen2.5-14b
                 decode widths (h 40, kv 8, hd 128, bs 16, bf16), S=1 and
                 S=4, against kernels/ref.paged_attention_fused_ref.
  data_parallel  (--chips 4) the train phase's 5 steps on a data=4 mesh
                 against the same global batches on one device.

The last line is {"ok": true, "device": {...}} only when every phase
passed. Without a TPU, or without the repository beside it, the script
exits non-zero and prints no result. Times are those of a bring-up run,
not a benchmark.
"""
import argparse
import json
import pathlib
import statistics
import sys

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

TRAIN_ARGV = ["--arch", "bert-large", "--full", "--steps", "5",
              "--batch", "16", "--seq", "128", "--optimizer", "lans",
              "--precision", "bf16"]
# tests/test_fused_integration.py: fused vs reference LANS params
LANS_RTOL, LANS_ATOL = 5e-4, 5e-5
# tests/test_kernels.py: fused paged kernel vs its oracle
PAGED_TOL = 2e-6


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def fail(phase: str, why: str):
    raise SystemExit(f"chip_smoke: {phase} failed: {why}")


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def tree_diff(got, want, rtol=LANS_RTOL, atol=LANS_ATOL) -> dict:
    """Max abs / rel difference of two trees, and whether every element
    passes |got - want| <= atol + rtol * |want| (assert_allclose's rule)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stats(a, b):
        out = []
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            x, y = x.astype(jnp.float32), y.astype(jnp.float32)
            d = jnp.abs(x - y)
            rel = jnp.where(y != 0, d / jnp.abs(y), jnp.where(d > 0, jnp.inf, 0))
            out.append(jnp.stack([jnp.max(d, initial=0.0),
                                  jnp.max(rel, initial=0.0),
                                  jnp.max(d - (atol + rtol * jnp.abs(y)),
                                          initial=-1.0)]))
        return jnp.max(jnp.stack(out), axis=0)

    mx_abs, mx_rel, excess = (float(v) for v in stats(got, want))
    return {"max_abs_diff": mx_abs, "max_rel_diff": mx_rel,
            "within_tol": excess <= 0.0}


# ---------------------------------------------------------------------------
# train: the launcher's own path at full width
# ---------------------------------------------------------------------------

def phase_train(dev):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import train

    args = train.build_parser().parse_args(TRAIN_ARGV)
    tr = train.make_trainer(args)
    p0 = jax.tree.map(jnp.copy, tr.params)
    hist = train.train_steps(tr, args.steps)
    moved = [bool(jnp.any(a != b)) for a, b in
             zip(jax.tree.leaves(tr.params), jax.tree.leaves(p0))]
    losses = hist["losses"]
    emit({"phase": "train", "arch": args.arch,
          "params": tr.arch.param_count(), "batch": args.batch,
          "seq": args.seq, "precision": args.precision,
          "optimizer": args.optimizer, "steps": args.steps,
          "compile_s": tr.compile_s,
          "step_s_median": statistics.median(hist["step_s"]),
          "step_s": hist["step_s"],
          "first_loss": losses[0], "last_loss": losses[-1],
          "losses": losses, "leaves_moved": f"{sum(moved)}/{len(moved)}",
          "peak_bytes_in_use": peak_bytes(dev)})
    if not np.all(np.isfinite(losses)):
        fail("train", f"non-finite loss {losses}")
    if not any(moved):
        fail("train", "params did not change")


# ---------------------------------------------------------------------------
# lans_kernel: compiled fused optimizer vs the jnp reference
# ---------------------------------------------------------------------------

def phase_lans_kernel(dev):
    import jax
    import jax.numpy as jnp
    from repro import precision as prec
    from repro.configs import get_arch
    from repro.core.optim import apply_updates, lans
    from repro.core.optim.fused import fused_lans
    from repro.kernels import default_interpret

    lr, steps = 2e-3, 2
    arch = get_arch("bert-large")
    params = jax.jit(arch.init)(jax.random.PRNGKey(0))

    def grads(like, i):
        leaves, treedef = jax.tree.flatten(like)
        keys = jax.random.split(jax.random.PRNGKey(100 + i), len(leaves))
        return jax.tree.unflatten(treedef, [
            1e-2 * jax.random.normal(k, x.shape, jnp.float32)
            for k, x in zip(keys, leaves)])

    def run(tx, p):
        """`steps` updates from the optimizer's initial state; returns
        (params, state). Grads and state are made inside the program."""
        state = tx.init(p)
        for i in range(steps):
            upd, state = tx.update(grads(p, i), state, p)
            p = apply_updates(p, upd)
        return p, state

    want = jax.jit(lambda p: run(lans(lr), p)[0])(params)
    got = jax.jit(lambda p: run(fused_lans(lr), p)[0])(params)
    fp32 = tree_diff(got, want)
    del got, want

    policy = prec.get_policy("bf16")
    lp = policy.cast_params(params)
    del params
    want = jax.jit(lambda p: run(
        prec.mixed_precision(lans(lr), policy), p)[1].master)(lp)
    got = jax.jit(lambda p: run(
        prec.fused_mixed_lans(lr, policy), p)[1].master)(lp)
    mixed = tree_diff(got, want)
    emit({"phase": "lans_kernel", "arch": "bert-large", "steps": steps,
          "interpret": default_interpret(), "tol": [LANS_RTOL, LANS_ATOL],
          "fused_lans_vs_lans": fp32,
          "fused_mixed_lans_bf16_vs_mixed_precision_lans_masters": mixed,
          "peak_bytes_in_use": peak_bytes(dev)})
    for name, d in (("fused_lans", fp32), ("fused_mixed_lans", mixed)):
        if not d["within_tol"]:
            fail("lans_kernel", f"{name} beyond tolerance: {d}")


# ---------------------------------------------------------------------------
# paged_kernel: compiled fused paged attention vs its oracle
# ---------------------------------------------------------------------------

def paged_case(S: int, seed: int):
    """Pool-shaped decode state at qwen2.5-14b widths: each live slot owns
    its blocks, history fills the ring up to its cursor, the S destination
    rows are unwritten (pos -1); the last slot is dead (null table,
    q_pos -1)."""
    import jax.numpy as jnp
    import numpy as np

    B, h, kv, hd, bs, nb = 8, 40, 8, 128, 16, 32
    ring, n_blocks = nb * bs, 1 + (B - 1) * nb
    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16
    ka = rng.standard_normal((n_blocks, bs, kv, hd), np.float32)
    va = rng.standard_normal((n_blocks, bs, kv, hd), np.float32)
    pos = np.full((n_blocks, bs), -1, np.int32)
    tbl = np.zeros((B, nb), np.int32)
    cursor = np.zeros(B, np.int32)
    qpos = np.full((B, S), -1, np.int32)
    for b in range(B - 1):
        tbl[b] = 1 + b * nb + np.arange(nb)
        cursor[b] = rng.integers(0, ring - S)
        rows = np.full(ring, -1, np.int32)
        rows[:cursor[b]] = np.arange(cursor[b])
        pos[tbl[b]] = rows.reshape(nb, bs)
        qpos[b] = cursor[b] + np.arange(S)
    q = rng.standard_normal((B, S, h, hd), np.float32)
    k_new = rng.standard_normal((B, S, kv, hd), np.float32)
    v_new = rng.standard_normal((B, S, kv, hd), np.float32)
    if S == 1:                                 # the 3-D decode form
        q, k_new, v_new, qpos = q[:, 0], k_new[:, 0], v_new[:, 0], qpos[:, 0]
    return (jnp.asarray(q, bf), jnp.asarray(k_new, bf),
            jnp.asarray(v_new, bf), jnp.asarray(ka, bf),
            jnp.asarray(va, bf), jnp.asarray(pos), jnp.asarray(tbl),
            jnp.asarray(qpos), jnp.asarray(cursor)), hd ** -0.5


def phase_paged_kernel(dev):
    import jax
    import numpy as np
    from repro.kernels import default_interpret, ref
    from repro.kernels.paged_attention_kernel import paged_attention_fused

    cases = []
    for S in (1, 4):
        args, scale = paged_case(S, seed=S)
        got = jax.block_until_ready(paged_attention_fused(*args, scale=scale))
        # the plain reference in true fp32 (TPU matmuls default to bf16
        # passes)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref.paged_attention_fused_ref,
                           static_argnames=("scale",))(*args, scale=scale)
        out_g, out_w = np.asarray(got[0]), np.asarray(want[0])
        ok_out = bool(np.allclose(out_g, out_w, rtol=PAGED_TOL,
                                  atol=PAGED_TOL))
        arenas = {n: bool(np.array_equal(np.asarray(g), np.asarray(w)))
                  for n, g, w in zip(("k", "v", "pos"), got[1:], want[1:])}
        cases.append({"S": S, "out_max_abs_diff":
                      float(np.max(np.abs(out_g - out_w))),
                      "out_within_tol": ok_out,
                      "arenas_bit_equal": arenas,
                      "finite": bool(np.isfinite(out_g).all())})
    emit({"phase": "paged_kernel", "h": 40, "n_kv": 8, "head_dim": 128,
          "block_size": 16, "dtype": "bfloat16",
          "interpret": default_interpret(), "tol": PAGED_TOL,
          "cases": cases})
    for c in cases:
        if not (c["out_within_tol"] and c["finite"]
                and all(c["arenas_bit_equal"].values())):
            fail("paged_kernel", f"S={c['S']}: {c}")


# ---------------------------------------------------------------------------
# data_parallel (--chips 4): data=4 mesh vs one device, same global batch
# ---------------------------------------------------------------------------

# LANS divides each element by its own second moment, so rounding noise
# in near-zero gradients moves those elements by up to a whole step:
# elementwise agreement is not expected, agreement of the update as a
# whole is. The same comparison on 4 virtual CPU devices (reduced config,
# batch 8, seq 32) gives |p4 - p1| / |p1 - p0| = 0.16 and relative loss
# differences up to 4e-4; the gates leave room for the chip's own
# reduction order.
DP_LOSS_RTOL = 5e-3
DP_UPDATE_RATIO = 0.5


def phase_data_parallel(devs):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import train

    def run(devices):
        args = train.build_parser().parse_args(TRAIN_ARGV)
        tr = train.make_trainer(args, devices=devices)
        p0 = jax.tree.map(lambda x: jax.device_put(x, devs[0]), tr.params)
        hist = train.train_steps(tr, args.steps)
        p = jax.tree.map(lambda x: jax.device_put(x, devs[0]), tr.params)
        return p0, p, hist, tr.compile_s

    _, p4, h4, c4 = run(devs)
    p0, p1, h1, c1 = run(devs[:1])

    @jax.jit
    def norms(a, b, c):
        sq = lambda x, y: sum(jnp.sum(jnp.square(u.astype(jnp.float32)
                                                 - v.astype(jnp.float32)))
                              for u, v in zip(jax.tree.leaves(x),
                                              jax.tree.leaves(y)))
        return jnp.sqrt(sq(a, b)), jnp.sqrt(sq(b, c))

    d41, d10 = (float(v) for v in norms(p4, p1, p0))
    loss_diff = [abs(a - b) for a, b in zip(h4["losses"], h1["losses"])]
    rel = [d / abs(b) for d, b in zip(loss_diff, h1["losses"])]
    ratio = d41 / d10
    emit({"phase": "data_parallel", "arch": "bert-large",
          "global_batch": 16, "seq": 128, "precision": "bf16",
          "steps": len(loss_diff), "mesh": {"data": len(devs), "model": 1},
          "losses_data4": h4["losses"], "losses_one_device": h1["losses"],
          "loss_abs_diff": loss_diff,
          "max_param_abs_diff": tree_diff(p4, p1)["max_abs_diff"],
          "update_diff_ratio": ratio,
          "compile_s": {"data4": c4, "one_device": c1},
          "step_s_median": {"data4": statistics.median(h4["step_s"]),
                            "one_device": statistics.median(h1["step_s"])},
          "peak_bytes_in_use": [peak_bytes(d) for d in devs]})
    if not (np.all(np.isfinite(h4["losses"])) and max(rel) <= DP_LOSS_RTOL
            and ratio <= DP_UPDATE_RATIO):
        fail("data_parallel", f"loss rel diff {max(rel)} (<= {DP_LOSS_RTOL}),"
             f" update ratio {ratio} (<= {DP_UPDATE_RATIO})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the data-parallel comparison")
    chips = ap.parse_args().chips

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devs[0].platform!r}); nothing is run on another backend",
              file=sys.stderr)
        return 1
    if len(devs) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 1
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"script ({e})", file=sys.stderr)
        return 1
    emit({"phase": "setup", "compile_cache": enable_compile_cache(),
          "jax": jax.__version__})

    if chips == 4:
        phase_data_parallel(devs[:4])
    else:
        phase_train(devs[0])
        phase_lans_kernel(devs[0])
        phase_paged_kernel(devs[0])
    emit({"ok": True, "device": {"platform": devs[0].platform,
                                 "kind": devs[0].device_kind,
                                 "count": len(devs)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
