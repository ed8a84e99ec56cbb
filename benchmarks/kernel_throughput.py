"""Pallas kernel benchmarks: fused optimizer step + paged decode attention.

On CPU the Pallas kernels run in interpret mode (Python-loop execution),
so wall-time favours the XLA paths — the meaningful numbers here are
(a) correctness at size and (b) the HBM-traffic model that predicts the
TPU win:

  fused_lans       the 3-phase pipeline reads/writes each tensor O(1)
                   times vs O(#ops) for the unfused elementwise chain;
  paged_attention  the read-side kernel streams exactly the block-table's
                   K/V blocks HBM->VMEM once, vs the XLA gather which
                   reads the arena, WRITES a dense (B, ring_len) K/V
                   copy and reads it back — ~3x the unavoidable bytes
                   on a memory-bound decode step;
  paged_attention_fused
                   additionally folds the decode token's K/V/pos scatter
                   into the kernel epilogue (arenas aliased in/out), so
                   the separate scatter round-trip disappears too: the
                   model drops to ~(1 + 1/nb)x the unavoidable K/V
                   bytes, gated at <= 1.1x below and machine-readably in
                   BENCH_kernels.json.

The XLA-path byte models are cross-checked against the compiled HLO's
own cost analysis (`measured/model` in the derived column) — the same
bytes-accessed source benchmarks/roofline_report.py aggregates — so the
3x claim is measured, not asserted; the fused-kernel model is arithmetic
over the BlockSpecs (interpret mode has no HBM counters to measure).

  PYTHONPATH=src python -m benchmarks.kernel_throughput                 # both
  PYTHONPATH=src python -m benchmarks.kernel_throughput --iters 1       # smoke
  PYTHONPATH=src python -m benchmarks.kernel_throughput --kernel paged_attention

The block/grid autotuner sweeps (block_size, S, grid order) per
(backend, head_dim, n_kv) and records each winner:

  PYTHONPATH=src python -m benchmarks.kernel_throughput --autotune
  PYTHONPATH=src python -m benchmarks.kernel_throughput --autotune --write-table

--write-table persists the winners to src/repro/configs/
paged_attn_tuned.json, the table `paged_attention` consults at trace
time (exact (backend, head_dim, n_kv, block_size, S) match; miss falls
back to the sequential "arbitrary" grid). The checked-in table carries
CPU/interpret results — harmless (grid order cannot change numerics,
only megacore utilization) and replaced by rerunning on real TPU.
"""
import argparse
import json
import pathlib
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.kernels.paged_attention_kernel import (
    paged_attention, paged_attention_fused)

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH_JSON = ROOT / "BENCH_kernels.json"
TUNED_TABLE = ROOT / "src" / "repro" / "configs" / "paged_attn_tuned.json"

SIZE = 1 << 16  # 64k-element block (fused_lans)

# paged-attention decode workload: 8 slots, ring 256 in 16-row blocks;
# head_dim 128 fills the 128-lane tile (tile_alignment_problems)
PA_SHAPE = dict(B=8, h=8, n_kv=2, hd=128, bs=16, nb=16)

# fused-model gate: bytes over the unavoidable K/V reads must stay under
FUSED_RATIO_LIMIT = 1.1

_iters_default = 5


def _time(fn, *args, iters=5):
    """Per-iteration wall times in us (callers reduce: p50 for tuning).

    The warmup result is block_until_ready'd BEFORE the timed region —
    otherwise compile + dispatch tail from the warmup leaks into the
    first timed iteration — and every iteration blocks on its own
    result, so each sample is a full dispatch+execute.
    """
    jax.block_until_ready(fn(*args))  # compile + drain
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    return times


def _p50(fn, *args, iters=5):
    return statistics.median(_time(fn, *args, iters=iters))


def _measured_bytes(fn, *args):
    """bytes-accessed of the compiled fn per XLA's own cost analysis —
    the number roofline_report feeds the memory roofline term."""
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return float(cost.get("bytes accessed", 0.0))


def run_lans(iters=_iters_default):
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(SIZE,)), jnp.float32)
    m = jnp.zeros((SIZE,), jnp.float32)
    v = jnp.zeros((SIZE,), jnp.float32)
    x = jnp.asarray(rng.normal(size=(SIZE,)), jnp.float32)

    fused = lambda: ops.fused_lans_step(g, m, v, x, eta=0.01, step=1)
    unfused = jax.jit(lambda: ref.lans_step_ref(g, m, v, x, eta=0.01, step=1))

    t_fused = _p50(fused, iters=iters)
    t_unfused = _p50(unfused, iters=iters)

    a = fused()
    b = unfused()
    err = float(jnp.max(jnp.abs(a.x - b.x)))

    # HBM traffic model (bytes touched per element, fp32):
    #   fused: phase0 reads g; phase1 reads g,m,v,x writes m,v; phase2 reads
    #          g,m,v,x writes x  -> 13 R/W per element
    #   unfused (op-at-a-time, ~20 elementwise passes over 4 tensors): ~40+
    bytes_fused = 13 * 4
    bytes_unfused = 40 * 4
    rows = [
        ("kernel/fused_lans_us", t_fused,
         f"interpret-mode on CPU; max|dx|={err:.2e} vs oracle"),
        ("kernel/unfused_lans_us", t_unfused, "jnp reference under jit"),
        ("kernel/hbm_bytes_per_elem", 0.0,
         f"fused {bytes_fused}B vs unfused ~{bytes_unfused}B "
         f"-> {bytes_unfused/bytes_fused:.1f}x traffic reduction on TPU"),
    ]
    return rows, err < 1e-4


def _pa_case(B, h, n_kv, hd, bs, nb, *, S=1, seed=0):
    """Dense-equivalent paged decode workload: slot b owns data blocks
    [1 + b*nb, 1 + (b+1)*nb); history fills the ring up to the cursor,
    the S rows at the cursor are unwritten (pos -1) — the state one
    fused decode/verify step consumes."""
    n_blocks = B * nb + 1
    rng = np.random.default_rng(seed)
    q_shape = (B, h, hd) if S == 1 else (B, S, h, hd)
    q = jnp.asarray(rng.normal(size=q_shape), jnp.bfloat16)
    ka = jnp.asarray(rng.normal(size=(n_blocks, bs, n_kv, hd)), jnp.bfloat16)
    va = jnp.asarray(rng.normal(size=(n_blocks, bs, n_kv, hd)), jnp.bfloat16)
    cur = (nb - 1) * bs + bs // 2              # first unwritten ring row
    pos = np.full((n_blocks, bs), -1, np.int32)
    tbl = 1 + np.arange(B * nb, dtype=np.int32).reshape(B, nb)
    for r in range(cur):                       # history: pos == ring row
        pos[tbl[:, r // bs], r % bs] = r
    kn_shape = (B, n_kv, hd) if S == 1 else (B, S, n_kv, hd)
    k_new = jnp.asarray(rng.normal(size=kn_shape), jnp.bfloat16)
    v_new = jnp.asarray(rng.normal(size=kn_shape), jnp.bfloat16)
    if S == 1:
        qpos = np.full((B,), cur, np.int32)
    else:
        qpos = np.tile(cur + np.arange(S, dtype=np.int32), (B, 1))
    cursor = np.full((B,), cur, np.int32)
    return dict(q=q, ka=ka, va=va, pos=jnp.asarray(pos),
                tbl=jnp.asarray(tbl), qpos=jnp.asarray(qpos),
                k_new=k_new, v_new=v_new, cursor=jnp.asarray(cursor),
                scale=1.0 / float(np.sqrt(hd)))


def run_paged_attention(iters=_iters_default):
    """Read-side and scatter-fused kernels vs the XLA gather/scatter."""
    B, h, n_kv, hd, bs, nb = (PA_SHAPE[k] for k in
                              ("B", "h", "n_kv", "hd", "bs", "nb"))
    c = _pa_case(B, h, n_kv, hd, bs, nb)
    ring = nb * bs

    # ----- read side: arenas already scattered ---------------------------
    scat = ref.paged_attention_fused_ref(
        c["q"], c["k_new"], c["v_new"], c["ka"], c["va"], c["pos"],
        c["tbl"], c["qpos"], c["cursor"], scale=c["scale"])
    ka2, va2, pos2 = scat[1], scat[2], scat[3]
    pallas_fn = lambda: paged_attention(
        c["q"], ka2, va2, pos2, c["tbl"], c["qpos"], scale=c["scale"])
    xla_fn = jax.jit(lambda: ref.paged_attention_ref(
        c["q"], ka2, va2, pos2, c["tbl"], c["qpos"], scale=c["scale"]))
    t_pallas = _p50(pallas_fn, iters=iters)
    t_xla = _p50(xla_fn, iters=iters)
    err = float(jnp.max(jnp.abs(pallas_fn() - xla_fn())))

    # ----- fused: pre-scatter arenas, the kernel carries the write -------
    fused_fn = lambda: paged_attention_fused(
        c["q"], c["k_new"], c["v_new"], c["ka"], c["va"], c["pos"],
        c["tbl"], c["qpos"], c["cursor"], scale=c["scale"])
    xla_fused = lambda ka, va, pos: ref.paged_attention_fused_ref(
        c["q"], c["k_new"], c["v_new"], ka, va, pos,
        c["tbl"], c["qpos"], c["cursor"], scale=c["scale"])
    t_fused = _p50(fused_fn, iters=iters)
    t_xla_fused = _p50(jax.jit(xla_fused), c["ka"], c["va"], c["pos"],
                       iters=iters)
    fo, fk, fv, fp = fused_fn()
    ro, rk, rv, rp = xla_fused(c["ka"], c["va"], c["pos"])
    err_f = float(jnp.max(jnp.abs(fo - ro)))
    arenas_exact = all(bool(jnp.array_equal(a, b))
                       for a, b in ((fk, rk), (fv, rv), (fp, rp)))

    # HBM traffic per decode step per layer (bf16 = 2 bytes):
    #   every path must read the referenced K+V blocks once (kv_bytes);
    #   the XLA gather additionally WRITES the dense (B, ring, kv, hd)
    #   K+V copy and READS it back for the attention contraction (3x),
    #   and the separate XLA scatter round-trips the touched arena rows
    #   on top. The fused kernel re-writes only the destination block per
    #   slot (1/nb of the reads) plus the new-row operands themselves.
    kv_bytes = B * ring * n_kv * hd * 2 * 2   # K+V blocks, read once
    xla_bytes = 3 * kv_bytes                  # + dense-copy write + read
    fused_bytes = (kv_bytes                   # block reads
                   + B * bs * n_kv * hd * 2 * 2   # dest-block K+V writes
                   + B * n_kv * hd * 2 * 2)       # new-row operands
    fused_ratio = fused_bytes / kv_bytes
    measured = _measured_bytes(xla_fused, c["ka"], c["va"], c["pos"])
    # The model is a LOWER bound on the compiled program's bytes: the
    # HLO must at least round-trip what the model charges. On CPU the
    # unfused graph also materializes every intermediate (repeated GQA
    # heads, fp32 logits, softmax temps), so measured/model lands well
    # above 1 here; TPU fusion is what brings it toward 1 — the gate is
    # therefore measured >= model, with the ratio reported for the
    # roofline comparison rather than pinned.
    meas_ratio = measured / xla_bytes if xla_bytes else 0.0

    rows = [
        ("kernel/paged_attn_pallas_us", t_pallas,
         f"interpret-mode on CPU; max|do|={err:.2e} vs XLA gather"),
        ("kernel/paged_attn_xla_us", t_xla,
         f"dense arena[table] gather under jit (B={B}, ring={ring})"),
        ("kernel/paged_attn_fused_us", t_fused,
         f"scatter-in-epilogue kernel; max|do|={err_f:.2e}, arenas "
         f"{'bit-exact' if arenas_exact else 'MISMATCH'} vs XLA scatter"),
        ("kernel/paged_attn_xla_fused_us", t_xla_fused,
         "XLA scatter + gather + attention under one jit"),
        ("kernel/paged_attn_hbm_bytes", 0.0,
         f"gather ~{xla_bytes}B vs fused {fused_bytes}B per step/layer "
         f"over {kv_bytes}B unavoidable -> {xla_bytes/kv_bytes:.1f}x vs "
         f"{fused_ratio:.2f}x (limit {FUSED_RATIO_LIMIT}x)"),
        ("kernel/paged_attn_measured_bytes", 0.0,
         f"XLA-path HLO cost_analysis {measured:.3g}B vs {xla_bytes}B "
         f"modeled -> measured/model {meas_ratio:.2f} (>= 1 required; "
         f"roofline_report uses the same bytes-accessed source)"),
    ]
    ok = (err < 1e-5 and err_f < 1e-5 and arenas_exact
          and fused_ratio <= FUSED_RATIO_LIMIT
          and meas_ratio >= 1.0)
    payload = {
        "kernels": [
            {"name": n, "us": round(us, 2), "derived": d}
            for n, us, d in rows],
        "bytes_model": {
            "kv_bytes_unavoidable": kv_bytes,
            "xla_gather_bytes": xla_bytes,
            "fused_bytes": fused_bytes,
            "fused_ratio": round(fused_ratio, 4),
            "fused_ratio_limit": FUSED_RATIO_LIMIT,
            "xla_measured_bytes": measured,
            "xla_measured_over_model": round(meas_ratio, 4),
        },
        "pass": bool(ok),
    }
    return rows, ok, payload


def autotune(iters=_iters_default, write_table=False):
    """Sweep (block_size, S, grid order) per (backend, head_dim, n_kv)
    on the fused kernel; winner = p50-fastest grid order per
    (block_size, S). Returns (rows, table)."""
    backend = jax.default_backend()
    rows, table = [], {backend: {}}
    h, n_kv, hd = PA_SHAPE["h"], PA_SHAPE["n_kv"], PA_SHAPE["hd"]
    B, nb = 4, 4                                  # small tuning workload
    for bs in (8, 16, 32):
        for S in (1, 4):
            best = None
            for order in ("arbitrary", "parallel"):
                c = _pa_case(B, h, n_kv, hd, bs, nb, S=S)
                fn = lambda: paged_attention_fused(
                    c["q"], c["k_new"], c["v_new"], c["ka"], c["va"],
                    c["pos"], c["tbl"], c["qpos"], c["cursor"],
                    scale=c["scale"], grid_order=order)
                us = statistics.median(_time(fn, iters=iters))
                rows.append((f"autotune/hd{hd}_kv{n_kv}_bs{bs}_S{S}_{order}",
                             us, f"backend={backend}"))
                if best is None or us < best[1]:
                    best = (order, us)
            table[backend].setdefault(f"hd{hd}_kv{n_kv}", {})[
                f"bs{bs}_S{S}"] = {"grid_order": best[0],
                                   "us": round(best[1], 2)}
    if write_table:
        existing = {}
        if TUNED_TABLE.exists():
            existing = json.loads(TUNED_TABLE.read_text())
        existing.update(table)                    # replace this backend
        TUNED_TABLE.write_text(json.dumps(existing, indent=2,
                                          sort_keys=True) + "\n")
        rows.append(("autotune/table_written", 0.0, str(TUNED_TABLE)))
    return rows, table


KERNELS = {"lans": run_lans, "paged_attention": run_paged_attention}


def run(kernel: str = "all", iters: int = _iters_default):
    """benchmarks/run.py entry point: rows + combined PASS flag. Also
    emits BENCH_kernels.json (name/us/bytes-model/PASS) whenever the
    paged-attention bench runs, so the perf trajectory is machine-
    trackable across PRs."""
    names = list(KERNELS) if kernel == "all" else [kernel]
    rows, ok, payload = [], True, None
    for name in names:
        out = KERNELS[name](iters=iters)
        r, o = out[0], out[1]
        if len(out) > 2:
            payload = out[2]
        rows += r
        ok = ok and o
    if payload is not None:
        payload["pass"] = bool(payload["pass"] and ok)
        BENCH_JSON.write_text(json.dumps(payload, indent=2) + "\n")
    return rows, ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel", default="all", choices=["all", *KERNELS])
    ap.add_argument("--iters", type=int, default=_iters_default,
                    help="timed iterations per kernel (p50 reported); "
                         "--iters 1 is the CI smoke mode")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep (block_size, S, grid order) on the fused "
                         "kernel and report winners per configuration")
    ap.add_argument("--write-table", action="store_true",
                    help="with --autotune: persist winners to "
                         "src/repro/configs/paged_attn_tuned.json (the "
                         "table paged_attention consults at trace time)")
    args = ap.parse_args()
    if args.write_table and not args.autotune:
        raise SystemExit("--write-table requires --autotune")
    rows, ok = run(args.kernel, iters=args.iters)
    if args.autotune:
        tune_rows, _ = autotune(iters=args.iters,
                                write_table=args.write_table)
        rows += tune_rows
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f'{name},{us:.1f},"{derived}"')
    print(f"kernel_throughput/STATUS,0,{'PASS' if ok else 'FAIL'}")
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
