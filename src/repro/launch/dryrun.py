import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination
with ShapeDtypeStruct inputs — no allocation — and record memory / cost /
collective analysis for the roofline report.

The two lines above MUST stay first: jax locks the device count at first
init, and the production meshes need 512 placeholder host devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all            # everything
Flags: --mesh {pod1,pod2,both}  --out experiments/dryrun  --microbatches N
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED, SHAPES, get_arch
from repro.core.optim import lans
from repro.core.schedules import warmup_hold_decay
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis
from repro.launch.mesh import V5E, make_production_mesh

# The placeholder devices are host CPUs; the roofline is for the chip the
# production mesh is made of.
TARGET_KIND = V5E


def _shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def make_tx(arch):
    """The paper's optimizer + schedule, as lowered into the train step."""
    sched = warmup_hold_decay(0.00675, 3519, 1501, 962)  # paper stage-1 shape
    mu_dtype = arch.cfg.param_dtype if arch.zero3 else jnp.float32
    return lans(sched, mu_dtype=mu_dtype)


def lower_one(arch_name: str, shape_name: str, multi_pod: bool,
              microbatches: int = 1, attn_kernel: str = "xla") -> dict:
    arch = get_arch(arch_name)
    if attn_kernel != "xla" and arch.kind == "decoder":
        # Lower the decode shapes with the fused Pallas paged-attention
        # step instead of the XLA gather. Off-TPU this lowers the
        # interpret-mode kernel (practical only for reduced shapes — the
        # interpreter unrolls the (B, blocks) grid); on TPU it lowers
        # the compiled Mosaic kernel the production mesh would run.
        import dataclasses as _dc
        arch = _dc.replace(arch, cfg=_dc.replace(arch.cfg,
                                                 attn_kernel=attn_kernel))
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size

    record = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "pod2" if multi_pod else "pod1", "n_chips": n_chips,
        "kind": shape.kind, "params": arch.param_count(),
        "device_kind": TARGET_KIND,
        "zero3": arch.zero3,
    }
    if not arch.supports(shape_name):
        record["status"] = "skipped"
        record["reason"] = ("long_500k requires sub-quadratic attention"
                            if shape_name == "long_500k"
                            else f"{arch.kind} has no {shape.kind} step")
        return record

    t0 = time.time()
    params_abs = arch.abstract_params()
    pspec = shd.params_pspec(params_abs, mesh, zero3=arch.zero3)
    batch_abs = arch.input_specs(shape_name)
    bspec = shd.batch_pspec(batch_abs, mesh)

    if shape.kind == "train":
        tx = make_tx(arch)
        opt_abs = jax.eval_shape(tx.init, params_abs)
        mspec = None
        if arch.zero1 and not arch.zero3:
            # ZeRO-1: moments additionally sharded over "data"
            mspec = shd.params_pspec(params_abs, mesh, zero3=True)
        ospec = shd.opt_state_pspec(opt_abs, pspec, moments_spec=mspec)

        # Microbatch rows must stay divisible by the FULL data-parallel
        # extent (pod x data) or batch_pspec degrades to replicated and
        # every chip computes the whole microbatch (qwen32 pod2 showed 32x
        # FLOP replication at mb=16 — EXPERIMENTS.md iter 5).
        dp_total = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        mb = max(1, min(arch.train_microbatches,
                        shape.global_batch // dp_total))

        def train_step(params, opt_state, batch):
            def loss_fn(p, b):
                loss, aux = arch.loss_fn(p, b)
                return loss, aux

            # fp32 grad accumulation for fp32-master archs; bf16 for the
            # bf16-weights archs (documented memory/precision trade).
            acc_dtype = arch.cfg.param_dtype

            if mb <= 1:
                (loss, _), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
                grads = jax.tree.map(
                    lambda g: g.astype(acc_dtype), grads)
            else:
                # gradient accumulation over microbatch slices (paper setup:
                # 96K global batch through a fixed device footprint)
                def body(carry, i):
                    acc, loss_acc = carry
                    sl = jax.tree.map(
                        lambda x: jax.lax.dynamic_slice_in_dim(
                            x, i * (x.shape[0] // mb), x.shape[0] // mb, 0)
                        if getattr(x, "ndim", 0) >= 1 else x, batch)
                    # re-pin batch sharding: GSPMD loses it on dynamic-slice
                    # along the sharded dim and would replicate the compute
                    sl = shd.constrain(sl, mesh, shd.batch_pspec(sl, mesh))
                    (loss, _), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(params, sl)
                    acc = jax.tree.map(
                        lambda a, g: a + g.astype(acc_dtype), acc, grads)
                    return (acc, loss_acc + loss), None

                zero = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, acc_dtype), params)
                (grads, loss_sum), _ = jax.lax.scan(
                    body, (zero, jnp.zeros((), jnp.float32)),
                    jnp.arange(mb))
                grads = jax.tree.map(lambda g: g / mb, grads)
                loss = loss_sum / mb

            updates, new_opt = tx.update(grads, opt_state, params)
            from repro.core.optim.base import apply_updates
            new_params = apply_updates(params, updates)
            return new_params, new_opt, loss

        jitted = jax.jit(
            train_step,
            in_shardings=(_shardings(mesh, pspec), _shardings(mesh, ospec),
                          _shardings(mesh, bspec)),
            out_shardings=(_shardings(mesh, pspec), _shardings(mesh, ospec),
                           None),
            donate_argnums=(0, 1),
        )
        with mesh:
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
            compiled = lowered.compile()

    elif shape.kind == "prefill":
        def prefill_step(params, batch):
            return arch.prefill(params, batch)

        cache_abs = jax.eval_shape(
            lambda p, b: arch.prefill(p, b)[1], params_abs, batch_abs)
        cspec = shd.cache_pspec(cache_abs, mesh)
        jitted = jax.jit(
            prefill_step,
            in_shardings=(_shardings(mesh, pspec), _shardings(mesh, bspec)),
            out_shardings=(None, _shardings(mesh, cspec)),
        )
        with mesh:
            lowered = jitted.lower(params_abs, batch_abs)
            compiled = lowered.compile()

    elif shape.kind == "decode" and arch.kind == "decoder":
        # Pooled PAGED decode: lower the exact serving step the
        # continuous-batching engine runs — block arenas sharded blocks-
        # over-data / head_dim-over-model, block-table gather included —
        # so the production-mesh sharding of the paged pool gets HLO
        # coverage (the engine-side no-recompile property is asserted in
        # tests/test_paged_cache.py).
        from repro.distributed.steps import build_serve_step

        cache_abs = arch.paged_cache_specs(shape_name)
        B = shape.global_batch
        tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        pos_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        jitted = build_serve_step(arch.decode_step, mesh,
                                  params_like=params_abs,
                                  cache_like=cache_abs)
        record["cache"] = "paged"
        record["attn_kernel"] = attn_kernel
        with mesh:
            lowered = jitted.lower(params_abs, tok_abs, pos_abs, cache_abs)
            compiled = lowered.compile()

    else:  # decode, enc-dec archs (whisper): dense cross-attention cache
        cache_abs = arch.cache_specs(shape_name)
        cspec = shd.cache_pspec(cache_abs, mesh)

        def serve_step(params, batch, cache):
            return arch.decode_step(params, batch, cache)

        jitted = jax.jit(
            serve_step,
            in_shardings=(_shardings(mesh, pspec), _shardings(mesh, bspec),
                          _shardings(mesh, cspec)),
            out_shardings=(None, _shardings(mesh, cspec)),
            donate_argnums=(2,),
        )
        with mesh:
            lowered = jitted.lower(params_abs, batch_abs, cache_abs)
            compiled = lowered.compile()

    analysis = hlo_analysis.analyze_compiled(lowered, compiled, n_chips,
                                             device_kind=TARGET_KIND)

    # useful-FLOPs ratio: MODEL_FLOPS / HLO_FLOPs (catches remat/redundancy)
    n_active = active_params(arch)
    n_tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                     else 1)
    if shape.kind == "train":
        model_flops = hlo_analysis.model_flops_training(n_active, n_tokens)
    else:
        model_flops = hlo_analysis.model_flops_inference(n_active, n_tokens)
    analysis["model_flops"] = model_flops
    analysis["useful_flops_ratio"] = (
        model_flops / analysis["flops_global"]
        if analysis.get("flops_global") else 0.0)

    record.update(analysis)
    record["status"] = "ok"
    record["lower_compile_s"] = round(time.time() - t0, 1)
    return record


def active_params(arch) -> int:
    """Parameters touched per token (MoE: top_k of n_experts)."""
    total = arch.param_count()
    cfg = arch.cfg
    if getattr(cfg, "n_experts", 0) and cfg.n_experts > cfg.top_k:
        import math
        expert_leaf = 0
        params = arch.abstract_params()
        from repro.core.optim.base import tree_paths
        paths = jax.tree.leaves(tree_paths(params))
        leaves = jax.tree.leaves(params)
        for pth, leaf in zip(paths, leaves):
            if leaf.ndim == 4 and leaf.shape[1] == cfg.n_experts:
                expert_leaf += math.prod(leaf.shape)
        total = total - expert_leaf + expert_leaf * cfg.top_k // cfg.n_experts
    return total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all' (the 10 assigned)")
    ap.add_argument("--shape", default="all", choices=["all", *SHAPES])
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2", "both"])
    ap.add_argument("--attn-kernel", default="xla", choices=["xla", "paged"],
                    help="decode shapes: lower the XLA arena gather or the "
                         "fused Pallas paged-attention step (see lower_one)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ASSIGNED if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"pod1": [False], "pod2": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch_name in archs:
        for shape_name in shapes:
            for multi_pod in meshes:
                tag = f"{arch_name}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
                try:
                    rec = lower_one(arch_name, shape_name, multi_pod,
                                    attn_kernel=args.attn_kernel)
                except Exception as e:
                    rec = {"arch": arch_name, "shape": shape_name,
                           "mesh": "pod2" if multi_pod else "pod1",
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    failures += 1
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=2, default=str)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" bound={r['dominant']} "
                             f"c={r['compute_s']:.3f}s m={r['memory_s']:.3f}s "
                             f"x={r['collective_s']:.3f}s "
                             f"useful={rec['useful_flops_ratio']:.2f} "
                             f"[{rec['lower_compile_s']}s]")
                elif status == "skipped":
                    extra = f" ({rec['reason']})"
                else:
                    extra = f" {rec['error'][:160]}"
                print(f"{tag:60s} {status}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} combinations failed")


if __name__ == "__main__":
    main()
