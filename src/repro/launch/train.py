"""Training launcher — runs real steps on local devices.

Trains with the paper's full recipe: LANS + warmup-hold-decay schedule +
sharded-without-replacement data. --reduced (the default) picks the small
CPU-sized config of --arch; --full the published widths. The step runs
data-parallel over every local device (batch split over the `data` axis;
--batch must divide evenly): one CPU, one TPU chip, or all chips of a host.

  PYTHONPATH=src python -m repro.launch.train --arch bert-large --steps 50 \
      --batch 32 --seq 128 --optimizer lans

Mixed precision (--precision {fp32,bf16,fp16}): fp16/bf16 hold the model
copy in half precision with fp32 master weights in the optimizer state;
fp16 adds apex-style dynamic loss scaling (skip the step + halve the scale
on overflow, grow it back after clean steps). The live `loss_scale` and
`overflow_count` appear in the console line and the JSONL metrics:

  PYTHONPATH=src python -m repro.launch.train --arch bert-large --steps 30 \
      --precision fp16 --metrics /tmp/fp16.jsonl
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save as ckpt_save
from repro.configs import get_arch, reduced_arch
from repro.core.optim import adamw, lamb, lans
from repro.core.schedules import warmup_hold_decay, warmup_linear_decay
from repro.data.corpus import SyntheticCorpus, lm_batch_iterator, mlm_batch_iterator
from repro.data.sharding import ShardSpec
from repro import precision as prec


def make_optimizer(name: str, schedule, *, policy=None, **kw):
    if name == "lans" and policy is not None:
        # moments store in the policy's dtype (math stays fp32 in-kernel).
        kw.setdefault("mu_dtype", policy.moment_dtype)
    return {"lans": lans, "lamb": lamb, "adamw": adamw}[name](schedule, **kw)


def make_data(arch, *, batch: int, seq: int, num_workers: int = 1, seed: int = 0):
    """Sharded-without-replacement stream (paper §3.4), worker 0 view."""
    corpus = SyntheticCorpus(vocab=arch.cfg.vocab, num_docs=4096,
                             doc_len=max(2 * seq + 2, 256), seed=seed)
    spec = ShardSpec(num_samples=corpus.num_docs, num_workers=num_workers,
                     worker=0, seed=seed)
    if arch.kind == "bert":
        return mlm_batch_iterator(corpus, spec, per_worker_batch=batch,
                                  seq_len=seq, seed=seed)
    if arch.kind == "encdec":
        rng = np.random.default_rng(seed)
        def gen():
            it = lm_batch_iterator(corpus, spec, per_worker_batch=batch,
                                   seq_len=seq)
            for b in it:
                yield {"frames": rng.normal(
                           size=(batch, arch.cfg.n_frames, arch.cfg.d_model)
                       ).astype(np.float32),
                       "tokens": b["tokens"], "labels": b["labels"]}
        return gen()
    if arch.embeds_input:
        rng = np.random.default_rng(seed)
        def gen():
            it = lm_batch_iterator(corpus, spec, per_worker_batch=batch,
                                   seq_len=seq)
            for b in it:
                yield {"embeds": rng.normal(
                           size=(batch, seq, arch.cfg.d_model)
                       ).astype(np.float32) * 0.02,
                       "labels": b["labels"]}
        return gen()
    return lm_batch_iterator(corpus, spec, per_worker_batch=batch, seq_len=seq)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bert-large")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--optimizer", default="lans",
                    choices=["lans", "lamb", "adamw"])
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "bf16", "fp16"],
                    help="fp16/bf16: low-precision model copy + fp32 master "
                         "weights; fp16 adds dynamic loss scaling")
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--schedule", default="hold",
                    choices=["hold", "linear", "const"])
    ap.add_argument("--warmup-frac", type=float, default=0.2)
    ap.add_argument("--hold-frac", type=float, default=0.3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--metrics", default="", help="JSONL metrics path")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    return ap


@dataclasses.dataclass
class Trainer:
    """Everything one training run holds: built by `make_trainer`,
    advanced by `train_steps`."""

    args: argparse.Namespace
    arch: object
    sched: object
    policy: object
    mesh: object
    step_fn: object
    pspec: object
    ospec: object
    params: object
    opt_state: object
    data: object
    compiled: object = None     # the AOT-compiled step, set by train_steps
    compile_s: float = 0.0


def make_trainer(args, devices=None) -> Trainer:
    """Config, optimizer, data-parallel mesh, initial params and state.

    The mesh is (data=len(devices), model=1) over `devices`, all local
    devices by default."""
    arch = reduced_arch(args.arch) if args.reduced else get_arch(args.arch)
    if args.reduced:
        args.seq = min(args.seq, arch.cfg.max_pos if arch.kind == "bert"
                       else getattr(arch.cfg, "max_seq", args.seq))

    warm = max(1, int(args.steps * args.warmup_frac))
    hold = int(args.steps * args.hold_frac)
    if args.schedule == "hold":
        sched = warmup_hold_decay(args.lr, args.steps + 1, warm, hold)
    elif args.schedule == "linear":
        sched = warmup_linear_decay(args.lr, args.steps + 1, warm)
    else:
        sched = lambda _: jnp.asarray(args.lr, jnp.float32)
    policy = prec.get_policy(args.precision)
    tx = make_optimizer(args.optimizer, sched, policy=policy)
    if policy.wants_wrapper:
        arch = dataclasses.replace(arch, cfg=policy.apply_to_cfg(arch.cfg))

    # One step builder for every entry point: build_train_step owns the
    # mixed-precision wiring (master-weight wrapper, loss scaling, metrics).
    from repro.distributed.steps import build_train_step
    from repro.launch.mesh import make_local_mesh

    devices = list(devices) if devices is not None else jax.devices()
    if args.batch % len(devices):
        raise ValueError(f"--batch {args.batch} does not split evenly over "
                         f"{len(devices)} devices")
    mesh = make_local_mesh(data=len(devices), model=1, devices=devices)
    step_fn, init_fn, specs_for = build_train_step(
        arch.loss_fn, tx, mesh, param_init_fn=arch.init, policy=policy)
    params, opt_state = init_fn(jax.random.PRNGKey(args.seed))
    pspec, ospec = specs_for(params, opt_state)
    data = make_data(arch, batch=args.batch, seq=args.seq, seed=args.seed)
    return Trainer(args, arch, sched, policy, mesh, step_fn, pspec, ospec,
                   params, opt_state, data)


def train_steps(tr: Trainer, steps: int, logger=None) -> dict:
    """Run `steps` steps. The first call compiles the step ahead of time
    (its seconds land in tr.compile_s); every step's wall time ends when
    its params, state and metrics are ready on the device.

    Returns {"losses": [...], "step_s": [...]}."""
    from repro.distributed.steps import jit_train_step

    args, policy = tr.args, tr.policy
    losses, step_s = [], []
    with tr.mesh:
        for i in range(steps):
            batch = {k: jnp.asarray(v) for k, v in next(tr.data).items()}
            if tr.compiled is None:
                t0 = time.perf_counter()
                tr.compiled = jit_train_step(
                    tr.step_fn, tr.mesh, tr.pspec, tr.ospec, batch).lower(
                        tr.params, tr.opt_state, batch).compile()
                tr.compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            tr.params, tr.opt_state, metrics = jax.block_until_ready(
                tr.compiled(tr.params, tr.opt_state, batch))
            step_s.append(time.perf_counter() - t0)
            losses.append(float(metrics["loss"]))
            if logger is None:
                continue
            extra = {}
            if policy.wants_wrapper:
                extra = {"loss_scale": metrics["loss_scale"],
                         "overflow_count": metrics["overflow_count"]}
            logger.log(i + 1, loss=metrics["loss"],
                       lr=tr.sched(jnp.asarray(i)), **extra)
            if (i + 1) % args.log_every == 0 or i == 0:
                ls_txt = (f"  scale {float(extra['loss_scale']):.0f}"
                          f"  ovf {int(extra['overflow_count'])}"
                          if extra else "")
                print(f"step {i+1:5d}  loss {losses[-1]:.4f}  "
                      f"(ema {logger.smoothed_loss:.4f})  "
                      f"lr {float(tr.sched(jnp.asarray(i))):.2e}  "
                      f"{sum(step_s) / len(step_s):.2f}s/step{ls_txt}",
                      flush=True)
    return {"losses": losses, "step_s": step_s}


def main(argv=None):
    args = build_parser().parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    tr = make_trainer(args)
    from repro.metrics import MetricsLogger

    logger = MetricsLogger(args.metrics or None)
    losses = train_steps(tr, args.steps, logger)["losses"]
    logger.close()

    if args.ckpt_dir:
        ckpt_save(args.ckpt_dir, args.steps, tr.params,
                  metadata={"arch": args.arch, "optimizer": args.optimizer,
                            "final_loss": losses[-1]})
        print("checkpoint saved to", args.ckpt_dir)
    print(json.dumps({"first_loss": losses[0], "final_loss": losses[-1],
                      "steps": args.steps}))


if __name__ == "__main__":
    main()
