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

`train_steps` stages input one step ahead: while step i runs on the
devices, the host takes batch i+1 from the feed and copies it into the
compiled step's own input sharding, so the build is off the step's
critical path. One step is in flight at a time, and a call takes exactly
as many batches as it runs steps.

Every phase of set-up and of each step is a host span of
`repro.metrics.trace` (`train.setup`, `train.init`, `train.data`;
`train.step`, `train.input`, `train.to_device`, `train.compile`,
`train.dispatch`, `train.wait`, `train.fetch`). Each JSONL line carries its
step's `input_ms` (the build of its own batch), `dispatch_ms` and `wait_ms`
from those spans, and --metrics also turns tracing on: a profile captured
from the running process (`jax.profiler`, TensorBoard's capture) then shows
the spans beside the device's ops.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save as ckpt_save
from repro.configs import get_arch, reduced_arch
from repro.core.optim import adamw, lamb, lans
from repro.core.schedules import warmup_hold_decay, warmup_linear_decay
from repro.data.corpus import SyntheticCorpus, lm_batch_iterator, mlm_batch_iterator
from repro.data.sharding import ShardSpec
from repro.metrics import trace
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
    compile_s: float = 0.0      # the `train.compile` span's seconds
    step: int = 0               # steps taken


def make_trainer(args, devices=None) -> Trainer:
    """Config, optimizer, data-parallel mesh, initial params and state.

    The mesh is (data=len(devices), model=1) over `devices`, all local
    devices by default. Timed as spans `train.setup`, `train.init` (until
    the state is ready on the devices) and `train.data`."""
    with trace.span("train.setup"):
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
        # mixed-precision wiring (master-weight wrapper, loss scaling,
        # metrics).
        from repro.distributed.steps import build_train_step
        from repro.launch.mesh import make_local_mesh

        devices = list(devices) if devices is not None else jax.devices()
        if args.batch % len(devices):
            raise ValueError(f"--batch {args.batch} does not split evenly "
                             f"over {len(devices)} devices")
        mesh = make_local_mesh(data=len(devices), model=1, devices=devices)
        step_fn, init_fn, specs_for = build_train_step(
            arch.loss_fn, tx, mesh, param_init_fn=arch.init, policy=policy)
        with trace.span("train.init"):
            params, opt_state = jax.block_until_ready(
                init_fn(jax.random.PRNGKey(args.seed)))
        pspec, ospec = specs_for(params, opt_state)
        with trace.span("train.data"):
            data = make_data(arch, batch=args.batch, seq=args.seq,
                             seed=args.seed)
    return Trainer(args, arch, sched, policy, mesh, step_fn, pspec, ospec,
                   params, opt_state, data)


def train_steps(tr: Trainer, steps: int, logger=None) -> dict:
    """Run `steps` steps, each a span `train.step` holding the spans of its
    phases (see `repro.metrics.trace`), on exactly `steps` batches taken
    from tr.data in order.

    Input is staged one step ahead (`_stage`). Batch 0 is staged in step 0,
    before its dispatch; batch i+1 in step i, after step i is dispatched
    and before its wait, so the host builds and copies it while step i
    runs. The last step stages nothing: no batch crosses the end of a
    call. Step i's outputs are waited on before step i+1 is dispatched, so
    one step is in flight at a time.

    The first call compiles the step ahead of time, in span
    `train.compile`, whose seconds land in tr.compile_s; a step's `step_s`
    is its dispatch and its wait, until its params, state and metrics are
    ready on the device.

    Returns {"losses": [...], "step_s": [...]}."""
    from repro.distributed.steps import jit_train_step

    losses, step_s = [], []
    staged = None
    with tr.mesh:
        for i in range(steps):
            with trace.span("train.step", step=tr.step):
                batch, inp = staged if staged is not None else _stage(tr)
                if tr.compiled is None:
                    with trace.span("train.compile") as comp:
                        tr.compiled = jit_train_step(
                            tr.step_fn, tr.mesh, tr.pspec, tr.ospec,
                            batch).lower(tr.params, tr.opt_state,
                                         batch).compile()
                    tr.compile_s = comp.seconds
                    trace.keep_program("train.step", tr.compiled)
                with trace.span("train.dispatch") as dispatch:
                    out = tr.compiled(tr.params, tr.opt_state, batch)
                staged = _stage(tr) if i + 1 < steps else None
                with trace.span("train.wait") as wait:
                    tr.params, tr.opt_state, metrics = jax.block_until_ready(
                        out)
                step_s.append(dispatch.seconds + wait.seconds)
                tr.step += 1
                with trace.span("train.fetch"):
                    losses.append(float(metrics["loss"]))
                    if logger is not None:
                        _log(tr, logger, i, metrics, losses, step_s,
                             input_ms=1e3 * inp.seconds,
                             dispatch_ms=1e3 * dispatch.seconds,
                             wait_ms=1e3 * wait.seconds)
    return {"losses": losses, "step_s": step_s}


def _stage(tr: Trainer):
    """The feed's next batch (span `train.input`), put by `jax.device_put`
    straight into the compiled step's input sharding (`train.to_device`),
    so that the call takes it as it is. Returns (batch, its input span)."""
    from repro.distributed.steps import batch_sharding

    with trace.span("train.input") as inp:
        host = next(tr.data)
    with trace.span("train.to_device"):
        batch = jax.device_put(host, batch_sharding(tr.mesh, host))
    return batch, inp


def _log(tr: Trainer, logger, i: int, metrics, losses, step_s, **spans_ms):
    """One JSONL line for step i, and every --log-every steps a console
    line."""
    extra = {}
    if tr.policy.wants_wrapper:
        extra = {"loss_scale": metrics["loss_scale"],
                 "overflow_count": metrics["overflow_count"]}
    logger.log(i + 1, loss=metrics["loss"], lr=tr.sched(jnp.asarray(i)),
               **extra, **spans_ms)
    if (i + 1) % tr.args.log_every == 0 or i == 0:
        ls_txt = (f"  scale {float(extra['loss_scale']):.0f}"
                  f"  ovf {int(extra['overflow_count'])}"
                  if extra else "")
        print(f"step {i+1:5d}  loss {losses[-1]:.4f}  "
              f"(ema {logger.smoothed_loss:.4f})  "
              f"lr {float(tr.sched(jnp.asarray(i))):.2e}  "
              f"{sum(step_s) / len(step_s):.2f}s/step{ls_txt}",
              flush=True)


def main(argv=None):
    args = build_parser().parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.metrics:
        trace.enable(True)
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
