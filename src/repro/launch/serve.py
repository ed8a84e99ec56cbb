"""Serving launcher: continuous-batching decode against an arch.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b \\
      --requests 16 --max-batch 4 --precision bf16 --metrics serve.jsonl \\
      --sampler temperature=0.8,top_k=40 --cache paged --shared-prefix 24

Generates a synthetic request stream (randomized prompt lengths and
generation budgets around --prompt-len / --new-tokens; --shared-prefix N
prepends a common N-token system prompt the paged cache deduplicates),
drives the requested engine and prints a JSON report: tokens/s,
time-to-first-token and inter-token latency percentiles, slot
utilization, peak concurrency, queue depth, preemption count and
shared/retained prefix block hits. --cache dense keeps the PR 2
per-slot-rows pool; --sampler greedy (default) or
"temperature=...,top_k=...,top_p=...,seed=..." samples with per-slot
PRNG keys (temperature=0 is bit-exact greedy). Scheduling is
policy-driven: --sched-policy picks the admission/preemption policy,
--growth lazy (default) allocates decode blocks on demand (preempting a
victim when the arena exhausts; --no-preempt turns that into an error),
--retain-blocks keeps evicted prefix blocks warm on a bounded LRU, and
--slo-ms evicts slots that blow their SLO. --chunk-budget N admits
prompts chunk by chunk within a per-step token budget (chunked prefill;
continuous+paged only). --spec-draft arms speculative draft-verify
decode: --spec-k draft tokens are proposed per slot and verified in one
batched step ('self' drafts with the target itself, 'truncated' builds
the make_spec_pair one-period draft whose proposals the doctored target
always accepts); the report gains acceptance-rate telemetry. --arrival-rate R replays the request stream as
seeded open-loop Poisson traffic at R req/s instead of submitting
everything up front, and reports goodput against the --ttft-slo-ms /
--itl-slo-ms bounds. --engine static runs the padded lockstep baseline
instead. --task picks the workload family: generate (default) decodes
with decoder or encoder-decoder archs — encdec archs synthesize
framed requests whose encoder output lands in the shared cross-
attention block arena (--shared-inputs N reuses N distinct inputs
round-robin, exercising encoder-block sharing) — while score / embed
need a bert arch and run batched masked-LM scoring / [CLS] embedding
through one fixed-shape forward (no KV cache; requests complete at
admission). --mesh DxM (e.g. 2x1, 1x2; a bare N means 1xN tensor
parallel) runs the continuous engine live-sharded over a local device
mesh — params per the distributed param rules, KV arenas blocks-over-
data / head_dim-over-model — with token output identical to the
unsharded engine (fp32 greedy, or bf16 with the stable-argmax
sampler). --replicas N serves the stream through N engine replicas
behind the prefix-affinity router (serving/router.py); --route-policy
picks prefix (content-addressed sticky routing, the default), depth
(least outstanding work) or rr (round-robin). --metrics writes one
JSONL record per decode step (active slots, queue depth, preemptions,
step latency) plus a final summary record — the serving analogue of
train.py's loss curve.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.configs import get_arch, reduced_arch
from repro.metrics import MetricsLogger
from repro.serving import (ContinuousEngine, ReplicaRouter, ServeEngine,
                           synthetic_encdec_requests, synthetic_requests,
                           synthetic_scoring_requests)

# Flags that configure the continuous engine's PAGED pool (or features
# built on it): each entry is (flag, fn(args) -> requested?). They must
# fail fast — uniformly — under --engine static or --cache dense, where
# the subsystem they configure does not exist and the printed numbers
# would never have exercised the requested setting.
PAGED_ONLY_FLAGS = (
    ("--growth", lambda a: a.growth is not None),
    ("--slots-budget", lambda a: a.slots_budget != 0),
    ("--retain-blocks", lambda a: a.retain_blocks is not None),
    ("--watermark", lambda a: a.watermark != 0),
    ("--chunk-budget", lambda a: a.chunk_budget is not None),
    ("--spec-draft", lambda a: a.spec_draft != "none"),
    ("--spec-k", lambda a: a.spec_k is not None),
    ("--replicas", lambda a: a.replicas != 1),
    ("--route-policy", lambda a: a.route_policy is not None),
    ("--attn-kernel paged", lambda a: a.attn_kernel == "paged"),
    ("--interpret", lambda a: a.interpret),
)

# Flags of the continuous engine's scheduler/traffic loop: valid with
# either cache, invalid under --engine static (no scheduler there).
CONTINUOUS_ONLY_FLAGS = (
    ("--sched-policy", lambda a: a.sched_policy != "fifo"),
    ("--slo-ms", lambda a: a.slo_ms is not None),
    ("--no-preempt", lambda a: not a.preempt),
    ("--arrival-rate", lambda a: a.arrival_rate is not None),
    ("--mesh", lambda a: a.mesh is not None),
)


def flag_errors(args) -> list:
    """Every flag-compatibility error for this parse, uniform wording —
    one SystemExit lists them all (unit-tested in-process over the full
    flag matrix in tests/test_metrics_and_launchers.py)."""
    errs = []
    paged = args.engine == "continuous" and args.cache == "paged"
    bad = [f for f, req in PAGED_ONLY_FLAGS if req(args) and not paged]
    if bad:
        errs.append(
            f"{' '.join(bad)}: only apply to the continuous engine's "
            f"paged pool (--engine continuous --cache paged)")
    if args.engine != "continuous":
        bad = [f for f, req in CONTINUOUS_ONLY_FLAGS if req(args)]
        if bad:
            errs.append(
                f"{' '.join(bad)}: only apply to the continuous "
                f"engine's scheduler (--engine continuous)")
    if paged and args.interpret and args.attn_kernel != "paged":
        errs.append(
            "--interpret: only applies to the Pallas kernel path "
            "(--attn-kernel paged)")
    return errs


def parse_mesh(spec):
    """'DxM' (data x model) or a bare 'N' (= 1xN tensor parallel) ->
    local mesh; None stays None (unsharded)."""
    if spec is None:
        return None
    from repro.launch.mesh import make_local_mesh
    low = str(spec).lower()
    data, model = low.split("x") if "x" in low else (1, low)
    return make_local_mesh(data=int(data), model=int(model))


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--engine", choices=["continuous", "static"],
                    default="continuous")
    ap.add_argument("--task", choices=["generate", "score", "embed"],
                    default="generate",
                    help="workload family: generate (decoder/encdec "
                         "autoregressive decode), score (bert batched "
                         "masked-LM scoring) or embed (bert pooled "
                         "[CLS] embeddings). score/embed need a bert "
                         "arch and hold no KV cache")
    ap.add_argument("--shared-inputs", type=int, default=0,
                    help="encdec only: number of DISTINCT encoder "
                         "inputs reused round-robin across --requests "
                         "(0: all distinct). Same-input requests share "
                         "cross-attention arena blocks copy-free")
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "bf16", "bf16_compute", "fp16"],
                    help="inference precision policy (greedy always fp32)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", "--max-batch", dest="max_batch", type=int,
                    default=4, help="decode slot-pool size")
    ap.add_argument("--max-len", type=int, default=0,
                    help="KV pool length (0: prompt-len + new-tokens)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--prefill-bucket", type=int, default=8,
                    help="round prompt lengths up to this multiple "
                         "(fewer prefill compiles; token-exact — one "
                         "batched prefill per bucket at admission)")
    ap.add_argument("--cache", choices=["paged", "dense"], default="paged",
                    help="paged: block arena + shared prompt prefixes; "
                         "dense: per-slot rows (PR 2 baseline)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged cache block granularity (must divide "
                         "max-len and any sliding window)")
    ap.add_argument("--slots-budget", type=int, default=0,
                    help="size the paged arena for this many dense-"
                         "equivalent slots (0: max-batch). Under lazy "
                         "growth this is a HIGH-WATERMARK on blocks in "
                         "use, not a per-request reservation — max-batch "
                         "can exceed it whenever budgets outrun typical "
                         "outputs or prefixes are shared")
    ap.add_argument("--growth", choices=["lazy", "eager"], default=None,
                    help="lazy (default): allocate decode blocks on "
                         "demand, preempt a victim when the arena "
                         "exhausts; eager: reserve the whole chain at "
                         "admission (PR 3 contract)")
    ap.add_argument("--sched-policy", default="fifo",
                    choices=["fifo", "arrival-deadline", "prefix-affinity"],
                    help="admission order + preemption victim selection "
                         "(see serving/scheduler.py)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="finish any slot active longer than this early "
                         "(SLO eviction of stuck slots; default off)")
    ap.add_argument("--no-preempt", dest="preempt", action="store_false",
                    help="turn lazy-growth arena exhaustion into an error "
                         "instead of preempting a victim")
    ap.add_argument("--retain-blocks", type=int, default=None,
                    help="LRU bound on warm prefix blocks kept alive after "
                         "their last holder evicts, per attention slot-"
                         "type (default: one batch's worth — covers a "
                         "multi-tenant prefix working set; 0 disables)")
    ap.add_argument("--watermark", type=int, default=0,
                    help="free blocks admission holds back per slot-type "
                         "so in-flight slots can grow without preempting")
    ap.add_argument("--attn-kernel", choices=["xla", "paged"], default=None,
                    help="paged decode attention: 'xla' gathers the block "
                         "arenas into a dense (B, ring) K/V copy per step; "
                         "'paged' streams blocks inside the fused Pallas "
                         "kernel (token-identical; interpret mode off-TPU; "
                         "requires --cache paged). Default: adopt the "
                         "arch config (usually 'xla')")
    ap.add_argument("--interpret", action="store_true",
                    help="force Pallas interpret mode for --attn-kernel "
                         "paged: the escape hatch for arena layouts that "
                         "fail real-TPU tile alignment (block_size / "
                         "head_dim off the 8/16 x 128 tile grid). Off-TPU "
                         "interpret is already the default")
    ap.add_argument("--chunk-budget", type=int, default=None,
                    help="per-step token budget for chunked-prefill "
                         "admission: prompts prefill chunk by chunk in "
                         "the decode loop's spare capacity instead of "
                         "one whole-prompt stall (continuous engine + "
                         "paged cache only; token-identical to whole-"
                         "prompt prefill)")
    ap.add_argument("--spec-draft", default="none",
                    choices=["none", "self", "truncated"],
                    help="speculative draft-verify decode: 'self' drafts "
                         "with the target model itself (exact-match "
                         "greedy proposals, acceptance ~1.0); "
                         "'truncated' doctors the target's upper "
                         "periods inert and drafts with its first "
                         "period (make_spec_pair; acceptance exactly "
                         "1.0). Continuous engine + paged cache only")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="draft tokens proposed and verified per "
                         "speculative round (>= 2; default 4)")
    ap.add_argument("--mesh", default=None,
                    help="serve live-sharded over a local device mesh: "
                         "'DxM' = data x model (e.g. 2x1, 1x2), bare N "
                         "= 1xN tensor parallel. Token-identical to "
                         "unsharded (fp32 greedy / bf16 stable argmax); "
                         "continuous engine only")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the prefix-affinity "
                         "router (serving/router.py); each replica owns "
                         "max-batch slots and its own paged arena")
    ap.add_argument("--route-policy", default=None,
                    choices=["prefix", "depth", "rr"],
                    help="router policy with --replicas: prefix "
                         "(content-addressed sticky affinity, default), "
                         "depth (least outstanding work), rr "
                         "(round-robin)")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="open-loop Poisson arrival rate in requests/s: "
                         "submit on the arrival clock instead of all up "
                         "front, and report goodput/SLO attainment "
                         "(continuous engine only; default closed-loop)")
    ap.add_argument("--ttft-slo-ms", type=float, default=1000.0,
                    help="open-loop TTFT bound (submit -> first token) "
                         "a request must meet to count toward goodput")
    ap.add_argument("--itl-slo-ms", type=float, default=200.0,
                    help="open-loop bound on EVERY inter-token gap; one "
                         "violation disqualifies the whole stream")
    ap.add_argument("--sampler", default="greedy",
                    help="'greedy' or 'temperature=0.8,top_k=40,"
                         "top_p=0.95,seed=0' (temperature=0 == greedy; "
                         "add stable=1 for the bf16 tie-stable argmax)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="common system-prompt tokens prepended to every "
                         "request (exercises prefix sharing)")
    ap.add_argument("--metrics", default=None,
                    help="JSONL path for per-step latency/throughput")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main():
    args = build_parser().parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    errs = flag_errors(args)
    if errs:
        raise SystemExit("; ".join(errs))

    arch = reduced_arch(args.arch) if args.reduced else get_arch(args.arch)
    if arch.kind not in ("decoder", "encdec", "bert"):
        raise SystemExit(f"{args.arch} is {arch.kind}: cannot serve")
    if args.engine == "static" and arch.kind != "decoder":
        raise SystemExit(
            f"--engine static is decoder-only, got {arch.kind}")
    if arch.kind == "bert" and args.task == "generate":
        raise SystemExit(f"{args.arch} is a bert arch: pass --task score "
                         f"or --task embed")
    if arch.kind != "bert" and args.task != "generate":
        raise SystemExit(f"--task {args.task} needs a bert arch, "
                         f"got {arch.kind}")
    for flag, wrong in (("--shared-prefix", args.shared_prefix
                         and arch.kind != "decoder"),
                        ("--shared-inputs", args.shared_inputs
                         and arch.kind != "encdec")):
        if wrong:
            raise SystemExit(f"{flag} does not apply to {arch.kind} archs")
    params = arch.init(jax.random.PRNGKey(args.seed))
    if arch.kind == "bert":     # scoring holds no decode budget
        max_len = args.max_len or args.prompt_len
    else:
        max_len = args.max_len or (args.prompt_len + args.new_tokens)

    if arch.kind == "encdec":
        reqs = synthetic_encdec_requests(
            args.requests, arch.cfg.vocab, n_frames=arch.cfg.n_frames,
            d_model=arch.cfg.d_model, prompt_len=args.prompt_len,
            new_tokens=args.new_tokens,
            n_inputs=args.shared_inputs or None, seed=args.seed)
    elif arch.kind == "bert":
        reqs = synthetic_scoring_requests(
            args.requests, arch.cfg.vocab, prompt_len=args.prompt_len,
            seed=args.seed)
    else:
        reqs = synthetic_requests(args.requests, arch.cfg.vocab,
                                  prompt_len=args.prompt_len,
                                  new_tokens=args.new_tokens,
                                  seed=args.seed,
                                  shared_prefix=args.shared_prefix)
    if args.shared_prefix:
        max_len += args.shared_prefix
    if args.cache == "paged" and arch.kind == "decoder":
        # arena rows come in whole blocks
        max_len = -(-max_len // args.block_size) * args.block_size
    log = MetricsLogger(args.metrics)

    spec_kw = {}
    spec_k = args.spec_k if args.spec_k is not None else 4
    if args.spec_draft == "self":
        spec_kw = dict(spec_draft=(arch, params), spec_k=spec_k)
    elif args.spec_draft == "truncated":
        from repro.serving import make_spec_pair
        params, draft_arch, draft_params = make_spec_pair(arch, params)
        spec_kw = dict(spec_draft=(draft_arch, draft_params),
                       spec_k=spec_k)

    mesh = parse_mesh(args.mesh)
    t0 = time.perf_counter()
    if args.engine == "continuous":
        last = {"t": t0}

        def make_on_step(replica):
            def on_step(rec):
                now = time.perf_counter()
                log.log(rec["step"], active=rec["active"],
                        queued=rec["queued"],
                        preemptions=rec["preemptions"],
                        step_latency_ms=(now - last["t"]) * 1e3,
                        replica=replica)
                last["t"] = now
            return on_step

        def make_engine(replica):
            return ContinuousEngine(
                arch, params, max_batch=args.max_batch, max_len=max_len,
                policy=args.precision, prefill_bucket=args.prefill_bucket,
                on_step=make_on_step(replica), cache=args.cache,
                block_size=args.block_size,
                slots_budget=args.slots_budget or None,
                sampler=args.sampler, attn_kernel=args.attn_kernel,
                kernel_interpret=True if args.interpret else None,
                growth=args.growth or "lazy",
                sched_policy=args.sched_policy,
                slo_ms=args.slo_ms, preempt=args.preempt,
                retain_blocks=args.retain_blocks,
                watermark=args.watermark,
                chunk_budget=args.chunk_budget, mesh=mesh,
                task=args.task, **spec_kw)

        if args.replicas > 1:
            engine = ReplicaRouter(
                [make_engine(i) for i in range(args.replicas)],
                policy=args.route_policy or "prefix")
        else:
            engine = make_engine(0)
        if args.arrival_rate is not None:
            from repro.serving import (OpenLoopDriver, SLO, poisson_arrivals,
                                       slo_report)
            arrivals = poisson_arrivals(len(reqs), args.arrival_rate,
                                        seed=args.seed)
            t0 = time.perf_counter()
            wall = OpenLoopDriver(engine, reqs, arrivals).run()
            stats = engine.report(wall)
            stats.update(slo_report(
                reqs, SLO(args.ttft_slo_ms, args.itl_slo_ms), wall))
        else:
            engine.run(reqs)
            stats = engine.report(time.perf_counter() - t0)
        pools = (engine.replicas[0].pool if args.replicas > 1
                 else engine.pool)
        attn_kernel = (pools.attn_kernel
                       if args.cache == "paged" and arch.kind == "decoder"
                       else "xla")
    else:
        attn_kernel = "xla"
        engine = ServeEngine(arch, params, max_len=max_len,
                             policy=args.precision, sampler=args.sampler)
        from repro.serving.metrics import aggregate
        for r in reqs:              # TTFT includes the inter-wave queue wait
            r.trace.mark_submit()
        for i in range(0, len(reqs), args.max_batch):
            engine.run_batch(reqs[i:i + args.max_batch])
        dt = time.perf_counter() - t0
        stats = aggregate([r.trace for r in reqs], dt,
                          sum(len(r.generated) for r in reqs))

    stats["engine"] = args.engine
    stats["task"] = args.task
    stats["precision"] = args.precision
    stats["cache"] = args.cache if args.engine == "continuous" else "static"
    stats["attn_kernel"] = attn_kernel
    stats["sampler"] = args.sampler
    stats["mesh"] = args.mesh or "1x1"
    log.log(-1, **{k: v for k, v in stats.items()
                   if isinstance(v, (int, float))})
    log.close()
    print(json.dumps({k: round(v, 3) if isinstance(v, float) else v
                      for k, v in stats.items()}))


if __name__ == "__main__":
    main()
