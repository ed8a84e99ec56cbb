#!/usr/bin/env python3
"""One run of one benchmark cell with the program's tracing on, and what its
spans and named scopes show.

    python3 bench/profile.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--out DIR] [--hlo]

The run is bench/run.py's own (`run.run_cell`: set-up steps, timed window,
with --trace 1 the profiled window, the check), after the program's
`repro.metrics.trace.enable(True)`, so that its spans also enter the
profile. It prints run.py's result line, then writes
<out>/<cell>.<seed>.trace<0|1>.json (default out: bench/out/profile):

- "steps": each step's span and its phases' spans in ms, with the window
  it belongs to (setup, timed, profiled) and its compiles;
- "spans": the program's totals per span name; "compiles": each compile
  event the program counted, with the span it ran in;
- with --trace 1, "scopes": the profiled window's device self time a step
  by named scope and pass, and the unscoped remainder (bench/scopes.py);
  "top_ops": the heaviest ops with their scope; "idle_by_leaf_span": the
  window's idle time by leaf host span (bench/spans.py).

--hlo also writes the compiled step's HLO text, gzipped, beside it.
"""
import time

T0 = time.perf_counter()

import argparse                                            # noqa: E402
import collections                                         # noqa: E402
import gzip                                                # noqa: E402
import json                                                # noqa: E402
import pathlib                                             # noqa: E402
import sys                                                 # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
PHASES = ("train.input", "train.to_device", "train.compile", "train.dispatch",
          "train.wait", "train.fetch")


def step_rows(ptrace, setup_steps: int, timed_steps: int) -> list:
    """One row a `train.step` span: its phases in ms and its window."""
    phases = collections.defaultdict(dict)
    compiles = collections.Counter()
    for s in ptrace.spans():
        if s.name in PHASES and s.parent is not None:
            phases[id(s.parent)][s.name] = 1e3 * s.seconds
            compiles[id(s.parent)] += s.compiles
    rows = []
    for s in ptrace.spans("train.step"):
        window = ("setup" if s.step < setup_steps else
                  "timed" if s.step < setup_steps + timed_steps else
                  "profiled")
        rows.append({"step": s.step, "window": window, "ms": 1e3 * s.seconds,
                     "compiles": s.compiles + compiles[id(s)],
                     **phases[id(s)]})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--out", default=str(BENCH / "out" / "profile"))
    ap.add_argument("--hlo", action="store_true")
    args = ap.parse_args(argv)

    here = [p for p in sys.path if pathlib.Path(p or ".").resolve() != BENCH]
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + here
    from bench import check, run, scopes, spans
    from bench import trace as btrace
    from repro.metrics import trace as ptrace

    run.T0 = T0
    spec = run.resolve(args.workload)
    devices = run.find_chips(spec["cell"]["chips"])
    if devices is None:
        return 2
    run.use_compile_cache()
    ptrace.enable(True)
    result, _ = run.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                             devices)
    print(json.dumps(result), flush=True)

    out = {"cell": args.workload, "seed": args.seed, "trace": args.trace,
           "result": result,
           "steps": step_rows(ptrace, check.STEPS, result["attempted"]),
           "spans": ptrace.summary(),
           "compiles": [[(t - ptrace.spans("train.setup")[0].start_ns) / 1e9,
                         name, kind] for t, name, kind in ptrace.compiles()]}
    compiled = ptrace.program("train.step")
    hlo = compiled.as_text()
    if args.trace:
        tr = btrace.load(btrace.latest(BENCH / "out" / "trace"
                                       / args.workload))
        times, _ = scopes.window_self_times(tr)
        steps = sum(r["window"] == "profiled" for r in out["steps"])
        where = scopes.scope_map(hlo)
        table = scopes.split(times, where, steps)
        out["scopes"] = table
        out["top_ops"] = [[op, t / steps, *where.get(scopes.bare(op),
                                                      (None, "other"))]
                          for op, t in collections.Counter(times)
                          .most_common(25)]
        out["idle_by_leaf_span"] = spans.idle_by_leaf_span(tr)
    dest = pathlib.Path(args.out)
    dest.mkdir(parents=True, exist_ok=True)
    stem = f"{args.workload}.{args.seed}.trace{args.trace}"
    (dest / f"{stem}.json").write_text(json.dumps(out, indent=1))
    if args.hlo:
        with gzip.open(dest / f"{stem}.hlo.txt.gz", "wt") as f:
            f.write(hlo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
