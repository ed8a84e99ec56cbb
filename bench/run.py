#!/usr/bin/env python3
"""One run of one benchmark cell, on the TPU this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (`bench/configs/`), its training job
(`bench/traffic/`), its limits (`bench/limits/`) and its per-layer metrics
(`bench/metrics/`) are found by the names in BENCHMARK.json. A run:

1. builds the program's trainer (`repro.launch.train.make_trainer`) with
   weights the reference makes from the seed, and wraps its batch feed in a
   timed span `bench.input`;
2. takes the first `check.STEPS` steps through the program's own
   `train_steps`, keeping what `correct` compares; they compile the step
   (or load it from the persistent cache). `setup_s` is the wall time from
   process start to here;
3. times one `train_steps` call of as many steps as fill `--seconds` at the
   set-up steps' pace, from settled state to settled state: `tokens_per_s`
   is all tokens of those steps over that time, on all chips of the cell;
4. with `--trace 1`, then profiles a few more steps under a host span
   `bench.window` and prints the per-layer metrics instead;
5. reads the peak device memory, frees the trainer, and runs the reference
   over the same steps (`bench/check.py`).

The last line of standard output is one JSON object; the numbers compared
for `correct` end it, under "checks", and end standard error too. Without a
TPU, with fewer chips than the cell asks for, or without the program beside
the benchmark, it exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse                                            # noqa: E402
import gc                                                  # noqa: E402
import importlib.util                                      # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import pathlib                                             # noqa: E402
import shutil                                              # noqa: E402
import sys                                                 # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"
TRACE_SECONDS = 2.0      # device time a traced run profiles, at least 3 steps


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(name: str) -> dict:
    """Everything BENCHMARK.json and the data files say about one cell."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT / entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{name}.json")["limits"]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    return {"cell": cell, "config": config, "traffic": traffic,
            "limits": limits, "end_to_end": bench["end_to_end"],
            "per_layer": per_layer}


def find_chips(chips: int):
    """The first `chips` TPU devices, or None with the reason on stderr."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU found (JAX platform {devs[0].platform!r}); "
              f"nothing runs on another backend", file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, found {len(devs)}",
              file=sys.stderr)
        return None
    return devs[:chips]


def use_compile_cache() -> None:
    """JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache; every
    program is cached, so that only a checkout's first run compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def start(spec: dict, seed: int, devices):
    """Step 1 and 2 of the module docstring: the trainer after its first
    check.STEPS steps, its timed feed, what `correct` compares, and the
    seconds of one step at the set-up steps' pace."""
    from bench import check, program

    config, traffic = spec["config"], spec["traffic"]
    weights = check.initial_weights(config, seed)
    tr = program.build(config, traffic, spec["cell"]["chips"], seed, weights,
                       devices)
    del weights
    feed = tr.data = program.Feed(tr.data, check.STEPS)

    losses = program.train_steps(tr, 1)
    mu = program.first_moment(tr)
    t = time.perf_counter()
    losses += program.train_steps(tr, check.STEPS - 1)
    pace = (time.perf_counter() - t) / (check.STEPS - 1)
    beta1 = config["optimizer"]["beta1"]
    sut = check.Readings(losses, {p: a.astype("float32") / (1 - beta1)
                                  for p, a in mu.items()},
                         program.masters(tr))
    return tr, feed, sut, pace


def measure(spec: dict, seed: int, seconds: float, trace: bool, devices):
    """Steps 1 to 4 of the module docstring. Returns (record, readings)."""
    import jax

    from bench import program

    config, traffic = spec["config"], spec["traffic"]
    chips = spec["cell"]["chips"]
    tr, feed, sut, pace = start(spec, seed, devices)
    setup_s = time.perf_counter() - T0

    steps = max(1, round(seconds / pace))
    program.settle(tr)
    feed.reset()
    t = time.perf_counter()
    window_losses = program.train_steps(tr, steps)
    program.settle(tr)
    wall = time.perf_counter() - t

    tokens = traffic["per_chip_batch"] * chips * traffic["seq"]
    record = {
        "cell": spec["cell"]["name"], "chips": chips, "config": config,
        "traffic": traffic, "seed": seed, "setup_s": setup_s,
        "compile_s": tr.compile_s, "tokens_per_step": tokens,
        "window": {"steps": steps, "seconds": wall,
                   "input_s": feed.seconds},
        "failed": sum(1 for x in window_losses if finite(x) is None),
        "device_kind": devices[0].device_kind,
        "batches": feed.kept,
        "trace": None,
    }
    if trace:
        from bench import trace as btrace

        n = max(3, round(TRACE_SECONDS / pace))
        where = OUT / "trace" / spec["cell"]["name"]
        shutil.rmtree(where, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0       # Python's own calls slow the host
        with jax.profiler.trace(str(where), profiler_options=options):
            with jax.profiler.TraceAnnotation(btrace.WINDOW):
                program.train_steps(tr, n)
                program.settle(tr)
        record["trace"] = btrace.reduce(btrace.latest(where), n)
    record["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    del tr, feed
    gc.collect()
    return record, sut


def correctness(spec: dict, record: dict, sut) -> dict:
    """The numbers compared, against the reference's same steps."""
    import numpy as np

    from bench import check

    rows = [b["tokens"] for b in record["batches"]]
    flat = np.concatenate([r.reshape(r.shape[0], -1) for r in rows])
    if len(np.unique(flat, axis=0)) != len(flat):
        raise SystemExit("the feed repeated a row in the checked steps")
    optimizer = dict(spec["config"]["optimizer"], lr=spec["traffic"]["lr"])
    ref, gnorm = check.reference_run(spec["config"], optimizer,
                                     record["seed"], record["batches"])
    x0 = check.paths_of(check.initial_weights(spec["config"], record["seed"]))
    return check.compare(sut, ref, gnorm, x0)


def metrics_of(spec: dict, record: dict, trace: bool) -> dict:
    """End-to-end metrics from the window, or per-layer ones from readers."""
    out = {}
    if not trace:
        values = {"tokens_per_s": record["tokens_per_step"]
                  * record["window"]["steps"] / record["window"]["seconds"],
                  "setup_s": record["setup_s"]}
        for m in spec["end_to_end"]:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        return out
    for m in spec["per_layer"]:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def finite(x):
    """JSON has no NaN: a number that is not finite is printed as null."""
    if isinstance(x, float) and (x != x or abs(x) == float("inf")):
        return None
    return x


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             devices) -> tuple:
    """Measure, check and report one run: (result object, diagnostics)."""
    record, sut = measure(spec, seed, seconds, trace, devices)
    numbers = correctness(spec, record, sut)
    checks = {k: {"value": finite(numbers[k]), "limit": lim}
              for k, lim in spec["limits"].items()}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    device = {"platform": devices[0].platform, "kind": record["device_kind"],
              "count": len(devices),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": record["window"]["steps"],
              "failed": record["failed"],
              "metrics": metrics_of(spec, record, trace),
              "device": device}
    if trace:
        tr = record["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = tr["breakdown"]
    result["checks"] = checks
    diagnostics = {k: finite(v) for k, v in numbers.items()
                   if k not in checks}
    return result, diagnostics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not beside the benchmark "
              f"under {ROOT}", file=sys.stderr)
        return 2
    here = [p for p in sys.path if pathlib.Path(p or ".").resolve() != BENCH]
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + here
    spec = resolve(args.workload)
    devices = find_chips(spec["cell"]["chips"])
    if devices is None:
        return 2
    use_compile_cache()

    result, diagnostics = run_cell(spec, args.seed, args.seconds,
                                   bool(args.trace), devices)
    print(json.dumps({"diagnostics": diagnostics}), flush=True)
    print(json.dumps(result), flush=True)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
