#!/usr/bin/env python3
"""The readings the limits of `correct` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds <first> --count 12 \
        [--controls 3]

In one process, for each of `--count` seeds from `--seeds` on: the program's
first check.STEPS steps against the float32 reference (the lower readings,
those of sound runs). For the first `--controls` of them also, each against
the same float32 reference: the control (the reference with its products in
scaled float8, bench/reference/numerics.py) and the faults a cell can have,
planted in the reference put in the program's place: half of each batch
left out with the mean taken over the rest (half of the tokens where a
batch is one sequence), and on several chips the
exchange between chips left out (each chip's gradient of its own rows
alone). A state left unchanged reads 1 by construction and needs no run.
Prints one JSON line per reading and a summary line last. Not part of a
benchmark run; it needs the cell's chips.
"""
import argparse
import gc
import json
import pathlib
import sys

sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])
from bench.run import (ROOT, find_chips, resolve, start,  # noqa: E402
                       use_compile_cache)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, required=True)
    ap.add_argument("--count", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args()
    sys.path.insert(1, str(ROOT / "src"))
    spec = resolve(args.workload)
    chips = spec["cell"]["chips"]
    devices = find_chips(chips)
    if devices is None:
        return 2
    use_compile_cache()
    from bench import check

    config = spec["config"]
    optimizer = dict(config["optimizer"], lr=spec["traffic"]["lr"])
    readings = []
    for i in range(args.count):
        seed = args.seeds + i
        tr, feed, sut, _ = start(spec, seed, devices)
        batches = feed.kept
        del tr, feed
        gc.collect()
        ref, gnorm = check.reference_run(config, optimizer, seed, batches)
        x0 = check.paths_of(check.initial_weights(config, seed))
        runs = {"program": sut}
        if i < args.controls:
            runs["control"] = check.reference_run(
                config, optimizer, seed, batches, mode="fp8")[0]
            runs["half_batch"] = check.reference_run(
                config, optimizer, seed, batches, share=0.5)[0]
            if chips > 1:
                runs["no_exchange"] = check.reference_run(
                    config, optimizer, seed, batches, share=1 / chips)[0]
        for name, got in runs.items():
            numbers = check.compare(got, ref, gnorm, x0)
            numbers.update(seed=seed, run=name, losses=got.losses,
                           ref_losses=ref.losses)
            readings.append(numbers)
            print(json.dumps(numbers), flush=True)
        del x0, ref, runs, sut
        gc.collect()
    summary = {}
    for name in sorted({r["run"] for r in readings}):
        rows = [r for r in readings if r["run"] == name]
        pick = max if name == "program" else min
        summary[name] = {k: pick(r[k] for r in rows)
                         for k in ("loss", "loss_later", "grad", "update",
                                   "grad_norm", "update_norm")}
    print(json.dumps({"workload": args.workload, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
