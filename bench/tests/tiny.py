"""A bert-large cell cut to a size the CPU runs in seconds, for driving the
harness in tests (every cut key listed in `reduced`), and a way to break
the program's train step underneath the harness."""
import json
import pathlib

import jax

from bench import program, run

ROOT = pathlib.Path(__file__).parents[2]
SIZES = {"n_layers": 2, "d_model": 64, "n_heads": 4, "d_ff": 128,
         "vocab": 256, "max_pos": 32}


def spec(cell: str = "bert-large.phase1") -> dict:
    full = run.resolve(cell)
    config = dict(full["config"], model=dict(full["config"]["model"], **SIZES),
                  reduced=sorted(SIZES))
    traffic = dict(full["traffic"], seq=16, per_chip_batch=8)
    return dict(full, config=config, traffic=traffic,
                cell=dict(full["cell"], chips=1))


def run_cell(spec: dict, seed: int = 3000000011) -> dict:
    """One run of the harness on the CPU, without its look for a chip."""
    result, _ = run.run_cell(spec, seed, 0.2, False, jax.devices()[:1])
    json.dumps(result)                       # the line it would print
    return result


def break_step(monkeypatch, wrap) -> None:
    """Every trainer the harness builds runs wrap(step_fn) as its step."""
    real = program.build

    def build(*args, **kw):
        tr = real(*args, **kw)
        tr.step_fn = wrap(tr.step_fn)
        return tr

    monkeypatch.setattr(program, "build", build)


def rows(batch: dict, share: float) -> dict:
    n = max(1, int(next(iter(batch.values())).shape[0] * share))
    return {k: v[:n] for k, v in batch.items()}
