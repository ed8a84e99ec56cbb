"""The system under test, as the harness drives it: the program's own
launcher (`repro.launch.train`), built from a configuration and a traffic
file. This is the one module of the benchmark that imports the program.
"""
from __future__ import annotations

import dataclasses
import time
from unittest import mock

import jax
import numpy as np


def leaves_under(tree, field: str) -> dict:
    """{param path: leaf} of the subtree of an optimizer state whose
    NamedTuple field is `field` (every optimizer of the program names its
    first moment `mu` and its float32 masters `master`)."""
    from bench.check import paths_of

    out = {}
    for path, leaf in paths_of(tree).items():
        names = path.split("/")
        if field in names:
            out["/".join(names[names.index(field) + 1:])] = leaf
    return out


def argv(config: dict, traffic: dict, chips: int, seed: int) -> list:
    """The launcher's command line for this cell."""
    if traffic.get("microbatches", 1) != 1:
        raise SystemExit("the launcher has no gradient accumulation option")
    return ["--arch", config["arch"], "--full",
            "--batch", str(traffic["per_chip_batch"] * chips),
            "--seq", str(traffic["seq"]),
            "--optimizer", config["optimizer"]["name"],
            "--precision", config["precision"],
            "--schedule", traffic["schedule"], "--lr", str(traffic["lr"]),
            "--steps", str(traffic["schedule_steps"]), "--seed", str(seed)]


def arch_for(config: dict, weights):
    """The program's registered architecture, cut as the configuration
    says, whose init returns `weights`. A key the configuration states
    that differs in the program, and is not in `reduced`, is an error."""
    from repro.configs import get_arch

    arch = get_arch(config["arch"])
    cut = {}
    for key, want in config["model"].items():
        if not hasattr(arch.cfg, key):
            continue                      # a setting the program hard-codes
        have = getattr(arch.cfg, key)
        if key in config["reduced"]:
            cut[key] = want
        elif have != want:
            raise SystemExit(f"program's {config['arch']} has {key}={have!r}, "
                             f"the configuration states {want!r}")

    class Seeded(type(arch)):
        def init(self, rng):
            del rng
            return weights

    fields = {f.name: getattr(arch, f.name) for f in dataclasses.fields(arch)}
    fields["cfg"] = dataclasses.replace(arch.cfg, **cut)
    return Seeded(**fields)


def build(config: dict, traffic: dict, chips: int, seed: int, weights,
          devices):
    """make_trainer for this cell, with the harness's weights."""
    from repro.launch import train

    args = train.build_parser().parse_args(argv(config, traffic, chips, seed))
    arch = arch_for(config, weights)
    with mock.patch.object(train, "get_arch", lambda name: arch):
        return train.make_trainer(args, devices=devices)


def train_steps(tr, n: int) -> list:
    """n steps through the program's own loop; returns their losses."""
    from repro.launch import train

    return train.train_steps(tr, n)["losses"]


def settle(tr) -> None:
    jax.block_until_ready((tr.params, tr.opt_state))


def first_moment(tr) -> dict:
    """Host copy of the optimizer's first moment, {param path: array}."""
    return {p: np.asarray(a) for p, a in
            jax.device_get(leaves_under(tr.opt_state, "mu")).items()}


def masters(tr) -> dict:
    """Host float32 parameters as the next step starts from them: the
    masters, or the model copy where it is float32 and has no master."""
    from bench.check import paths_of

    stored = jax.device_get(leaves_under(tr.opt_state, "master"))
    params = paths_of(tr.params)
    return {p: np.asarray(stored[p] if np.size(stored.get(p, ())) else
                          jax.device_get(params[p]), np.float32)
            for p in params}


class Feed:
    """The trainer's batch iterator, timed: each next() is a host span
    `bench.input` (in the profiler's trace too), and the first `keep`
    batches are kept for the reference."""

    def __init__(self, inner, keep: int):
        self.inner, self.keep = inner, keep
        self.kept, self.seconds = [], 0.0

    def __iter__(self):
        return self

    def __next__(self):
        with jax.profiler.TraceAnnotation("bench.input"):
            t = time.perf_counter()
            batch = next(self.inner)
            self.seconds += time.perf_counter() - t
        if len(self.kept) < self.keep:
            self.kept.append(batch)
        return batch

    def reset(self) -> None:
        self.seconds = 0.0
