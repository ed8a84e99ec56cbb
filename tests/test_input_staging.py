"""train_steps stages each batch one step ahead: the feed is read exactly
once a step, in order, never past the end of a call, and the losses are
those of the same compiled step run serially on the same batches.

The four-device case runs this file as a script in a process of its own,
whose CPU backend is split into four devices
(--xla_force_host_platform_device_count=4)."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import train
from repro.metrics import trace

ROOT = pathlib.Path(__file__).resolve().parents[1]
ARGV = ["--arch", "bert-large", "--seq", "16", "--precision", "bf16",
        "--steps", "3"]


@pytest.fixture(autouse=True)
def fresh():
    trace.reset()
    yield
    trace.reset()


class Recording:
    """A batch feed that keeps every batch it hands out."""

    def __init__(self, inner):
        self.inner, self.got = inner, []

    def __iter__(self):
        return self

    def __next__(self):
        batch = next(self.inner)
        self.got.append(batch)
        return batch


def run_as_bench_run_does(batch: int, devices=None):
    """A trainer over `devices`, its feed recorded, driven by one call of
    one step and one of two, as `bench/run.py` does; returns the trainer,
    its initial state, the feed, the losses and the feed's length after
    each call."""
    tr = train.make_trainer(
        train.build_parser().parse_args(ARGV + ["--batch", str(batch)]),
        devices=devices)
    feed = tr.data = Recording(tr.data)
    start = (tr.params, tr.opt_state)          # nothing is donated
    losses, taken = [], []
    for n in (1, 2):
        losses += train.train_steps(tr, n)["losses"]
        taken.append(len(feed.got))
    return tr, start, feed, losses, taken


def serial_replay(tr, start, batches):
    """The same compiled step, one batch at a time as it was fed before
    staging: `jnp.asarray` of each leaf, call, wait, loss."""
    params, opt_state = start
    losses = []
    for host in batches:
        params, opt_state, metrics = jax.block_until_ready(tr.compiled(
            params, opt_state, {k: jnp.asarray(v) for k, v in host.items()}))
        losses.append(float(metrics["loss"]))
    return losses, params


def staged_share(steps) -> tuple:
    """(staged, all) `train.input` spans inside `steps`: staged are those
    that open after their step's `train.dispatch` closed."""
    inside = {id(s) for s in steps}
    dispatch = {id(d.parent): d for d in trace.spans("train.dispatch")
                if id(d.parent) in inside}
    inputs = [s for s in trace.spans("train.input") if id(s.parent) in inside]
    staged = [s for s in inputs if s.start_ns >= dispatch[id(s.parent)].end_ns]
    return len(staged), len(inputs)


def bit_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_feed_read_once_a_step_and_losses_match_serial_replay():
    tr, start, feed, losses, taken = run_as_bench_run_does(batch=4)
    assert taken == [1, 3]                      # 1 batch, then 2: none ahead
    fresh_feed = train.make_data(tr.arch, batch=4, seq=tr.args.seq,
                                 seed=tr.args.seed)
    for got in feed.got:                        # in the feed's own order
        want = next(fresh_feed)
        assert all(np.array_equal(got[k], want[k]) for k in want)

    replayed, params = serial_replay(tr, start, feed.got)
    assert losses == replayed                   # bit for bit
    assert bit_equal(tr.params, params)

    steps = trace.spans("train.step")
    assert [s.step for s in steps] == [0, 1, 2]
    assert staged_share(steps[:1]) == (0, 1)    # a call of one stages none
    assert staged_share(steps[1:]) == (1, 2)    # of two, one of its two


def dp4_run() -> dict:
    """The four-device check, in a process with four CPU devices: a
    data=4 trainer at batch 8, seq 16."""
    from repro.distributed.sharding import batch_axes

    devices = jax.devices()
    assert len(devices) == 4, devices
    tr, start, feed, losses, taken = run_as_bench_run_does(
        batch=8, devices=devices)
    replayed, params = serial_replay(tr, start, feed.got)
    want = tr.compiled.input_shardings[0][2]
    staged, _ = train._stage(tr)                # the loop's own staging
    return {
        "taken": taken, "losses": losses, "replayed": replayed,
        "params_equal": bit_equal(tr.params, params),
        "same_sharding": {k: v.sharding == want[k] for k, v in staged.items()},
        "rows_per_device": {k: sorted({s.data.shape[0]
                                       for s in v.addressable_shards})
                            for k, v in staged.items()},
        "devices_per_leaf": {k: len(v.sharding.device_set)
                             for k, v in staged.items()},
        "batch_axes": list(batch_axes(tr.mesh)),
        "dtypes": {k: [str(v.dtype), str(jnp.asarray(feed.got[0][k]).dtype)]
                   for k, v in staged.items()},
    }


def test_dp4_staged_batch_in_step_sharding_and_losses_match():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   filter(None, [str(ROOT / "src"),
                                 os.environ.get("PYTHONPATH")])),
               XLA_FLAGS=" ".join(filter(None, [
                   os.environ.get("XLA_FLAGS"),
                   "--xla_force_host_platform_device_count=4"])))
    proc = subprocess.run([sys.executable, __file__], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["taken"] == [1, 3]
    assert got["losses"] == got["replayed"] and got["params_equal"]
    assert "data" in got["batch_axes"]
    assert all(got["same_sharding"].values()), got["same_sharding"]
    assert all(n == 4 for n in got["devices_per_leaf"].values())
    assert all(rows == [2] for rows in got["rows_per_device"].values())
    assert all(a == b for a, b in got["dtypes"].values()), got["dtypes"]


if __name__ == "__main__":
    print(json.dumps(dp4_run()))
