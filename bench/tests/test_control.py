"""The control: the reference with its products in scaled float8, put in
the program's place, is not correct under each cell's limits; the program
itself, at this size, is."""
import jax
import pytest

from bench import check, run
from bench.tests import tiny


@pytest.mark.parametrize("cell", ["bert-large.phase1"])
def test_control_is_not_correct(cell):
    spec = tiny.spec(cell)
    seed = 3000000013
    tr, feed, sut, _ = run.start(spec, seed, jax.devices()[:1])
    batches = feed.kept
    del tr, feed
    optimizer = dict(spec["config"]["optimizer"], lr=spec["traffic"]["lr"])
    ref, gnorm = check.reference_run(spec["config"], optimizer, seed, batches)
    x0 = check.paths_of(check.initial_weights(spec["config"], seed))
    control = check.reference_run(spec["config"], optimizer, seed, batches,
                                  mode="fp8")[0]
    limits = spec["limits"]
    held = lambda numbers: all(numbers[k] <= v for k, v in limits.items())
    assert held(check.compare(sut, ref, gnorm, x0))
    assert not held(check.compare(control, ref, gnorm, x0))
