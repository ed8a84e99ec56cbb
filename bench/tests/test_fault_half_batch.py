"""A train step that leaves out half of the batch, the mean taken over the
rest, is not correct."""
from bench.tests import tiny


def test_half_batch(monkeypatch):
    tiny.break_step(monkeypatch, lambda step: lambda p, o, b: step(
        p, o, tiny.rows(b, 0.5)))
    result = tiny.run_cell(tiny.spec())
    assert result["correct"] is False
