"""A data-parallel step whose chips skip the gradient exchange updates each
chip from its own rows alone; chip 0's copy, which the harness reads, is
then the step over the first quarter of a four-chip batch. Not correct."""
from bench.tests import tiny


def test_no_exchange(monkeypatch):
    tiny.break_step(monkeypatch, lambda step: lambda p, o, b: step(
        p, o, tiny.rows(b, 0.25)))
    result = tiny.run_cell(tiny.spec())
    assert result["correct"] is False
