"""A train step that returns its state unchanged is not correct."""
from bench.tests import tiny


def test_state_unchanged(monkeypatch):
    tiny.break_step(monkeypatch, lambda step: lambda p, o, b: (
        p, o, step(p, o, b)[2]))
    result = tiny.run_cell(tiny.spec())
    assert result["correct"] is False
    assert result["checks"]["update"]["value"] >= 1.0
