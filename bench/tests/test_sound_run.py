"""The harness, driven on the CPU at a small size without its look for a
chip, finds the program correct under the phase-1 limits, and prints a
result line the driver can read."""
from bench.tests import tiny


def test_sound_run():
    result = tiny.run_cell(tiny.spec())
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert {"tokens_per_s", "setup_s"} <= set(result["metrics"])
    assert result["attempted"] >= 1 and result["failed"] == 0
