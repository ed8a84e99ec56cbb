"""Host time a step the launcher spends calling the compiled step until the
call returns, in ms: the program's span `train.dispatch`, mean over the
steps of the traced run's timed (unprofiled) window (bench/spans.py)."""
from bench import spans


def read(record: dict):
    steps = spans.window_steps(record)
    if steps is None:
        return None
    calls = spans.children(steps, "train.dispatch")
    return 1e3 * sum(s.seconds for s in calls) / len(calls) if calls else None
