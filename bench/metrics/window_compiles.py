"""XLA compiles (or loads from the persistent compilation cache) inside the
run's timed window, as the program counts them from `jax.monitoring`
between the first and the last step of the window; expected 0
(bench/spans.py)."""
from bench import spans


def read(record: dict):
    steps = spans.window_steps(record)
    if steps is None:
        return None
    return len(spans.compiles_between(steps[0].start_ns, steps[-1].end_ns))
