"""Seconds the launcher spent making the initial state: the program's span
`train.init` (parameters, their precision cast and the optimizer's init,
until ready on the device), part of `setup_s` (bench/spans.py)."""
from bench import spans


def read(record: dict):
    ptrace = spans.program_trace()
    if ptrace is None:
        return None
    init = ptrace.spans("train.init")
    return init[-1].seconds if init else None
