"""The program's own host spans (`repro.metrics.trace`), as the per-layer
readers of a run of the harness see them, and idle gaps of a profile put
down to leaf host spans.

A run of the harness takes `check.STEPS` set-up steps, then its timed
window, then (traced) a profiled window, all through one trainer's
`train_steps`; each step is a span `train.step` carrying its step number,
so the timed window's steps are numbers STEPS to STEPS + window steps - 1.
A program without the spans gives None here, not an error.
"""
from __future__ import annotations

import collections

from bench import trace as btrace

NO_LABEL = ("train.step", "train.setup")   # enclosing spans, never a label


def program_trace():
    """The program's span record module, or None where it has none."""
    try:
        from repro.metrics import trace
    except ImportError:
        return None
    return trace


def window_steps(record: dict):
    """The `train.step` spans of the run's timed window, oldest first, or
    None where the program kept none for it."""
    from bench.check import STEPS

    ptrace = program_trace()
    if ptrace is None:
        return None
    n = record["window"]["steps"]
    steps = [s for s in ptrace.spans("train.step")
             if s.step is not None and STEPS <= s.step < STEPS + n]
    return steps[-n:] if len(steps) >= n else None


def children(steps, name: str) -> list:
    """The spans called `name` directly inside any of `steps`."""
    ptrace = program_trace()
    inside = {id(s) for s in steps}
    return [s for s in ptrace.spans(name)
            if s.parent is not None and id(s.parent) in inside]


def compiles_between(start_ns: int, end_ns: int) -> list:
    """(time ns, span name) of the XLA compiles (or cache loads) the
    program counted between two of its clock readings."""
    ptrace = program_trace()
    return [(t, name) for t, name, kind in ptrace.compiles()
            if kind == "compile" and start_ns <= t <= end_ns]


def leaves(spans) -> list:
    """[start, end, name] of the spans that enclose no other span, less
    the enclosing spans that never label a gap."""
    spans = sorted(([s, e, n] for s, e, n in spans if n not in NO_LABEL),
                   key=lambda x: (x[0], -x[1]))
    out = []
    for i, (s, e, n) in enumerate(spans):
        nxt = spans[i + 1] if i + 1 < len(spans) else None
        if nxt is None or nxt[0] >= e or nxt[1] > e:
            out.append([s, e, n])
    return out


def idle_by_leaf_span(trace: dict) -> dict:
    """{label: idle seconds} over a loaded trace's `bench.window`: each gap
    in which no device op runs goes to the leaf host span that overlaps it
    most, or `bench.trace.NO_SPAN` (mean over the devices)."""
    lo, hi = next((s, s + d) for s, d, n in trace["host"]
                  if n == btrace.WINDOW)
    spans = leaves([(s, s + d, n) for s, d, n in trace["host"]
                    if n != btrace.WINDOW])
    out = collections.Counter()
    for dev in trace["devices"].values():
        covered = btrace.union([o[:2] for o in btrace.clip(dev["ops"], lo, hi)])
        for g in btrace.minus([[lo, hi]], covered):
            best, name = 0, btrace.NO_SPAN
            for s, e, sname in spans:
                over = min(e, g[1]) - max(s, g[0])
                if over > best:
                    best, name = over, sname
            out[name] += (g[1] - g[0]) / 1e9
    n = len(trace["devices"])
    return {k: v / n for k, v in out.items()}
