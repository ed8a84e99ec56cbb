"""Host spans and compile counts of the training loop, on one clock.

    from repro.metrics import trace

    with trace.span("train.dispatch") as s:
        out = compiled(params, opt_state, batch)
    s.seconds

Every span is timed with `time.perf_counter_ns` and kept in an in-memory
record of the process: the last `KEEP` closed spans (name, start and end in
ns, the enclosing span, the compiles that ran inside it) and, per name, a
running count, total and compile count. A listener on `jax.monitoring`
counts each XLA compile (`backend_compile_duration`, also fired by a load
from the persistent compilation cache) and each jaxpr trace
(`jaxpr_trace_duration`) against the innermost open span. Spans are opened
by one thread, the training loop's.

The record costs two clock reads and a few list and dict updates a span;
it is always kept, because the launcher's own timings (`Trainer.compile_s`,
the `step_s` of `train_steps`, the per-step fields of the JSONL metrics)
are read from it.

`enable(True)` also writes each span into the JAX profiler's trace, as a
`jax.profiler.TraceAnnotation` (a `StepTraceAnnotation` where the span
carries a step number), so that a profile taken meanwhile shows the host's
spans on the clock the TPU planes share. It is off by default: without a
profiler session the annotations record nothing.

Span names used by the launcher (`repro.launch.train`):

    train.setup       make_trainer
      train.init        parameters, precision cast, optimizer init, ready
      train.data        the batch feed
    train.step        one step of train_steps (step number attached)
      train.input       next() of the batch feed
      train.to_device   the batch's host-to-device copy
      train.compile     the first call's lower and compile
      train.dispatch    the compiled step's call, until it returns
      train.wait        block_until_ready on its outputs
      train.fetch       the loss to the host, and the metrics logger
"""
from __future__ import annotations

import collections
import time
from typing import Optional

import jax

KEEP = 100_000                  # closed spans kept; the oldest drop first
COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"

_profile = False                # enable(): mirror spans into the profiler
_open: list = []                # open spans, innermost last
_closed: collections.deque = collections.deque(maxlen=KEEP)
_totals: dict = {}              # name -> [count, total ns, compiles, traces]
_events: collections.deque = collections.deque(maxlen=KEEP)
_programs: dict = {}            # name -> the latest compiled program


class Span:
    """One timed block of host work; a context manager."""

    __slots__ = ("name", "step", "start_ns", "end_ns", "parent", "compiles",
                 "traces", "_note")

    def __init__(self, name: str, step: Optional[int] = None):
        self.name, self.step = name, step
        self.start_ns = self.end_ns = 0
        self.parent: Optional[Span] = None
        self.compiles = self.traces = 0
        self._note = None

    def __enter__(self) -> "Span":
        if _profile:
            self._note = (jax.profiler.TraceAnnotation(self.name)
                          if self.step is None else
                          jax.profiler.StepTraceAnnotation(
                              self.name, step_num=self.step))
            self._note.__enter__()
        self.parent = _open[-1] if _open else None
        _open.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        _open.pop()
        _closed.append(self)
        total = _totals.setdefault(self.name, [0, 0, 0, 0])
        total[0] += 1
        total[1] += self.end_ns - self.start_ns
        total[2] += self.compiles
        total[3] += self.traces
        if self._note is not None:
            self._note.__exit__(*exc)
            self._note = None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def span(name: str, step: Optional[int] = None) -> Span:
    """A new span `name`; `step` marks a training step for the profiler."""
    return Span(name, step)


def enable(on: bool = True) -> None:
    """Mirror spans into the JAX profiler's trace (on) or not (off)."""
    global _profile
    _profile = bool(on)


def enabled() -> bool:
    return _profile


def spans(name: Optional[str] = None) -> list:
    """Closed spans kept, oldest first; only those called `name` if given."""
    return [s for s in _closed if name is None or s.name == name]


def compiles() -> list:
    """(time ns, innermost open span's name or None, "compile" | "trace")
    of each compile event kept, oldest first."""
    return list(_events)


def summary() -> dict:
    """{name: {count, seconds, compiles, traces}} over every closed span."""
    return {n: {"count": c, "seconds": ns / 1e9, "compiles": k, "traces": t}
            for n, (c, ns, k, t) in _totals.items()}


def keep_program(name: str, compiled) -> None:
    """Keep the latest compiled program under `name`, so that a profile
    taken later can be mapped to its HLO (`program(name).as_text()`)."""
    _programs[name] = compiled


def program(name: str):
    return _programs.get(name)


def reset() -> None:
    """Forget every closed span, total, compile event and program."""
    _closed.clear()
    _totals.clear()
    _events.clear()
    _programs.clear()


def _on_event(event: str, duration: float, **_) -> None:
    if event not in (COMPILE, TRACE):
        return
    inner = _open[-1] if _open else None
    kind = "compile" if event == COMPILE else "trace"
    if inner is not None:
        if kind == "compile":
            inner.compiles += 1
        else:
            inner.traces += 1
    _events.append((time.perf_counter_ns(), inner.name if inner else None,
                    kind))


jax.monitoring.register_event_duration_secs_listener(_on_event)
