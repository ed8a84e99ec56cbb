"""Device time of a traced window split by the program's named scopes.

The program wraps parts of its train step in `jax.named_scope` (`embed`,
`layers`, `attention`, `ffn`, `moe.router`, `moe.dispatch`, `moe.experts`,
`moe.combine`, `head`, `loss`, `optimizer`, `grad_norm`). The scopes reach
each HLO instruction's `op_name` metadata, wrapped by the pass that made
the instruction: `jvp(...)` in the forward pass, `transpose(jvp(...))` in
the backward pass, and `rematted_computation` where `jax.checkpoint`
recomputes the forward pass for the backward one. `scope_map` reads that
from the compiled step's HLO text; `split` sums the self time of each device
op of a traced window (`bench.trace.self_times`, every op) by the innermost
scope and the pass of the instruction of that name. Ops of no scope are the
unscoped remainder; scoped time and the remainder add up to the busy self
time.

A fusion is mapped by its own `op_name`; where that is empty, by the scope
and pass that most of the scoped instructions of its fused computation
carry, nested fusions mapped the same way.
"""
from __future__ import annotations

import collections
import re
import sys
import traceback

SCOPES = ("embed", "layers", "attention", "ffn", "moe.router", "moe.dispatch",
          "moe.experts", "moe.combine", "head", "loss", "optimizer",
          "grad_norm")
PASSES = ("forward", "recompute", "backward", "other")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w.\-]*\((.*)\)$")


def bare(name: str) -> str:
    """The instruction name as a trace event or the HLO text gives it."""
    return name.lstrip("%")


def unwrap(part: str) -> str:
    """`transpose(jvp(layers))` -> `layers`."""
    while True:
        m = _WRAPPED.match(part)
        if not m:
            return part
        part = m.group(1)


def classify(op_name: str) -> tuple:
    """(innermost scope or None, pass) of one `op_name`."""
    parts = op_name.split(";")[0].split("/")
    scope = None
    for part in parts:
        name = unwrap(part)
        if name in SCOPES:
            scope = name
    if "rematted_computation" in parts:
        kind = "recompute"
    elif any(p.startswith("transpose(") for p in parts):
        kind = "backward"
    elif any(p.startswith("jvp(") for p in parts):
        kind = "forward"
    else:
        kind = "other"
    return scope, kind


def parse(hlo: str) -> tuple:
    """({instruction: op_name}, {instruction: called computation},
    {computation: [instruction, ...]}) of an HLO module's text."""
    op_names, calls, members = {}, {}, collections.defaultdict(list)
    computation = None
    for line in hlo.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name = m.group(1)
        members[computation].append(name)
        o = _OP_NAME.search(line)
        op_names[name] = o.group(1) if o else ""
        k = _CALLS.search(line)
        if k is not None:
            calls[name] = k.group(1)
    return op_names, calls, members


def scope_map(hlo: str) -> dict:
    """{instruction name: (scope or None, pass)} for every instruction."""
    op_names, calls, members = parse(hlo)
    out = {}

    def of(name: str, depth: int = 0) -> tuple:
        if name in out:
            return out[name]
        if op_names.get(name) or name not in calls or depth > 8:
            got = classify(op_names.get(name, ""))
        else:
            votes = collections.Counter(
                got for got in (of(inner, depth + 1)
                                for inner in members[calls[name]])
                if got[0] is not None)
            got = votes.most_common(1)[0][0] if votes else (None, "other")
        out[name] = got
        return got

    for name in op_names:
        of(name)
    return out


def split(self_times: dict, scopes: dict, steps: int) -> dict:
    """Seconds a step by scope and pass, and the unscoped remainder."""
    table = collections.defaultdict(lambda: dict.fromkeys(PASSES, 0.0))
    unscoped = collections.Counter()
    for op, seconds in self_times.items():
        scope, kind = scopes.get(bare(op), (None, "other"))
        if scope is None:
            unscoped[bare(op)] += seconds / steps
        else:
            table[scope][kind] += seconds / steps
    busy = sum(self_times.values()) / steps
    return {"steps": steps, "busy_self_s": busy,
            "scopes": {s: dict(t) for s, t in table.items()},
            "unscoped_s": sum(unscoped.values()),
            "unscoped_top": unscoped.most_common(10)}


def scoped_seconds(table: dict, *names: str) -> float:
    """Seconds a step under any of `names`, all passes."""
    return sum(sum(table["scopes"].get(n, {}).values()) for n in names)


def window_self_times(trace: dict) -> tuple:
    """({op: self seconds summed over the devices} / device count, the
    window's seconds) over the `bench.window` span of a loaded trace."""
    from bench import trace as btrace

    lo, hi = next((s, s + d) for s, d, n in trace["host"]
                  if n == btrace.WINDOW)
    total = collections.Counter()
    for dev in trace["devices"].values():
        total.update(btrace.self_times(btrace.clip(dev["ops"], lo, hi)))
    n = len(trace["devices"])
    return {k: v / n for k, v in total.items()}, (hi - lo) / 1e9


_cache: dict = {}


def of_record(record: dict):
    """The scope table of a traced run of the harness, or None where the
    run has no trace or the program keeps no compiled step (a program
    without named scopes then has every op unscoped)."""
    if record.get("trace") is None:
        return None
    try:
        from repro.metrics import trace as ptrace
    except ImportError:
        return None
    compiled = ptrace.program("train.step")
    if compiled is None:
        return None
    from bench import run
    from bench import trace as btrace

    try:
        path = btrace.latest(run.OUT / "trace" / record["cell"])
        key = (str(path), path.stat().st_mtime_ns, id(compiled))
        if key not in _cache:
            times, _ = window_self_times(btrace.load(path))
            _cache.clear()
            _cache[key] = split(times, scope_map(compiled.as_text()),
                                record["trace"]["steps"])
    except Exception:               # a reader must not end the run
        traceback.print_exc(file=sys.stderr)
        return None
    return _cache[key]
