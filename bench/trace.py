"""From a `jax.profiler` trace to the per-layer numbers of a traced run.

`load` reads the `.xplane.pb` that `jax.profiler.trace` writes into plain
lists (times in ns on the profile's clock, which the TPU planes share with
the host's):

    {"devices": {"/device:TPU:0": {"ops": [[start, duration, name], ...],
                                   "async": [[start, duration, name], ...]}},
     "host": [[start, duration, name], ...]}

`ops` are the events of the device's "XLA Ops" line, named by their HLO
instruction ("%fusion.12"); a `while` or `cond` event encloses the events
of its body. `async` are the "Async XLA Ops" (start to done of async copies
and collectives). `host` are the named spans of the thread that opened the
harness's `bench.window` span, Python's own function events left out.

`summarize` reduces that, over the `bench.window` span, to:

- busy: the union of the device's op intervals; idle = window - busy;
- self time per op: duration less its enclosed ops', summed by name;
- collective time: the union of the intervals of all-reduce, reduce-scatter,
  all-gather, all-to-all and collective-permute ops, and the part of it
  during which no other op of that device runs (exposed);
- the idle gaps, each labelled with the host span (other than the window)
  that overlaps it most, or "host, no span".

Every device number is the mean over the devices traced.
"""
from __future__ import annotations

import collections
import pathlib

COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
               "collective-permute")
WINDOW = "bench.window"
NO_SPAN = "host, no span"


def latest(where) -> pathlib.Path:
    found = sorted(pathlib.Path(where).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {where}")
    return found[-1]


def load(path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                key: [[e.start_ns, e.duration_ns, e.name.split(" = ")[0]]
                      for e in lines[line].events] if line in lines else []
                for key, line in (("ops", "XLA Ops"),
                                  ("async", "Async XLA Ops"))}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = [[e.start_ns, e.duration_ns, e.name]
                          for e in line.events if not e.name.startswith("$")]
                if any(e[2] == WINDOW for e in events):
                    host = events
    return {"devices": devices, "host": host}


def union(intervals) -> list:
    """Sorted disjoint [start, end] covering the given [start, end]s."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def minus(a, b) -> list:
    """Disjoint sorted intervals a less disjoint sorted intervals b."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(events, lo, hi) -> list:
    """[start, end, name] of the events, cut to [lo, hi]."""
    return [[max(s, lo), min(s + d, hi), n] for s, d, n in events
            if s < hi and s + d > lo]


def self_times(ops) -> dict:
    """Seconds per op name, each op less the ops it encloses."""
    out = collections.Counter()
    stack = []                       # [end, name, own time] of open ops
    for s, e, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            end, name, own = stack.pop()
            out[name] += own
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, n, e - s])
    for end, name, own in stack:
        out[name] += own
    return {n: t / 1e9 for n, t in out.items()}


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def summarize(trace: dict, steps: int) -> dict:
    windows = [(s, s + d) for s, d, n in trace["host"] if n == WINDOW]
    if not windows or not trace["devices"]:
        raise ValueError("the trace has no bench.window span or no device")
    lo, hi = windows[0]
    spans = [(s, s + d, n) for s, d, n in trace["host"] if n != WINDOW]
    busy, coll, exposed = 0.0, 0.0, 0.0
    per_op = collections.Counter()
    gaps = []
    for dev in trace["devices"].values():
        ops = clip(dev["ops"], lo, hi)
        covered = union([o[:2] for o in ops])
        busy += length(covered)
        per_op.update(self_times(ops))
        comm = union([o[:2] for o in ops + clip(dev["async"], lo, hi)
                      if is_collective(o[2])])
        compute = union([o[:2] for o in ops if not is_collective(o[2])])
        coll += length(comm)
        exposed += length(minus(comm, compute))
        gaps += minus([[lo, hi]], covered)
    n = len(trace["devices"])

    def label(gap):
        best, name = 0.0, NO_SPAN
        for s, e, sname in spans:
            over = min(e, gap[1]) - max(s, gap[0])
            if over > best:
                best, name = over, sname
        return name

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    by_label = collections.Counter()
    for g in gaps:
        by_label[label(g)] += (g[1] - g[0]) / 1e9 / n
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9 / n,
        "steps": steps,
        "collective_s": coll / 1e9 / n,
        "collective_exposed_s": exposed / 1e9 / n,
        "idle_by_span": dict(by_label),
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in per_op.most_common(10)],
            "idle_gaps": [[label(g), (g[1] - g[0]) / 1e9] for g in longest],
        },
    }


def reduce(path, steps: int) -> dict:
    return summarize(load(path), steps)
