"""The reduction from a profiler trace to per-layer numbers.

A hand-made trace with worked answers, and traces recorded on the chip,
trimmed to a few tens of milliseconds (bench/tests/data), checked against a
sweep over the event edges that shares no code with bench/trace.py.
"""
import gzip
import json
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data"
MS = 1_000_000


def hand_made():
    return {
        "devices": {"/device:TPU:0": {
            "ops": [[0, 100, "%while.1"], [10, 20, "%fusion.1"],
                    [40, 20, "%fusion.2"], [120, 30, "%fusion.3"],
                    [140, 40, "%all-reduce.1"]],
            "async": [[190, 20, "%all-gather-start.1"],
                      [195, 5, "%copy-start.1"]]}},
        "host": [[0, 250, "bench.window"], [150, 90, "bench.input"],
                 [240, 10, "PjitFunction(jit(step_fn))"]],
    }


def test_hand_made_trace():
    s = trace.summarize(hand_made(), steps=2)
    assert s["window_s"] == 250e-9
    assert s["busy_s"] == pytest.approx(160e-9)      # [0,100] + [120,180]
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["%while.1"] == pytest.approx(60e-9)   # less its two children
    assert ops["%fusion.1"] == pytest.approx(20e-9)
    assert ops["%all-reduce.1"] == pytest.approx(40e-9)
    assert s["collective_s"] == pytest.approx(60e-9)         # 140-180, 190-210
    assert s["collective_exposed_s"] == pytest.approx(50e-9)  # 150-180, 190-210
    gaps = s["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.input", pytest.approx(70e-9)]   # 180-250
    assert gaps[1] == [trace.NO_SPAN, pytest.approx(20e-9)]   # 100-120
    assert s["idle_by_span"]["bench.input"] == pytest.approx(70e-9)


def sweep(intervals):
    """Length covered by at least one interval, by counting edges."""
    edges = sorted([(s, 1) for s, e in intervals] + [(e, -1) for s, e in intervals])
    depth, covered, last = 0, 0, None
    for x, d in edges:
        if depth > 0:
            covered += x - last
        depth, last = depth + d, x
    return covered


def fixtures():
    return sorted(DATA.glob("*.json.gz"))


@pytest.mark.parametrize("path", fixtures(), ids=lambda p: p.name)
def test_chip_trace(path):
    with gzip.open(path, "rt") as f:
        tr = json.load(f)
    s = trace.summarize(tr, steps=1)
    lo, hi = next((a, a + d) for a, d, n in tr["host"] if n == trace.WINDOW)
    n = len(tr["devices"])
    cut = lambda evs: [(max(a, lo), min(a + d, hi)) for a, d, _ in evs
                       if a < hi and a + d > lo]
    busy = sum(sweep(cut(d["ops"])) for d in tr["devices"].values()) / n
    assert s["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    # ops on one device run one at a time, nested in while/cond bodies:
    # self times add up to the busy time
    total_self = sum(t for _, t in s["breakdown"]["device_ops"])
    assert total_self <= s["busy_s"] * (1 + 1e-9)
    idle = sum(t for t in s["idle_by_span"].values())
    assert idle == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-6)
    names = {nm for _, _, nm in tr["host"]} | {trace.NO_SPAN}
    assert {g[0] for g in s["breakdown"]["idle_gaps"]} <= names
    coll = sum(sweep(cut([e for e in d["ops"] + d["async"]
                          if trace.is_collective(e[2])]))
               for d in tr["devices"].values()) / n
    assert s["collective_s"] == pytest.approx(coll / 1e9, rel=1e-9)
    assert 0 <= s["collective_exposed_s"] <= s["collective_s"] + 1e-15


def test_self_times_add_up_to_busy_time():
    with gzip.open(fixtures()[0], "rt") as f:
        tr = json.load(f)
    (dev,) = tr["devices"].values()
    lo, hi = next((a, a + d) for a, d, n in tr["host"] if n == trace.WINDOW)
    ops = trace.clip(dev["ops"], lo, hi)
    assert sum(trace.self_times(ops).values()) == pytest.approx(
        trace.length(trace.union([o[:2] for o in ops])) / 1e9, rel=1e-9)
