"""repro.metrics.trace: spans, their nesting, the compile counter, the
profiler annotations, and the launcher's spans around each step."""
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.launch import train
from repro.metrics import read_metrics, trace


@pytest.fixture(autouse=True)
def fresh():
    trace.reset()
    trace.enable(False)
    yield
    trace.enable(False)
    trace.reset()


def host_events(where) -> set:
    from jax.profiler import ProfileData

    (path,) = pathlib.Path(where).glob("**/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return {e.name for p in pd.planes if p.name == "/host:CPU"
            for ln in p.lines for e in ln.events}


@pytest.mark.parametrize("on", [False, True])
def test_profiler_sees_spans_only_when_on(tmp_path, on):
    trace.enable(on)
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("train.step", step=7):
            with trace.span("train.dispatch"):
                jnp.ones(4).block_until_ready()
    names = host_events(tmp_path)
    assert ("train.dispatch" in names) is on
    assert ("train.step" in names) is on
    # on or off, the record keeps both, timed
    step, = trace.spans("train.step")
    assert step.step == 7 and step.seconds > 0
    assert trace.summary()["train.dispatch"]["count"] == 1


def test_off_opens_no_annotation(monkeypatch):
    opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: opened.append(name))
    with trace.span("train.input"):
        pass
    assert opened == [] and not trace.enabled()


def test_spans_nest_with_their_parents():
    with trace.span("train.step", step=0) as step:
        with trace.span("train.input") as inp:
            pass
        with trace.span("train.wait") as wait:
            with trace.span("inner") as inner:
                pass
    assert step.parent is None
    assert inp.parent is step and wait.parent is step
    assert inner.parent is wait
    assert step.start_ns <= inp.start_ns <= inp.end_ns <= wait.start_ns
    assert inner.end_ns <= wait.end_ns <= step.end_ns
    # closed in the order they ended
    assert [s.name for s in trace.spans()] == [
        "train.input", "inner", "train.wait", "train.step"]
    s = trace.summary()["train.wait"]
    assert s["count"] == 1 and s["seconds"] == pytest.approx(wait.seconds)


def test_record_is_bounded():
    for _ in range(trace.KEEP + 5):
        with trace.span("x"):
            pass
    assert len(trace.spans()) == trace.KEEP
    assert trace.summary()["x"]["count"] == trace.KEEP + 5


def test_compile_counter_counts_fresh_jit_not_cached_call():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.arange(5.0)
    with trace.span("first") as first:
        f(x).block_until_ready()
    with trace.span("again") as again:
        f(x).block_until_ready()
    assert first.compiles == 1 and first.traces >= 1
    assert again.compiles == 0 and again.traces == 0
    kinds = [(name, kind) for _, name, kind in trace.compiles()]
    assert kinds.count(("first", "compile")) == 1
    assert not any(name == "again" for name, _ in kinds)
    assert trace.summary()["first"]["compiles"] == 1


def test_compile_outside_spans_has_no_span():
    jax.jit(lambda x: x - 2.0)(jnp.ones(3)).block_until_ready()
    assert ("compile" in {k for _, n, k in trace.compiles() if n is None})


@pytest.fixture(scope="module")
def bert_args():
    return train.build_parser().parse_args(
        ["--arch", "bert-large", "--batch", "4", "--seq", "16",
         "--precision", "bf16", "--steps", "3"])


def test_train_steps_spans(bert_args):
    tr = train.make_trainer(bert_args)
    for name in ("train.setup", "train.init", "train.data"):
        assert len(trace.spans(name)) == 1
    init, = trace.spans("train.init")
    assert init.parent is trace.spans("train.setup")[0]

    hist = train.train_steps(tr, 3)
    steps = trace.spans("train.step")
    assert [s.step for s in steps] == [0, 1, 2] and tr.step == 3
    for name in ("train.dispatch", "train.wait", "train.fetch"):
        got = trace.spans(name)
        assert [s.parent for s in got] == steps, name
    # input staged one step ahead: one batch a step, batch 0 inside step 0
    # before its compile and dispatch, batch i+1 inside step i between its
    # dispatch and its wait
    dispatch, wait = trace.spans("train.dispatch"), trace.spans("train.wait")
    compile_span, = trace.spans("train.compile")
    for name in ("train.input", "train.to_device"):
        got = trace.spans(name)
        assert [s.parent for s in got] == [steps[0]] + steps[:-1], name
        assert got[0].end_ns <= compile_span.start_ns, name
        for s, d, w in zip(got[1:], dispatch, wait):
            assert d.end_ns <= s.start_ns <= s.end_ns <= w.start_ns, name
    assert compile_span.parent is steps[0]
    assert tr.compile_s == compile_span.seconds
    assert compile_span.compiles == 1
    assert trace.program("train.step") is tr.compiled
    dispatch, wait = trace.spans("train.dispatch"), trace.spans("train.wait")
    assert hist["step_s"] == [d.seconds + w.seconds
                              for d, w in zip(dispatch, wait)]

    train.train_steps(tr, 2)                  # compiled already: no compile
    assert len(trace.spans("train.compile")) == 1
    assert [s.step for s in trace.spans("train.step")] == [0, 1, 2, 3, 4]
    # the new call stages its own two batches, both inside its first step
    step3 = trace.spans("train.step")[3]
    for name in ("train.input", "train.to_device"):
        assert [s.parent for s in trace.spans(name)[3:]] == [step3, step3]
    assert sum(s.compiles for s in trace.spans("train.step")[3:]) == 0


def test_metrics_jsonl_carries_step_spans(tmp_path, monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    path = tmp_path / "m.jsonl"
    train.main(["--arch", "bert-large", "--batch", "4", "--seq", "16",
                "--steps", "2", "--log-every", "1", "--metrics", str(path)])
    assert trace.enabled()
    lines = list(read_metrics(str(path)))
    assert [r["step"] for r in lines] == [1, 2]
    waits = [s.seconds for s in trace.spans("train.wait")]
    for row, wait in zip(lines, waits):
        assert {"input_ms", "dispatch_ms", "wait_ms"} <= set(row)
        assert row["wait_ms"] == pytest.approx(1e3 * wait)
    json.dumps(lines)
