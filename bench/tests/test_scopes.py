"""Device time by named scope (bench/scopes.py), the program's span readers
(bench/spans.py, bench/metrics/), the least bytes of a LANS update
(bench/flops/lans_bytes.py), and gaps put down to leaf host spans."""
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import pytest

from bench import check, run, scopes, spans
from bench import trace as btrace
from bench.flops import lans_bytes
from bench.tests.test_trace import hand_made

CONFIGS = pathlib.Path(__file__).parents[1] / "configs"


def reader(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py")


@pytest.mark.parametrize("op_name, want", [
    ("jit(step_fn)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/dot_general", ("attention", "recompute")),
    ("jit(step_fn)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "ffn/mul", ("ffn", "backward")),
    ("jit(step_fn)/jvp(layers)/while/body/closed_call/moe.dispatch/"
     "scatter-add", ("moe.dispatch", "forward")),
    ("jit(step_fn)/jvp(embed)/jit(_take)/gather", ("embed", "forward")),
    ("jit(step_fn)/optimizer/cond/branch_1_fun/sqrt", ("optimizer", "other")),
    ("jit(step_fn)/transpose(jvp(loss))/mul;jit(step_fn)/transpose(jvp(loss))"
     "/broadcast_in_dim", ("loss", "backward")),
    ("jit(step_fn)/jvp(layers)/while/body/add", ("layers", "forward")),
    ("jit(step_fn)/jvp()/add", (None, "forward")),
    ("", (None, "other")),
])
def test_classify(op_name, want):
    assert scopes.classify(op_name) == want


HLO = """HloModule jit_step_fn

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %a = f32[4]{0} exponential(%param_0), metadata={op_name="jit(step_fn)/jvp(layers)/while/body/attention/exp"}
  %b = f32[4]{0} add(%a, %a), metadata={op_name="jit(step_fn)/jvp(layers)/while/body/attention/add"}
  ROOT %c = f32[4]{0} multiply(%b, %b), metadata={op_name="jit(step_fn)/jvp(layers)/while/body/ffn/mul"}
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  ROOT %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/transpose(jvp(head))/mul"}
}
"""


def test_fusion_without_op_name_takes_its_computations_majority():
    m = scopes.scope_map(HLO)
    assert m["fusion.1"] == ("attention", "forward")      # 2 of 3 inside
    assert m["fusion.2"] == ("head", "backward")          # its own op_name
    assert m["Arg_0.1"] == (None, "other")


# A scatter as the TPU compiler fuses it: the outer fusion and two of its
# three nested fusions carry no op_name; the scatter's operands do.
NESTED = """HloModule jit_step_fn

%zeros (p0: bf16[]) -> bf16[8,4] {
  %p0 = bf16[] parameter(0)
  ROOT %broadcast.1 = bf16[8,4]{1,0} broadcast(%p0), dimensions={}
}

%rows (p1: bf16[6,4]) -> bf16[6,4] {
  ROOT %p1 = bf16[6,4]{1,0} parameter(0)
}

%scatter_rows (a: bf16[8,4], i: s32[6], u: bf16[6,4]) -> bf16[8,4] {
  %a = bf16[8,4]{1,0} parameter(0)
  %i = s32[6]{0} parameter(1)
  %u = bf16[6,4]{1,0} parameter(2)
  %reshape.1 = bf16[6,4]{1,0} reshape(%u), metadata={op_name="jit(step_fn)/jvp(layers)/while/body/moe.dispatch/broadcast_in_dim"}
  ROOT %scatter.1 = bf16[8,4]{1,0} scatter(%a, %i, %reshape.1), to_apply=%add
}

%outer (q0: bf16[], q1: s32[6], q2: bf16[6,4]) -> bf16[8,4] {
  %q0 = bf16[] parameter(0)
  %q1 = s32[6]{0} parameter(1)
  %q2 = bf16[6,4]{1,0} parameter(2)
  %fusion.11 = bf16[8,4]{1,0} fusion(%q0), kind=kLoop, calls=%zeros
  %fusion.12 = bf16[6,4]{1,0} fusion(%q2), kind=kLoop, calls=%rows
  ROOT %fusion.13 = bf16[8,4]{1,0} fusion(%fusion.11, %q1, %fusion.12), kind=kCustom, calls=%scatter_rows
}

ENTRY %main.2 (x: bf16[], y: s32[6], z: bf16[6,4]) -> bf16[8,4] {
  %x = bf16[] parameter(0)
  %y = s32[6]{0} parameter(1)
  %z = bf16[6,4]{1,0} parameter(2)
  ROOT %fusion.10 = bf16[8,4]{1,0} fusion(%x, %y, %z), kind=kCustom, calls=%outer
}
"""


def test_nested_fusions_vote_only_with_scoped_instructions():
    m = scopes.scope_map(NESTED)
    assert m["fusion.11"] == m["fusion.12"] == (None, "other")
    assert m["fusion.13"] == ("moe.dispatch", "forward")
    assert m["fusion.10"] == ("moe.dispatch", "forward")


def toy_step():
    def layer(x, w):
        with jax.named_scope("attention"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("ffn"):
            h = h * jax.nn.gelu(h)
        return x + h, None

    def loss(params, x):
        with jax.named_scope("embed"):
            x = x * params["e"]
        with jax.named_scope("layers"):
            x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["w"])
        with jax.named_scope("loss"):
            return jnp.mean(x ** 2)

    def step(params, x):
        value, grads = jax.value_and_grad(loss)(params, x)
        with jax.named_scope("optimizer"):
            params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
        return params, value

    params = {"e": jnp.ones((8,)), "w": jnp.full((3, 8, 8), 0.1)}
    return jax.jit(step).lower(params, jnp.ones((4, 8))).compile()


def test_toy_step_under_scan_and_checkpoint_separates_passes():
    found = set(scopes.scope_map(toy_step().as_text()).values())
    for kind in ("forward", "recompute", "backward"):
        assert ("attention", kind) in found
        assert ("ffn", kind) in found
    assert {("embed", "forward"), ("embed", "backward"), ("loss", "forward"),
            ("loss", "backward"), ("optimizer", "other")} <= found


@pytest.mark.parametrize("arch, want", [
    ("bert-large", {"embed", "layers", "attention", "ffn", "head", "loss",
                    "optimizer", "grad_norm"}),
    ("granite-moe-3b-a800m", {"embed", "layers", "attention", "moe.router",
                              "moe.dispatch", "moe.experts", "moe.combine",
                              "head", "loss", "optimizer", "grad_norm"}),
])
def test_program_step_carries_every_scope(arch, want):
    from repro.launch import train
    from repro.metrics import trace

    args = train.build_parser().parse_args(
        ["--arch", arch, "--batch", "2", "--seq", "16", "--precision", "bf16"])
    tr = train.make_trainer(args)
    train.train_steps(tr, 1)
    found = set(scopes.scope_map(trace.program("train.step").as_text())
                .values())
    assert want <= {s for s, _ in found}
    passes = {s: {k for t, k in found if t == s} for s in want}
    assert {"forward", "recompute", "backward"} <= passes["attention"]
    assert passes["optimizer"] == {"other"}
    if "moe.dispatch" in want:
        for s in ("moe.dispatch", "moe.combine"):
            assert {"forward", "recompute", "backward"} <= passes[s]


def scoped_trace():
    """Two steps: a while enclosing two fusions, and ops after it."""
    return {
        "devices": {"/device:TPU:0": {
            "ops": [[0, 400, "%while.1"], [10, 100, "%fusion.1"],
                    [200, 100, "%fusion.2"], [500, 100, "%fusion.3"],
                    [700, 50, "%copy.4"], [800, 40, "%fusion.5"]],
            "async": []}},
        "host": [[0, 1000, "bench.window"]],
    }


SCOPE_MAP = {"while.1": ("layers", "forward"),
             "fusion.1": ("attention", "forward"),
             "fusion.2": ("attention", "backward"),
             "fusion.3": ("optimizer", "other"),
             "fusion.5": ("moe.dispatch", "recompute")}   # copy.4: none


def test_split_of_a_hand_made_trace():
    times, window = scopes.window_self_times(scoped_trace())
    assert window == 1000e-9
    table = scopes.split(times, SCOPE_MAP, steps=2)
    # self times: while 400 - 200 = 200, fusions 100 each, copy 50, 40
    assert table["scopes"]["layers"]["forward"] == pytest.approx(100e-9)
    assert table["scopes"]["attention"] == pytest.approx(
        {"forward": 50e-9, "recompute": 0.0, "backward": 50e-9, "other": 0.0})
    assert table["scopes"]["optimizer"]["other"] == pytest.approx(50e-9)
    assert table["unscoped_s"] == pytest.approx(25e-9)
    assert table["unscoped_top"] == [("copy.4", pytest.approx(25e-9))]
    assert scopes.scoped_seconds(table, "attention") == pytest.approx(100e-9)
    # scoped + unscoped = busy self time = busy union / steps
    busy = 400 + 100 + 50 + 40
    scoped = sum(scopes.scoped_seconds(table, s) for s in table["scopes"])
    assert scoped + table["unscoped_s"] == pytest.approx(busy / 2 * 1e-9)
    assert table["busy_self_s"] == pytest.approx(busy / 2 * 1e-9)


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_lans_bytes_bert_large_by_hand():
    # matrices: embeddings (30522 + 512 + 2) x 1024 = 31,780,864; 24 layers
    # of 4 x 1024^2 + 2 x 1024 x 4096 = 301,989,888; MLM transform and
    # pooler 1024^2 each, NSP 2,048: 335,869,952 with a float32 master.
    # float32 vectors: embedding LN 2,048; 24 x (8 x 1024 + 4096) = 294,912
    # (q, k, v biases, two LNs, two MLP biases); MLM bias 1,024, LN 2,048,
    # output bias 30,522; pooler 1,024, NSP 2: 331,580.
    assert lans_bytes.bert_params(config("bert-large")["model"]) == (
        335_869_952, 331_580)
    least = lans_bytes.least_bytes(config("bert-large"))
    assert least == 335_869_952 * 22 + 331_580 * 20 == 7_395_770_544
    assert least / 819e9 == pytest.approx(9.03e-3, rel=1e-3)


def test_lans_bytes_granite_by_hand():
    # embedding 49155 x 1536; 4 layers of q, o 1536^2, k, v 1536 x 512,
    # router 1536 x 40, 40 gated experts 3 x 1536 x 512; 9 norms of 1536
    per_layer = 2 * 1536 ** 2 + 2 * 1536 * 512 + 1536 * 40 + 40 * 3 * 1536 * 512
    matrices = 49155 * 1536 + 4 * per_layer
    assert lans_bytes.decoder_params(
        config("granite-moe-3b-a800m")["model"]) == (matrices, 9 * 1536)
    assert matrices + 9 * 1536 == 478_414_848
    assert lans_bytes.least_bytes(config("granite-moe-3b-a800m")) == (
        matrices * 22 + 9 * 1536 * 20)


@pytest.mark.parametrize("arch", ["bert-large", "granite-moe-3b-a800m"])
def test_lans_bytes_match_the_programs_tree(arch):
    """At a CPU size, the count's split of parameters into those with a
    master and those kept float32 is the program's own."""
    import dataclasses

    from repro import precision
    from repro.configs import reduced_arch
    from repro.precision.policy import tree_paths

    a = reduced_arch(arch)
    policy = precision.get_policy("bf16")
    shapes = jax.eval_shape(a.init, jax.random.PRNGKey(0))
    master = vector = 0
    for leaf, path in zip(jax.tree.leaves(shapes),
                          jax.tree.leaves(tree_paths(shapes))):
        if policy.leaf_dtype(path) == jnp.float32:
            vector += leaf.size
        else:
            master += leaf.size
    model = dataclasses.asdict(a.cfg)
    if a.kind == "decoder":
        model["head_dim"] = a.cfg.resolved_head_dim
        count = lans_bytes.decoder_params(model)
    else:
        count = lans_bytes.bert_params(model)
    assert count == (master, vector)


def table(**per_scope):
    return {"steps": 2, "busy_self_s": 1.0, "unscoped_s": 0.0,
            "unscoped_top": [],
            "scopes": {s: dict(dict.fromkeys(scopes.PASSES, 0.0), **p)
                       for s, p in per_scope.items()}}


def record(**kw):
    base = {"cell": "bert-large.phase1", "config": config("bert-large"),
            "device_kind": "TPU v5 lite", "chips": 1,
            "window": {"steps": 4, "seconds": 1.0, "input_s": 0.1},
            "trace": {"steps": 2}}
    return dict(base, **kw)


def test_scope_readers(monkeypatch):
    t = table(optimizer={"other": 0.018},
              attention={"forward": 0.002, "recompute": 0.002,
                         "backward": 0.004},
              **{"moe.dispatch": {"forward": 0.001, "backward": 0.002},
                 "moe.combine": {"recompute": 0.0005}})
    monkeypatch.setattr(scopes, "of_record", lambda rec: t)
    rec = record()
    assert reader("update_ms").read(rec) == pytest.approx(18.0)
    assert reader("attention_ms").read(rec) == pytest.approx(8.0)
    assert reader("moe_dispatch_combine_ms").read(rec) == pytest.approx(3.5)
    # 7,395,770,544 bytes at 819 GB/s is 9.0302 ms; over 18 ms
    assert reader("lans_update_roofline").read(rec) == pytest.approx(
        100 * 7_395_770_544 / 819e9 / 0.018)


def test_scope_readers_read_nothing_without_scopes(monkeypatch):
    monkeypatch.setattr(scopes, "of_record", lambda rec: table())
    for name in ("update_ms", "attention_ms", "moe_dispatch_combine_ms",
                 "lans_update_roofline"):
        assert reader(name).read(record()) is None
    monkeypatch.undo()
    assert scopes.of_record(record(trace=None)) is None


def test_scope_table_of_a_run_without_its_profile_is_none():
    from repro.metrics import trace

    trace.keep_program("train.step", object())
    try:
        assert scopes.of_record(record(cell="no-such-cell")) is None
    finally:
        trace.reset()


def span(name, start_ms, end_ms, parent=None, step=None):
    from repro.metrics.trace import Span

    s = Span(name, step)
    s.start_ns, s.end_ns = int(start_ms * 1e6), int(end_ms * 1e6)
    s.parent = parent
    return s


def fake_program(n_steps=4):
    """Set-up, a timed window of n_steps and two profiled steps, 100 ms
    each, dispatch 2 ms + step number / 10 ms, one compile in the window."""
    kept = [span("train.init", 0, 1500)]
    for i in range(check.STEPS + n_steps + 2):
        step = span("train.step", 2000 + 100 * i, 2100 + 100 * i, step=i)
        kept += [span("train.dispatch", 2010 + 100 * i,
                      2012 + 100 * i + i / 10, parent=step), step]
    t = lambda ms: int(ms * 1e6)
    events = [(t(1000), "train.init", "compile"),      # set-up
              (t(2050), "train.compile", "compile"),   # step 0
              (t(2000 + 100 * (check.STEPS + 1) + 50), "train.wait",
               "compile"),                             # in the window
              (t(2000 + 100 * (check.STEPS + 1) + 60), "train.wait",
               "trace"),
              (t(2000 + 100 * (check.STEPS + n_steps) + 50), None,
               "compile")]                             # profiled window
    return types.SimpleNamespace(
        spans=lambda name=None: [s for s in kept
                                 if name is None or s.name == name],
        compiles=lambda: events)


def test_span_readers(monkeypatch):
    program = fake_program()
    monkeypatch.setattr(spans, "program_trace", lambda: program)
    rec = record()
    steps = spans.window_steps(rec)
    assert [s.step for s in steps] == [3, 4, 5, 6]
    # dispatch of steps 3..6: 2.3, 2.4, 2.5, 2.6 ms
    assert reader("dispatch_ms_per_step").read(rec) == pytest.approx(2.45)
    assert reader("init_state_s").read(rec) == pytest.approx(1.5)
    assert reader("window_compiles").read(rec) == 1


def test_span_readers_read_nothing_without_spans(monkeypatch):
    monkeypatch.setattr(spans, "program_trace", lambda: None)
    for name in ("dispatch_ms_per_step", "init_state_s", "window_compiles"):
        assert reader(name).read(record()) is None
    program = fake_program(0)
    monkeypatch.setattr(spans, "program_trace", lambda: program)
    # the program kept fewer window steps than the run took
    assert reader("dispatch_ms_per_step").read(record()) is None


def test_gaps_go_to_leaf_spans():
    plain = hand_made()
    assert spans.idle_by_leaf_span(plain) == pytest.approx(
        btrace.summarize(plain, steps=2)["idle_by_span"])
    # the program's spans around the harness's: a step enclosing all, the
    # input phase around bench.input, dispatch around JAX's own span
    nested = hand_made()
    nested["host"] += [[0, 250, "train.step"], [140, 102, "train.input"],
                       [238, 12, "train.dispatch"]]
    got = spans.idle_by_leaf_span(nested)
    assert got == pytest.approx({"bench.input": 70e-9,
                                 btrace.NO_SPAN: 20e-9})


def test_leaves():
    got = spans.leaves([[0, 10, "train.step"], [1, 4, "a"], [2, 3, "b"],
                        [5, 9, "c"], [9, 9, "d"]])
    assert got == [[2, 3, "b"], [5, 9, "c"], [9, 9, "d"]]
