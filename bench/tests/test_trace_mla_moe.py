"""The Moonlight cell's readers: the latent-projection ops found by scope in
a hand-made step and in the program's own, and the two metrics read from
times given by hand."""
import json
import types

import pytest

from bench import mla_ops, run, scopes
from bench.tests import test_scopes

# A step whose latent projections are a fusion without an op_name, two of
# whose three instructions are in `mla.latent`, and a product with one; the
# attention kernel's three calls; a fusion of `attention` outside the latent.
LATENT_HLO = """HloModule jit_step_fn

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %a = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step_fn)/jvp(layers)/while/body/attention/mla.latent/mul"}
  %b = f32[4]{0} rsqrt(%a), metadata={op_name="jit(step_fn)/jvp(layers)/while/body/attention/mla.latent/rsqrt"}
  ROOT %c = f32[4]{0} add(%b, %b), metadata={op_name="jit(step_fn)/jvp(layers)/while/body/attention/add"}
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %convolution.3 = f32[4]{0} convolution(%fusion.1, %fusion.1), metadata={op_name="jit(step_fn)/transpose(jvp(layers))/while/body/checkpoint/attention/mla.latent/dot_general"}
  %flash_attention_fwd.3 = f32[4]{0} custom-call(%convolution.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jvp(layers)/while/body/attention/pallas_call"}
  ROOT %fusion.2 = f32[4]{0} fusion(%flash_attention_fwd.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/jvp(layers)/while/body/attention/mul"}
}
"""


def moonlight_record(**kw):
    config = json.loads((run.BENCH / "configs" / "moonlight-16b-a3b.json")
                        .read_text())
    base = {"cell": "moonlight-16b-a3b.pretrain-8k", "config": config,
            "traffic": {"seq": 8192, "per_chip_batch": 1},
            "device_kind": "TPU v5 lite", "chips": 1, "trace": {"steps": 2}}
    return dict(base, **kw)


def reader(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py")


def test_latent_ops_of_a_hand_made_step():
    # the fusion by its computation's majority, and the fused instructions
    # themselves (which no trace event names)
    assert mla_ops.latent_ops(LATENT_HLO) == {"fusion.1", "convolution.3",
                                              "a", "b"}


def test_mla_readers(monkeypatch):
    from repro.metrics import trace as ptrace

    times = {"%fusion.1": 0.004, "%convolution.3": 0.002, "%fusion.2": 0.010,
             "%flash_attention_fwd.3": 0.1, "flash_attention_bwd_dkv.3": 0.2,
             "%flash_attention_bwd_dq.3": 0.1, "%copy.4": 0.001}
    monkeypatch.setattr(mla_ops, "window_times", lambda rec: times)
    monkeypatch.setattr(ptrace, "program", lambda name: types.SimpleNamespace(
        as_text=lambda: LATENT_HLO))
    rec = moonlight_record()
    # 6 ms of latent ops over 2 steps
    assert reader("mla_latent_ms").read(rec) == pytest.approx(3.0)
    # 7,902,739,824,640 FLOPs a step at 197 TFLOP/s over 0.2 s a step
    assert reader("mla_flash_roofline").read(rec) == pytest.approx(
        100 * 7_902_739_824_640 / 197e12 / 0.2)


def test_mla_readers_read_nothing_without_their_ops(monkeypatch):
    assert mla_ops.window_times(moonlight_record(trace=None)) is None
    for name in ("mla_latent_ms", "mla_flash_roofline"):
        assert reader(name).read(moonlight_record(trace=None)) is None
    monkeypatch.setattr(mla_ops, "window_times",
                        lambda rec: {"%fusion.2": 0.010})
    assert reader("mla_flash_roofline").read(moonlight_record()) is None


def test_latent_ops_of_the_program_step():
    """The program's step (moonlight-16b-a3b reduced, on the CPU) has ops
    in `mla.latent` in every pass, all of them booked as `attention` by
    the scope split, and its shared experts' ops booked as `ffn`."""
    from repro.launch import train
    from repro.metrics import trace as ptrace

    args = train.build_parser().parse_args(
        ["--arch", "moonlight-16b-a3b", "--batch", "2", "--seq", "16",
         "--precision", "bf16"])
    tr = train.make_trainer(args)
    train.train_steps(tr, 1)
    hlo = ptrace.program("train.step").as_text()
    latent = mla_ops.latent_ops(hlo)
    booked = scopes.scope_map(hlo)
    assert {booked[op][0] for op in latent} == {"attention"}
    assert {"forward", "recompute", "backward"} <= {booked[op][1]
                                                   for op in latent}
    op_names, _, _ = scopes.parse(hlo)
    shared = [op for op, n in op_names.items() if "/moe.shared/" in n]
    assert shared and {booked[op][0] for op in shared} == {"ffn"}


def test_moonlight_step_carries_every_scope():
    # the scope test of the other cells, on the Moonlight step
    test_scopes.test_program_step_carries_every_scope(
        "moonlight-16b-a3b",
        {"embed", "layers", "attention", "ffn", "moe.router", "moe.dispatch",
         "moe.experts", "moe.combine", "head", "loss", "optimizer",
         "grad_norm"})
