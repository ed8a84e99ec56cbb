"""FLOPs per token of both configurations, against sums worked by hand."""
import json
import pathlib

import pytest

from bench.flops import bert, decoder

CONFIGS = pathlib.Path(__file__).parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


@pytest.mark.parametrize("seq, want", [
    # 6 x (24 x 12,582,912 encoder + 0.15 x 32,303,104 MLM head
    #      + 1,050,624 / seq pooler and NSP) + 12 x 24 x seq x 1024
    (128, 6 * (301_989_888 + 4_845_465.6 + 8_208) + 37_748_736),
    (512, 6 * (301_989_888 + 4_845_465.6 + 2_052) + 150_994_944),
])
def test_bert_large(seq, want):
    assert bert.flops_per_token(model("bert-large"), seq) == pytest.approx(want)


def test_granite_moe_four_layers():
    # per layer: q,k,v,o 6,291,456 + router 61,440 + 8 gated experts
    # 18,874,368; tied head 75,502,080; causal attention 6 x 4096 x 1536
    per_layer = 6_291_456 + 61_440 + 18_874_368
    want = 6 * (4 * per_layer + 75_502_080) + 4 * 37_748_736
    got = decoder.flops_per_token(model("granite-moe-3b-a800m"), 4096)
    assert got == pytest.approx(want)
    assert got == pytest.approx(1_209_461_760)
