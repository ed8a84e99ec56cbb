"""FLOPs of the Moonlight configuration, against sums worked by hand."""
import pytest

from bench.flops import mla_moe
from bench.tests.test_flops import model


def test_moonlight_first_stage():
    # MLA a layer: q 6,291,456 + latent 1,179,648 + W_kv_b 2,097,152 + out
    # 4,194,304; dense MLP 69,206,016; an expert layer: router 131,072,
    # shared 17,301,504, 0.75 of an expert 6,488,064; head 41,943,040;
    # causal attention 3 x 8192 x 16 x (192 + 128) a layer; 5 layers
    mla, dense, experts = 13_762_560, 69_206_016, 23_920_640
    params = 5 * mla + dense + 4 * experts + 41_943_040
    want = 6 * params + 5 * 125_829_120
    got = mla_moe.flops_per_token(model("moonlight-16b-a3b"), 8192)
    assert got == pytest.approx(want)
    assert got == pytest.approx(2_283_012_096)


def test_moonlight_attention_kernel():
    # 5 layers x 16 heads x 8192^2 x (5 x 192 + 4 x 128) for one sequence
    got = mla_moe.kernel_flops_per_step(model("moonlight-16b-a3b"), 8192, 1)
    assert got == 80 * 8192 ** 2 * 1472 == 7_902_739_824_640
    assert mla_moe.kernel_flops_per_step(
        model("moonlight-16b-a3b"), 8192, 2) == 2 * got
