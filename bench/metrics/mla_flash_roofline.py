"""Share of its roofline the training attention kernel reaches on latent
attention, in %: the kernel's causal FLOPs a step at the configuration's
shapes (bench/flops/mla_moe.py), at the chip's bfloat16 peak, over the
device self time a step of its `flash_attention_*` calls in the traced
window (bench/mla_ops.py). The kernel is bound by compute."""
from bench import mla_ops
from bench.flops import mla_moe
from bench.peaks import peaks


def read(record: dict):
    config = record["config"]
    times = mla_ops.window_times(record)
    if config["kind"] != "mla_moe" or not times:
        return None
    seconds = mla_ops.kernel_seconds(times) / record["trace"]["steps"]
    if seconds <= 0:
        return None
    flops = mla_moe.kernel_flops_per_step(
        config["model"], record["traffic"]["seq"],
        record["traffic"]["per_chip_batch"])
    least_s = flops / peaks(record["device_kind"])["bf16_flops"]
    return 100.0 * least_s / seconds
