"""Model FLOP utilisation of the whole step, in %: FLOPs per token from
bench/flops for the configuration's kind, times the tokens per second of
the traced run's untraced window, over the chips' bfloat16 peak."""
import importlib

from bench.peaks import peaks


def read(record: dict):
    config, w = record["config"], record["window"]
    flops = importlib.import_module(f"bench.flops.{config['kind']}")
    per_token = flops.flops_per_token(config["model"], record["traffic"]["seq"])
    tokens_per_s = record["tokens_per_step"] * w["steps"] / w["seconds"]
    peak = peaks(record["device_kind"])["bf16_flops"] * record["chips"]
    return 100.0 * per_token * tokens_per_s / peak
