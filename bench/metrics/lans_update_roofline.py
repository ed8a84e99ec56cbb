"""Share of its roofline the optimizer update reaches, in %: the least
bytes of one LANS update of the configuration (bench/flops/lans_bytes.py)
at the chip's HBM bandwidth, over the device time a step under the
program's `optimizer` scope (`update_ms`). LANS is bound by memory."""
from bench import scopes
from bench.flops import lans_bytes
from bench.peaks import peaks


def read(record: dict):
    table = scopes.of_record(record)
    config = record["config"]
    if (table is None or "optimizer" not in table["scopes"]
            or config["precision"] not in lans_bytes.BYTES):
        return None
    seconds = scopes.scoped_seconds(table, "optimizer")
    if seconds <= 0:
        return None
    least_s = (lans_bytes.least_bytes(config)
               / peaks(record["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
