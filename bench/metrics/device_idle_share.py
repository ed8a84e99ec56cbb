"""Share of the traced window, in %, in which no op ran on the device: one
less the union of the device's op intervals over the `bench.window` span,
averaged over the cell's chips."""


def read(record: dict):
    t = record["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
