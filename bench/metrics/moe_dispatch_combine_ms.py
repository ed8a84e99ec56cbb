"""Device time a step of moving tokens to and from the experts, in ms: the
self time of the traced window's ops under the program's `moe.dispatch`
(the scatter-add into expert buffers) and `moe.combine` (the gather back)
scopes, every pass, over its steps (bench/scopes.py)."""
from bench import scopes

NAMES = ("moe.dispatch", "moe.combine")


def read(record: dict):
    table = scopes.of_record(record)
    if table is None or not any(n in table["scopes"] for n in NAMES):
        return None
    return 1e3 * scopes.scoped_seconds(table, *NAMES)
