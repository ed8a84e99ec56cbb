"""Device time a step of attention, in ms: the self time of the traced
window's ops under the program's `attention` scope, forward, recomputed
forward and backward, over its steps (bench/scopes.py)."""
from bench import scopes


def read(record: dict):
    table = scopes.of_record(record)
    if table is None or "attention" not in table["scopes"]:
        return None
    return 1e3 * scopes.scoped_seconds(table, "attention")
