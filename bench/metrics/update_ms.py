"""Device time a step of the optimizer update, in ms: the self time of the
traced window's ops under the program's `optimizer` scope (LANS, inside the
master-weight wrapper's `cond`), over its steps (bench/scopes.py)."""
from bench import scopes


def read(record: dict):
    table = scopes.of_record(record)
    if table is None or "optimizer" not in table["scopes"]:
        return None
    return 1e3 * scopes.scoped_seconds(table, "optimizer")
