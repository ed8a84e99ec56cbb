"""Device time a step of latent attention's projections, in ms: the self
time of the traced window's ops whose `op_name` in the compiled step has
the program's scope `mla.latent` (W_kv_a, the latent's RMSNorm and W_kv_b,
inside `attention`), every pass, over its steps (bench/mla_ops.py)."""
from bench import mla_ops, scopes


def read(record: dict):
    times = mla_ops.window_times(record)
    if not times:
        return None
    try:
        from repro.metrics import trace as ptrace
    except ImportError:
        return None
    compiled = ptrace.program("train.step")
    if compiled is None:
        return None
    ops = mla_ops.latent_ops(compiled.as_text())
    if not ops:
        return None
    seconds = sum(t for op, t in times.items() if scopes.bare(op) in ops)
    return 1e3 * seconds / record["trace"]["steps"]
