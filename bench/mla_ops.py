"""What the latent-attention metrics read from a traced run: the self time
of each device op over the `bench.window` span (loaded once per run), the
ops of the training attention kernel, and the ops of the program's
`mla.latent` scope.

The kernel's calls are custom calls named after their Pallas kernels,
`flash_attention_fwd`, `flash_attention_bwd_dkv` and
`flash_attention_bwd_dq`, which the trace's op names carry. An op is in
`mla.latent` where its instruction's `op_name` in the compiled step has a
part `mla.latent`; a fusion with no `op_name` is where most of the
instructions of its fused computation that have one are (bench/scopes.py
maps the enclosing scopes the same way).
"""
from __future__ import annotations

import collections
import sys
import traceback

from bench import scopes

KERNEL = "flash_attention_"
LATENT = "mla.latent"

_cache: dict = {}


def window_times(record: dict):
    """{op: self seconds} of the traced window, or None without a trace."""
    if record.get("trace") is None:
        return None
    from bench import run
    from bench import trace as btrace

    try:
        path = btrace.latest(run.OUT / "trace" / record["cell"])
        key = (str(path), path.stat().st_mtime_ns)
        if key not in _cache:
            _cache.clear()
            _cache[key] = scopes.window_self_times(btrace.load(path))[0]
    except Exception:               # a reader must not end the run
        traceback.print_exc(file=sys.stderr)
        return None
    return _cache[key]


def kernel_seconds(times: dict) -> float:
    """Self seconds of the attention kernel's calls among `times`."""
    return sum(t for op, t in times.items()
               if scopes.bare(op).startswith(KERNEL))


def in_latent(op_name: str) -> bool:
    parts = op_name.split(";")[0].split("/")
    return any(scopes.unwrap(p) == LATENT for p in parts)


def latent_ops(hlo: str) -> set:
    """Instruction names of the compiled step that belong to `mla.latent`."""
    op_names, calls, members = scopes.parse(hlo)
    out = {}

    def of(name: str, depth: int = 0) -> bool:
        if name in out:
            return out[name]
        if op_names.get(name) or name not in calls or depth > 8:
            got = in_latent(op_names.get(name, ""))
        else:
            votes = collections.Counter(
                of(inner, depth + 1) for inner in members[calls[name]]
                if op_names.get(inner) or inner in calls)
            got = bool(votes) and votes.most_common(1)[0][0]
        out[name] = got
        return got

    return {name for name in op_names if of(name)}
