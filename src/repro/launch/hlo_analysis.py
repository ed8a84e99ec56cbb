"""Roofline-term extraction from compiled dry-run artifacts.

compiled.cost_analysis() provides HLO FLOPs and bytes-accessed; collective
traffic is NOT in cost_analysis, so we parse the (optimized) HLO text and
sum the operand bytes of every collective op:

  all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute

Roofline terms per §Roofline, with the target chip's peaks from
launch/mesh.PEAKS (v5e: 197e12 FLOP/s, 819e9 B/s HBM, 50e9 B/s per link):

  compute   = HLO_FLOPs / (chips * flops_bf16)
  memory    = HLO_bytes / (chips * hbm_bw)
  collective= collective_bytes / (chips * ici_bw)
"""
from __future__ import annotations

import re
from typing import Dict

import numpy as np

from repro.launch import mesh as mesh_lib

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

# e.g. "bf16[16,4096,384]{2,1,0}" inside an HLO op line
_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|f8e4m3|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")

_COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                     "all-to-all", "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum of OUTPUT shape bytes of every collective op, by kind.

    HLO lines look like:
      %ag = bf16[16,512]{...} all-gather(%x), replica_groups=...
    The leading shape is the op result; for collectives this is the traffic
    unit we charge (all-gather: gathered bytes; all-reduce: reduced tensor).
    """
    out = {k: 0 for k in _COLLECTIVE_KINDS}
    counts = {k: 0 for k in _COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"^(%?[\w.\-]+)\s*=\s*(.*)$", s)
        if not m:
            continue
        rhs = m.group(2)
        kind = None
        for k in _COLLECTIVE_KINDS:
            # op name appears right after the result shape(s)
            if re.search(rf"\b{k}(-start|-done)?\(", rhs):
                kind = k
                break
        if kind is None:
            continue
        if f"{kind}-done(" in rhs:
            continue  # -done pairs with -start; count once
        shapes = _SHAPE_RE.findall(rhs.split("(")[0])
        if not shapes:
            shapes = _SHAPE_RE.findall(rhs)
        total = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        out[kind] += total
        counts[kind] += 1
    out["_counts"] = counts
    return out


def op_counts(hlo_text: str, kinds=("scatter",)) -> Dict[str, int]:
    """Count ops of the named kinds in an HLO or StableHLO dump.

    Matches both spellings — HLO `scatter(...)` and StableHLO
    `"stablehlo.scatter"(...)` — while the kind must START the op name,
    so "scatter" does NOT match reduce-scatter / reduce_scatter and
    "gather" does not match all-gather. Used to pin fusion claims
    structurally: the fused paged-attention decode step must lower with
    ZERO arena scatters where the XLA branch lowers three
    (tests/test_paged_cache.py). NB count on the PRE-optimization
    lowering for backend-portable results: the CPU backend's scatter
    expander rewrites scatter into while loops during optimization.
    """
    counts = {k: 0 for k in kinds}
    for line in hlo_text.splitlines():
        m = re.match(r"^(%?[\w.\-\"]+)\s*=\s*(.*)$", line.strip())
        if not m:
            continue
        rhs = m.group(2)
        for k in kinds:
            if re.search(rf'(?:^|[^\w.\-])(?:\w+\.)?{k}"?\(', rhs):
                counts[k] += 1
    return counts


def roofline_terms(flops: float, bytes_accessed: float,
                   coll_bytes_total: float, n_chips: int,
                   device_kind: str) -> Dict[str, float]:
    """Roofline seconds on `device_kind` chips. Inputs are GLOBAL totals;
    divide by chip count.

    NB: when costs come from the partitioned (per-chip) HLO program, pass
    n_chips=1 — the program is already one chip's share.
    """
    peaks = mesh_lib.chip_peaks(device_kind)
    compute_s = flops / (n_chips * peaks.flops_bf16)
    memory_s = bytes_accessed / (n_chips * peaks.hbm_bw)
    collective_s = coll_bytes_total / (n_chips * peaks.ici_bw)
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom
    terms["bound_s"] = terms[dom]
    return terms


def model_flops_training(n_params_active: int, n_tokens: int) -> float:
    """6*N*D — the standard training-FLOPs estimate (fwd 2ND + bwd 4ND)."""
    return 6.0 * n_params_active * n_tokens


def model_flops_inference(n_params_active: int, n_tokens: int) -> float:
    return 2.0 * n_params_active * n_tokens


def analyze_compiled(lowered, compiled, n_chips: int,
                     device_kind: str) -> dict:
    from repro.launch import hlo_cost

    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))

    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()

    # Loop-aware cost model: XLA's cost_analysis counts while bodies once,
    # which undercounts scanned-layer models by the layer count.
    loop_cost = hlo_cost.analyze_hlo_text(hlo)
    flops = loop_cost.flops
    byts = loop_cost.bytes
    coll = dict(loop_cost.coll)
    coll["_counts"] = {k: int(v) for k, v in loop_cost.coll_counts.items()}
    coll_total = sum(v for k, v in coll.items() if not k.startswith("_"))

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            if hasattr(ma, attr):
                mem[attr] = int(getattr(ma, attr))
    except Exception as e:  # CPU backend may not expose memory analysis
        mem["error"] = str(e)

    # The SPMD-partitioned HLO is the per-chip program: costs are per chip.
    terms = roofline_terms(flops, byts, coll_total, n_chips=1,
                           device_kind=device_kind)
    return {
        "flops": flops,                      # per chip
        "flops_global": flops * n_chips,
        "bytes_accessed": byts,              # per chip
        "xla_flops_loop_blind": xla_flops,
        "xla_bytes_loop_blind": xla_bytes,
        "collective_bytes": {k: v for k, v in coll.items()
                             if not k.startswith("_")},
        "collective_counts": coll.get("_counts", {}),
        "collective_bytes_total": coll_total,
        "memory_analysis": mem,
        "roofline": terms,
        "n_chips": n_chips,
    }
