"""Pallas TPU kernels: paged-attention decode, plain and scatter-fused.

The serving decode step stores attention KV in block ARENAS of
(n_blocks, block_size, n_kv, head_dim) addressed through per-slot block
TABLES (serving/cache_pool.PagedCachePool). The XLA path lowers the
block-table gather as `arena[table]`, which materializes a dense
(B, ring_len, n_kv, head_dim) K **and** V copy in HBM every layer every
step — read arena + write dense + read dense is ~3x the unavoidable K/V
traffic, and decode is memory-bound (Pati et al. 2021), so that copy IS
the step time at scale.

Two kernels remove the materialization:

`paged_attention` (PR 4/7) is the READ-side kernel: the block table
rides in as a scalar-prefetch operand, the K/V/pos BlockSpec index maps
select arena block `table[b, j]` for grid step (b, j), and the pipeline
emitter streams exactly the referenced blocks HBM -> VMEM
(double-buffered) while the kernel body folds each block into an
online-softmax accumulator. Nothing of size (B, ring_len, ...) ever
exists. It still expects POST-scatter arenas: the decode token's K/V
were written by three separate XLA scatters that read-modify-write the
full arenas in HBM, then the kernel re-reads those same rows.

`paged_attention_fused` (PR 10) folds that scatter into the kernel's
EPILOGUE: the new K/V rows and the cursor ride in as operands, the
arenas are aliased in/out via `input_output_aliases`, and the grid step
that streams a destination block overlays the new rows in VMEM — the
updated arenas come back alongside the attention output and the three
arena round-trips disappear. The new rows join the softmax as a
"virtual block" folded once at j == 0 (key positions = q_pos), which is
legal because every STALE row at a destination offset is already
masked: previously-unwritten/rolled-back rows carry pos == -1, and a
wrapped sliding-window row satisfies q_pos - pos_old >= ring_len -
(S - 1) >= window by the pool's `row_margin = spec_k - 1` contract.

Write routing: a scalar-prefetch FLUSH MAP W (B, max_blocks) gives, for
every grid step j, the arena block the k/v/pos output buffers map to —
the destination block with the largest table position <= j (the region
below the first destination joins its region, so each destination block
is filled before its region ends and flushed exactly once on real TPU's
flush-on-index-change pipelining). A slot with no valid row maps W to
the null block 0 and copies the streamed null block through unchanged —
the fused kernel NEVER writes new bytes into block 0 (unlike the XLA
scatter branch, which dumps invalid rows' K/V into null row 0; both
keep its positions -1, so the difference is invisible to attention).
Valid rows must target real (nonzero, exclusively-owned) blocks — the
allocator/growth contract.

Aliasing rules that make this safe (see docs/kernels.md for the worked
example): `input_output_aliases` indices count the FLATTENED inputs
including scalar-prefetch operands; interpret mode initialises aliased
outputs from their input buffers, so blocks the grid never maps stay
bit-identical; input blocks are read from the pristine pre-call arenas
(interpret snapshots; on TPU the only flush that targets a destination
block happens after its input-read step, and destination blocks are
exclusively owned so no other slot streams them).

Grid: (B, max_blocks), sequential on TPU — the per-slot running state
(m, l, acc) lives in VMEM scratch, initialised at j == 0 and written to
the output block at j == max_blocks - 1 (the same revisited-output
idiom as the lans reduction kernels). The query block is (S, h, hd)
with S >= 1: speculative verify feeds the K draft tokens of a slot as
S = K query rows sharing one HBM sweep of the slot's K/V blocks, each
row causally masked against its own position (q_pos is (B, S)). S = 1
is the plain decode special case — same kernel, same numerics.

Masking happens ON-CHIP from the streamed position block: position -1
rows (the reserved null block, unwritten ring rows, evicted slots) drop
out of the softmax exactly — `exp(NEG_INF - m) == 0` — and causality /
sliding windows test the block positions against the slot's query
position, also a scalar-prefetch operand. A slot with no valid key at
all (an inactive decode slot: every table entry is the null block)
returns exactly 0 rather than NaN.

Numerics: all arithmetic is fp32 in VREGs regardless of the arena
storage dtype, mirroring the XLA decode branch (which accumulates its
logit and PV contractions in fp32 via preferred_element_type) — the two
paths agree to fp32 summation-order tolerance, which is what keeps
greedy decode token-identical between kernel="xla" and kernel="paged"
(tests/test_paged_cache.py runs both engines differentially). The
epilogue writes are bitwise: rows are SELECTED (jnp.where), never
scaled, so the fused arenas match the XLA scatter bit-for-bit on every
data block.

`interpret` defaults by backend: True off-TPU (this CPU container),
False on real TPU. `grid_order` (None = consult the checked-in tuned
table, fall back to "arbitrary") selects the Mosaic dimension
semantics: "arbitrary" runs the whole grid sequentially; "parallel"
lets megacore split the batch dimension (safe: slots only write their
own exclusively-owned destination blocks, and concurrent null-block
copies write identical bytes). kernels/ref.py:paged_attention_ref /
paged_attention_fused_ref are the dense pure-jnp oracles tests gate
against — the fused oracle CARRIES THE WRITE so arena mutation is part
of the pinned contract, not a side effect.
"""
from __future__ import annotations

import functools
import json
import pathlib
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import NEG_INF, default_interpret

_VALID_FLOOR = -1e37     # any real logit is far above this
# fp32 contractions on the MXU: without it the TPU may round the fp32
# softmax weights to bf16 before the PV product.
_FP32 = jax.lax.Precision.HIGHEST

# TPU register/VMEM tiling: the last ("lane") dim tiles by 128 always;
# the second-to-last ("sublane") dim tiles by 8 for 4-byte dtypes and 16
# for 2-byte dtypes. Interpret mode does not check these — real TPU does.
TILE_LANE = 128
# VMEM is ~16 MiB/core on current TPUs; leave headroom for the compiler.
VMEM_BUDGET = int(16 * 1024 * 1024 * 0.9)

_TUNED_TABLE = pathlib.Path(__file__).resolve().parent.parent / \
    "configs" / "paged_attn_tuned.json"


# --------------------------------------------------------------------------
# tile alignment / VMEM sizing (validated at PagedCachePool construction)
# --------------------------------------------------------------------------

def tile_sublane(dtype) -> int:
    """Minimum sublane multiple for a dtype (8 fp32-class, 16 bf16-class)."""
    return 8 if jnp.dtype(dtype).itemsize >= 4 else 16


def tile_alignment_problems(block_size: int, head_dim: int, dtype) -> list:
    """Why (block_size, head_dim) K/V blocks won't tile on real TPU.

    Arena blocks reach the kernel as (block_size, n_kv, head_dim) VMEM
    windows: head_dim is the lane dim (must be a multiple of 128) and
    block_size lands on a sublane dim (multiple of 8 for fp32 arenas,
    16 for bf16). Empty list = clean; interpret mode tolerates anything.
    """
    problems = []
    sub = tile_sublane(dtype)
    if head_dim % TILE_LANE:
        problems.append(
            f"head_dim {head_dim} is not a multiple of the {TILE_LANE} "
            f"lane tile: pad the head dim (or fold heads into the lane "
            f"axis) before running compiled on TPU")
    if block_size % sub:
        problems.append(
            f"block_size {block_size} is not a multiple of the {sub} "
            f"sublane tile for {jnp.dtype(dtype).name} arenas: use "
            f"block_size {-(-block_size // sub) * sub}")
    return problems


def block_rule_problems(name: str, block: tuple, array: tuple) -> list:
    """The TPU compiler's rule for one BlockSpec: each of the block's last
    two dims is a multiple of its tile (8 sublanes, 128 lanes) or equals
    the array's whole dim. `array` may be given by its trailing dims."""
    problems = []
    for dim, tile, what in ((-2, 8, "sublane"), (-1, TILE_LANE, "lane")):
        if block[dim] != array[dim] and block[dim] % tile:
            problems.append(
                f"{name} block {block} over {array}: {what} dim "
                f"{block[dim]} is neither a multiple of {tile} nor the "
                f"array's {array[dim]}")
    return problems


def fused_blocks(S: int, h: int, n_kv: int, hd: int, bs: int) -> dict:
    """Block shape of each blocked operand of `paged_attention_fused` and
    the trailing dims of the array it tiles (leading dims count slots or
    arena blocks; the block rule ignores them). The outputs reuse the q,
    arena and pos blocks."""
    return {"q": ((1, S, h, hd), (S, h, hd)),
            "new_kv": ((1, S, n_kv, hd), (S, n_kv, hd)),
            "kv_arena": ((1, bs, n_kv, hd), (bs, n_kv, hd)),
            "pos": ((1, 1, bs), (1, bs))}


def kernel_fit_problems(block_size: int, head_dim: int, n_heads: int,
                        n_kv: int, dtype, *, S: int = 1,
                        vmem_budget: int = VMEM_BUDGET) -> list:
    """Tile-alignment, the compiler's block rule and VMEM-scratch sizing
    for one kernel launch.

    The VMEM estimate covers the fused kernel at production head counts:
    fp32 online-softmax scratch (m, l, acc), the double-buffered K/V/pos
    input stream, the aliased K/V/pos output buffers, and the q / new-row
    / attention-out blocks.
    """
    problems = tile_alignment_problems(block_size, head_dim, dtype)
    for name, (block, array) in fused_blocks(
            S, n_heads, n_kv, head_dim, block_size).items():
        problems += block_rule_problems(name, block, array)
    isz = jnp.dtype(dtype).itemsize
    blk = block_size * n_kv * head_dim * isz + block_size * 4  # K|V + pos
    scratch = 4 * S * n_heads * (2 + head_dim)                 # m, l, acc fp32
    vmem = (scratch
            + 2 * 2 * blk            # k/v in, double-buffered
            + 2 * blk                # k/v/pos out buffers
            + S * n_heads * head_dim * (isz + 4)   # q in + fp32 out
            + 2 * S * n_kv * head_dim * isz)       # new K/V rows
    if vmem > vmem_budget:
        problems.append(
            f"kernel VMEM estimate {vmem} bytes exceeds the "
            f"{vmem_budget}-byte budget: shrink block_size or S")
    return problems


def ensure_kernel_fit(block_size: int, head_dim: int, n_heads: int,
                      n_kv: int, dtype, *, S: int = 1,
                      interpret: Optional[bool] = None) -> list:
    """Raise on real TPU for a layout the compiled kernel cannot take.

    Returns the problem list either way; off-TPU (or with the
    `interpret` escape hatch forced on) problems are advisory — the
    interpret-mode kernel executes any layout.
    """
    problems = kernel_fit_problems(block_size, head_dim, n_heads, n_kv,
                                   dtype, S=S)
    if interpret is None:
        interpret = default_interpret()
    if problems and not interpret:
        raise ValueError(
            "paged-attention kernel layout cannot compile on TPU: "
            + "; ".join(problems)
            + " (pass interpret/--interpret to force interpret mode)")
    return problems


# --------------------------------------------------------------------------
# tuned-config table (written by `benchmarks/kernel_throughput --autotune`)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def tuned_table() -> dict:
    """The checked-in autotuner results: backend -> hd<d>_kv<k> ->
    bs<bs>_S<S> -> {"grid_order": ..., "us": ...}."""
    try:
        with open(_TUNED_TABLE) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def tuned_grid_order(backend: str, head_dim: int, n_kv: int,
                     block_size: int, S: int) -> str:
    """Trace-time table consult: exact (backend, head_dim, n_kv,
    block_size, S) match, else the documented "arbitrary" fallback (the
    fully-sequential grid every correctness test runs)."""
    entry = (tuned_table().get(backend, {})
             .get(f"hd{head_dim}_kv{n_kv}", {})
             .get(f"bs{block_size}_S{S}", {}))
    return entry.get("grid_order", "arbitrary")


def _compiler_params(grid_order: str):
    if grid_order == "parallel":
        return pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    if grid_order != "arbitrary":
        raise ValueError(
            f"grid_order must be 'arbitrary' or 'parallel', got {grid_order}")
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))


def pos_rows(pos_arena):
    """(n_blocks, bs) positions as (n_blocks, 1, bs): one arena block's
    row is then a (1, 1, bs) block whose last two dims equal the array's,
    which the TPU compiler accepts for any block_size (a (1, bs) block of
    the 2-D arena is refused: its sublane dim is neither 8-aligned nor
    the whole array). The kernels read and write positions in this form."""
    n_blocks, bs = pos_arena.shape
    return pos_arena.reshape(n_blocks, 1, bs)


# --------------------------------------------------------------------------
# shared online-softmax fold
# --------------------------------------------------------------------------

def _online_fold(q, k, v, kp, qp, m_ref, l_ref, acc_ref, *,
                 scale, causal, window, softcap, n_kv):
    """Fold one key block into the (m, l, acc) scratch state.

    q (S, h, hd) fp32; k/v (T, n_kv, hd) any float (upcast here);
    kp (1, T) int32 key positions (-1 = invalid row); qp (S, 1) int32.
    """
    S, h, hd = q.shape
    g = h // n_kv
    k = k.astype(jnp.float32)

    # GQA without materializing repeated heads: head r = kv*g + i reads
    # kv head r // g — the same layout jnp.repeat(k, g, axis=2) yields.
    # The S query rows batch through the same contraction: regroup
    # (S, h, hd) -> (n_kv, S*g, hd) so n_kv stays the dot batch dim.
    logits = jax.lax.dot_general(
        q.reshape(S, n_kv, g, hd).swapaxes(0, 1).reshape(n_kv, S * g, hd),
        k,
        dimension_numbers=(((2,), (2,)), ((0,), (1,))),
        precision=_FP32,
        preferred_element_type=jnp.float32,    # (n_kv, S*g, T)
    ).reshape(n_kv, S, g, -1).swapaxes(0, 1).reshape(S, h, -1) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)

    ok = jnp.broadcast_to(kp >= 0, (S, kp.shape[1]))
    if causal:                                 # row s masks against ITS pos
        ok = ok & (kp <= qp)
    if window is not None:
        ok = ok & ((qp - kp) < window)
    logits = jnp.where(ok[:, None, :], logits, NEG_INF)

    m_prev = m_ref[...].reshape(S, h)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=2))
    # A fully-masked prefix keeps m at NEG_INF; shift by 0 there so the
    # masked exp still underflows to exactly 0 instead of exp(0) == 1.
    m_safe = jnp.where(m_new > _VALID_FLOOR, m_new, 0.0)
    alpha = jnp.exp(m_prev - m_safe)           # 0 when m_prev is NEG_INF
    e = jnp.exp(logits - m_safe[:, :, None])   # masked entries -> exactly 0

    v = v.astype(jnp.float32)
    pv = jax.lax.dot_general(
        e.reshape(S, n_kv, g, -1).swapaxes(0, 1).reshape(n_kv, S * g, -1),
        v,
        dimension_numbers=(((2,), (0,)), ((0,), (1,))),
        precision=_FP32,
        preferred_element_type=jnp.float32,    # (n_kv, S*g, hd)
    ).reshape(n_kv, S, g, hd).swapaxes(0, 1).reshape(S, h, hd)

    m_ref[...] = m_new.reshape(S * h, 1)
    l_ref[...] = (alpha * l_ref[...].reshape(S, h)
                  + jnp.sum(e, axis=2)).reshape(S * h, 1)
    acc_ref[...] = (alpha.reshape(S * h, 1) * acc_ref[...]
                    + pv.reshape(S * h, hd))


def _slot_positions(qpos_ref, b, S: int, axis: int):
    """Slot b's S query positions as an (S, 1) (axis 0) or (1, S) (axis 1)
    int32 vector. SMEM holds the prefetched positions and only yields one
    scalar per load, so the vector is assembled from S scalar loads."""
    shape = (S, 1) if axis == 0 else (1, S)
    idx = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    out = jnp.zeros(shape, jnp.int32)
    for s in range(S):                         # S static and small
        out = jnp.where(idx == s, qpos_ref[b, s], out)
    return out


def _finish_out(out_ref, m_ref, l_ref, acc_ref, S, h, hd):
    lsum = l_ref[...].reshape(S, h)
    live = lsum > 0.0                          # False only for dead rows
    out = (acc_ref[...].reshape(S, h, hd)
           / jnp.where(live, lsum, 1.0)[:, :, None])
    out_ref[0] = jnp.where(live[:, :, None], out, 0.0).astype(out_ref.dtype)


# --------------------------------------------------------------------------
# read-side kernel (PR 4/7): arenas already scattered
# --------------------------------------------------------------------------

def _paged_attn_kernel(tbl_ref, qpos_ref, q_ref, k_ref, v_ref, pos_ref,
                       out_ref, m_ref, l_ref, acc_ref, *,
                       scale, causal, window, softcap, n_kv):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)           # (S, h, hd)
    S, h, hd = q.shape
    qp = _slot_positions(qpos_ref, b, S, 0)    # (S, 1) this slot's positions
    _online_fold(q, k_ref[0], v_ref[0], pos_ref[0], qp,
                 m_ref, l_ref, acc_ref, scale=scale, causal=causal,
                 window=window, softcap=softcap, n_kv=n_kv)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        _finish_out(out_ref, m_ref, l_ref, acc_ref, S, h, hd)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "softcap", "interpret",
                     "grid_order"))
def paged_attention(q, k_arena, v_arena, pos_arena, tables, q_pos, *,
                    scale, causal=True, window=None, softcap=None,
                    interpret=None, grid_order=None):
    """Fused paged decode attention, S=1 or a small-S query block.

    Args:
      q: (B, h, head_dim) query for the single decode token, or
        (B, S, h, head_dim) for an S-token speculative-verify block;
        any float dtype (upcast to fp32 on-chip).
      k_arena / v_arena: (n_blocks, block_size, n_kv, head_dim) block
        arenas, POST-scatter (the decode tokens' K/V already written).
      pos_arena: (n_blocks, block_size) int32 absolute key positions;
        -1 marks invalid rows (null block, unwritten ring slots) and is
        masked unconditionally.
      tables: (B, max_blocks) int32 arena indices, 0 = the null block.
      q_pos: (B,) — or (B, S) matching a 4-D q — int32 absolute query
        positions; with S > 1 each query row is masked causally against
        its OWN position, so one kernel launch verifies all S draft
        tokens per slot.
      scale / causal / window / softcap: static attention config,
        matching models/attention.AttnConfig semantics.
      interpret: Pallas interpret mode; None = auto (True off-TPU).
      grid_order: Mosaic dimension semantics — "arbitrary" (sequential
        grid) or "parallel" (megacore may split the batch dim). None
        consults the checked-in tuned table (configs/
        paged_attn_tuned.json) by (backend, head_dim, n_kv, block_size,
        S) and falls back to "arbitrary" on a miss.

    Returns (B, h, head_dim) or (B, S, h, head_dim) fp32, matching q.
    Query rows whose table references no valid key (inactive decode
    slots) return exactly 0 — see kernels/ref.py:paged_attention_ref,
    the oracle that pins this contract.
    """
    if interpret is None:
        interpret = default_interpret()
    squeeze = q.ndim == 3
    if squeeze:
        q, q_pos = q[:, None], q_pos[:, None]
    B, S, h, hd = q.shape
    _, bs, n_kv, _ = k_arena.shape
    nb = tables.shape[1]
    if h % n_kv:
        raise ValueError(f"n_heads {h} not a multiple of n_kv {n_kv}")
    if grid_order is None:
        grid_order = tuned_grid_order(jax.default_backend(), hd, n_kv, bs, S)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # tables, q_pos
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, S, h, hd), lambda b, j, tbl, qp: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, n_kv, hd),
                         lambda b, j, tbl, qp: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, n_kv, hd),
                         lambda b, j, tbl, qp: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec((1, 1, bs), lambda b, j, tbl, qp: (tbl[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, S, h, hd),
                               lambda b, j, tbl, qp: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((S * h, 1), jnp.float32),   # running max m
            pltpu.VMEM((S * h, 1), jnp.float32),   # running normalizer l
            pltpu.VMEM((S * h, hd), jnp.float32),  # unnormalized out acc
        ],
    )
    kern = functools.partial(
        _paged_attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, n_kv=n_kv)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, h, hd), jnp.float32),
        compiler_params=_compiler_params(grid_order),
        interpret=interpret,
    )(tables.astype(jnp.int32), q_pos.astype(jnp.int32),
      q, k_arena, v_arena, pos_rows(pos_arena))
    return out[:, 0] if squeeze else out


# --------------------------------------------------------------------------
# scatter-in-epilogue kernel (PR 10): the kernel carries the write
# --------------------------------------------------------------------------

def _flush_map(tables, q_pos, cursor, bs: int, nb: int):
    """(B, nb) int32: the arena block the k/v/pos OUT buffers map to at
    grid step j — the destination block with the largest table position
    <= j among this slot's valid rows; steps below the first destination
    join its region; a slot with no valid row maps the null block 0
    (identity rewrite). Regions are contiguous runs, so real TPU's
    flush-on-index-change writes each destination block exactly once,
    strictly after the step that filled its buffer."""
    B, S = q_pos.shape
    ring = nb * bs
    r = jax.lax.rem(cursor[:, None].astype(jnp.int32)
                    + jnp.arange(S, dtype=jnp.int32), ring)
    jblk = r // bs                                       # (B, S) table pos
    valid = q_pos >= 0
    dest = jnp.take_along_axis(tables, jblk, axis=1)     # (B, S)
    jj = jnp.arange(nb, dtype=jnp.int32)[None, :, None]  # (1, nb, 1)
    cand = jnp.where(valid[:, None, :] & (jblk[:, None, :] <= jj),
                     jblk[:, None, :], -1)               # (B, nb, S)
    has_le = jnp.max(cand, axis=2) >= 0                  # (B, nb)
    pick_le = jnp.argmax(cand, axis=2)                   # s of largest <= j
    pick_min = jnp.argmin(jnp.where(valid, jblk, nb), axis=1)  # (B,)
    pick = jnp.where(has_le, pick_le, pick_min[:, None])
    W = jnp.take_along_axis(dest, pick, axis=1)
    return jnp.where(jnp.any(valid, axis=1)[:, None], W, 0).astype(jnp.int32)


def _paged_attn_fused_kernel(tbl_ref, qpos_ref, cur_ref, w_ref,
                             q_ref, kn_ref, vn_ref, k_ref, v_ref, pos_ref,
                             out_ref, ko_ref, vo_ref, po_ref,
                             m_ref, l_ref, acc_ref, *,
                             scale, causal, window, softcap, n_kv, bs, nb):
    b = pl.program_id(0)
    j = pl.program_id(1)
    ring = nb * bs

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)           # (S, h, hd)
    S, h, hd = q.shape
    qp = _slot_positions(qpos_ref, b, S, 0)    # (S, 1) this slot's positions

    # The S new rows fold ONCE as a virtual key block (positions q_pos):
    # the streamed destination blocks still hold pre-scatter bytes at the
    # destination offsets, and those stale rows are masked — pos == -1
    # for never-written/rolled-back rows, out-of-window by the
    # row_margin contract for wrapped ring rows (module docstring).
    @pl.when(j == 0)
    def _fold_new_rows():
        _online_fold(q, kn_ref[0], vn_ref[0],
                     _slot_positions(qpos_ref, b, S, 1), qp,
                     m_ref, l_ref, acc_ref, scale=scale, causal=causal,
                     window=window, softcap=softcap, n_kv=n_kv)

    _online_fold(q, k_ref[0], v_ref[0], pos_ref[0], qp,
                 m_ref, l_ref, acc_ref, scale=scale, causal=causal,
                 window=window, softcap=softcap, n_kv=n_kv)

    # Epilogue scatter: when the streamed block is a destination block,
    # refresh the aliased out buffers from the (pristine) streamed input
    # and overlay the rows that land here. Selection is bitwise
    # (jnp.where), matching the XLA scatter exactly. A slot with no
    # valid row copies the null block through at j == 0 so its W region
    # (the whole slot) flushes identical bytes back to block 0.
    cur = cur_ref[b]
    hits, all_invalid = [], True
    for s in range(S):
        r_s = jax.lax.rem(cur + s, ring)
        hits.append(((qpos_ref[b, s] >= 0) & (r_s // bs == j),
                     jax.lax.rem(r_s, bs), s))
    any_hit = functools.reduce(jnp.logical_or, [h_ for h_, _, _ in hits])
    none_valid = functools.reduce(
        jnp.logical_and, [qpos_ref[b, s] < 0 for s in range(S)])
    fill = any_hit | ((j == 0) & none_valid)

    @pl.when(fill)
    def _write_epilogue():
        kbuf = k_ref[0]                        # (bs, n_kv, hd) arena dtype
        vbuf = v_ref[0]
        pbuf = pos_ref[0]                      # (1, bs) int32
        rows3 = jax.lax.broadcasted_iota(jnp.int32, (bs, 1, 1), 0)
        rows2 = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        for hit, off, s in hits:               # S static and small: unrolled
            m3 = hit & (rows3 == off)
            kbuf = jnp.where(m3, kn_ref[0, s].astype(kbuf.dtype), kbuf)
            vbuf = jnp.where(m3, vn_ref[0, s].astype(vbuf.dtype), vbuf)
            pbuf = jnp.where(hit & (rows2 == off), qpos_ref[b, s], pbuf)
        ko_ref[0] = kbuf
        vo_ref[0] = vbuf
        po_ref[0] = pbuf

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        _finish_out(out_ref, m_ref, l_ref, acc_ref, S, h, hd)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "softcap", "interpret",
                     "grid_order"))
def paged_attention_fused(q, k_new, v_new, k_arena, v_arena, pos_arena,
                          tables, q_pos, cursor, *, scale, causal=True,
                          window=None, softcap=None, interpret=None,
                          grid_order=None):
    """Paged decode attention with the K/V/pos scatter fused into the
    kernel epilogue: arenas are PRE-scatter and come back updated.

    Args (beyond `paged_attention`):
      k_new / v_new: (B, n_kv, head_dim) — or (B, S, n_kv, head_dim)
        matching a 4-D q — the decode tokens' K/V rows, already in the
        arena storage dtype (written bit-exact).
      cursor: (B,) int32 per-slot write cursors; row s of slot b lands
        at logical ring row (cursor[b] + s) % ring_len, i.e. arena
        [tables[b, r // bs], r % bs]. Rows with q_pos < 0 write nothing
        (the XLA branch routes them to null row 0 instead — same masked
        visibility, see module docstring).

    Returns (out, k_arena, v_arena, pos_arena): attention output as
    `paged_attention`, plus the post-write arenas (aliased in/out — on
    TPU and under donation the update is in place; no extra arena
    round-trip exists in the lowered HLO).
    """
    if interpret is None:
        interpret = default_interpret()
    squeeze = q.ndim == 3
    if squeeze:
        q, q_pos = q[:, None], q_pos[:, None]
        k_new, v_new = k_new[:, None], v_new[:, None]
    B, S, h, hd = q.shape
    _, bs, n_kv, _ = k_arena.shape
    nb = tables.shape[1]
    if h % n_kv:
        raise ValueError(f"n_heads {h} not a multiple of n_kv {n_kv}")
    if S > bs * nb:
        raise ValueError(f"S={S} exceeds the ring ({nb}x{bs} rows)")
    if grid_order is None:
        grid_order = tuned_grid_order(jax.default_backend(), hd, n_kv, bs, S)

    tables = tables.astype(jnp.int32)
    q_pos = q_pos.astype(jnp.int32)
    cursor = cursor.astype(jnp.int32)
    wmap = _flush_map(tables, q_pos, cursor, bs, nb)
    pos3 = pos_rows(pos_arena)
    blk = {k: shapes[0] for k, shapes in
           fused_blocks(S, h, n_kv, hd, bs).items()}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,                 # tables, q_pos, cursor, W
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec(blk["q"],
                         lambda b, j, tbl, qp, cur, w: (b, 0, 0, 0)),
            pl.BlockSpec(blk["new_kv"],
                         lambda b, j, tbl, qp, cur, w: (b, 0, 0, 0)),
            pl.BlockSpec(blk["new_kv"],
                         lambda b, j, tbl, qp, cur, w: (b, 0, 0, 0)),
            pl.BlockSpec(blk["kv_arena"],
                         lambda b, j, tbl, qp, cur, w: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec(blk["kv_arena"],
                         lambda b, j, tbl, qp, cur, w: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec(blk["pos"],
                         lambda b, j, tbl, qp, cur, w: (tbl[b, j], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(blk["q"],
                         lambda b, j, tbl, qp, cur, w: (b, 0, 0, 0)),
            pl.BlockSpec(blk["kv_arena"],
                         lambda b, j, tbl, qp, cur, w: (w[b, j], 0, 0, 0)),
            pl.BlockSpec(blk["kv_arena"],
                         lambda b, j, tbl, qp, cur, w: (w[b, j], 0, 0, 0)),
            pl.BlockSpec(blk["pos"],
                         lambda b, j, tbl, qp, cur, w: (w[b, j], 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((S * h, 1), jnp.float32),   # running max m
            pltpu.VMEM((S * h, 1), jnp.float32),   # running normalizer l
            pltpu.VMEM((S * h, hd), jnp.float32),  # unnormalized out acc
        ],
    )
    kern = functools.partial(
        _paged_attn_fused_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, n_kv=n_kv, bs=bs, nb=nb)
    out, k_out, v_out, pos_out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, S, h, hd), jnp.float32),
            jax.ShapeDtypeStruct(k_arena.shape, k_arena.dtype),
            jax.ShapeDtypeStruct(v_arena.shape, v_arena.dtype),
            jax.ShapeDtypeStruct(pos3.shape, pos3.dtype),
        ],
        # Flattened-input indices INCLUDE the 4 scalar-prefetch operands:
        # inputs are [tbl, qp, cur, W, q, k_new, v_new, k, v, pos] so the
        # arenas sit at 7/8/9; outputs [out, k, v, pos] at 1/2/3.
        input_output_aliases={7: 1, 8: 2, 9: 3},
        compiler_params=_compiler_params(grid_order),
        interpret=interpret,
    )(tables, q_pos, cursor, wmap, q, k_new, v_new,
      k_arena, v_arena, pos3)
    return ((out[:, 0] if squeeze else out), k_out, v_out,
            pos_out.reshape(pos_arena.shape))
