"""Grouped-query attention with the variants the assigned archs need:

  - GQA / MHA / MQA (n_kv_heads),
  - optional QKV bias (qwen2.5),
  - optional QK-norm (chameleon),
  - attention-logit soft-capping (gemma2),
  - sliding-window masking (gemma2 local layers; mistral long-ctx variant),
  - RoPE or no positional op (whisper uses learned pos embs upstream),
  - bidirectional (whisper encoder, BERT) or causal,
  - cross-attention (whisper decoder),
  - incremental decoding against a KV cache.

The training core is `attend`: attention of queries over keys and values
at given positions, with no cache. It is the one place that repeats K/V
heads up to the query heads (GQA) and that chooses the implementation:
the fused Pallas kernel (`_flash_attend`), the XLA path over remat'd
query blocks, or the XLA path whole (`_attend_block`). `attn_apply`
(projections, QK-norm, RoPE) and `models/mla.mla_apply` call it.

The serving cache layouts begin at `_cache_attend`, which `attn_apply`
calls instead when it is given a cache: read-only cross K/V
(`_cross_kv`), paged self-attention (`_paged`), ring prefill
(`_ring_prefill`) and contiguous decode (`_contiguous`). Each writes the
new K/V rows into its layout and returns what `attend` reads, except the
fused paged kernel, which returns the finished output.

Shapes: x (B, S, d_model); cache k/v (B, max_len, n_kv, head_dim).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import NEG_INF  # single-sourced masked-logit value
from repro.kernels.flash_attention_kernel import flash_attention
from repro.models.common import (ambient_mesh, apply_rope, dense_apply,
                                 dense_init, fit_spec, maybe_constrain,
                                 rmsnorm_apply, rmsnorm_init, softcap)

# Shortest sequence the training attention kernel takes: the shortest at
# which a TPU v5e showed it winning (S = 512; at S = 128 its fixed cost per
# grid step made it 4x slower than the XLA path: PERF.md, section 6).
FLASH_MIN_SEQ = 512
# Sequences of at least Q_CHUNK_MIN_SEQ tokens that the kernel does not take
# attend in remat'd blocks of Q_BLOCK queries: the live scores are then
# (B, H, Q_BLOCK, S), and the backward pass recomputes each block's
# probabilities instead of saving all S x S of them (the XLA-level analogue
# of flash attention that makes a 32k prefill fit).
Q_CHUNK_MIN_SEQ = 4096
Q_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False
    rope: bool = True
    rope_theta: float = 10000.0
    causal: bool = True
    sliding_window: Optional[int] = None
    logit_softcap: Optional[float] = None
    query_scale: Optional[float] = None  # default 1/sqrt(head_dim)
    # Paged decode implementation: "xla" gathers arena[table] into a dense
    # (B, ring_len) K/V copy; "paged" streams the table's blocks from HBM
    # inside the fused Pallas kernel (kernels/paged_attention_kernel.py).
    # Only the paged serving branch reads this; token output is identical.
    decode_kernel: str = "xla"
    # Pallas interpret-mode override for the paged kernel: None = auto
    # (interpret off-TPU, compiled on TPU); True forces interpret — the
    # escape hatch (serve.py --interpret) for arena layouts that fail
    # TPU tile alignment (kernels/paged_attention_kernel.py).
    kernel_interpret: Optional[bool] = None


def attn_init(rng, cfg: AttnConfig, *, cross: bool = False, dtype=jnp.float32):
    ks = jax.random.split(rng, 6)
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(ks[0], d, h * hd, use_bias=cfg.qkv_bias, dtype=dtype),
        "wk": dense_init(ks[1], d, kv * hd, use_bias=cfg.qkv_bias, dtype=dtype),
        "wv": dense_init(ks[2], d, kv * hd, use_bias=cfg.qkv_bias, dtype=dtype),
        "wo": dense_init(ks[3], h * hd, d, use_bias=False, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    del cross
    return p


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def _mask_logits(logits, q_pos, k_pos, *, causal, window, kv_valid_len=None):
    """logits: (B, H, Sq, Sk); q_pos (Sq,) or (B, Sq), k_pos (Sk,) or (B, Sk)
    absolute positions. Negative k_pos marks invalid rows (unwritten ring
    slots, left-padding, evicted serving slots) and is always masked."""
    kp = k_pos[None, None, :] if k_pos.ndim == 1 else k_pos[:, None, :]
    qp = q_pos[None, :, None] if q_pos.ndim == 1 else q_pos[:, :, None]
    ok = kp >= 0
    if causal:
        ok = ok & (kp <= qp)
    if window is not None:
        ok = ok & ((qp - kp) < window)
    ok = jnp.broadcast_to(ok, (ok.shape[0],) + logits.shape[-2:])
    mask = ok[:, None]  # (B or 1, 1, Sq, Sk)
    if kv_valid_len is not None:  # (B,) number of valid cache slots
        valid = kp < kv_valid_len[:, None, None]  # (B, 1|Sq, Sk)
        mask = mask & valid[:, None]
    return jnp.where(mask, logits, NEG_INF)


def _attend_block(q, q_pos, *, k, v, k_pos, kv_len, scale, causal, window,
                  logit_softcap, decode, compute_dtype):
    """XLA attention of q (B, Sq, H, hd) over k/v (B, Sk, H, hd): logits,
    mask, float32 softmax, PV. Returns (B, Sq, H, hd) in compute_dtype."""
    # Single-token cached decode runs its logit/PV contractions with fp32
    # accumulation and keeps probs fp32: the (B, H, 1, K) intermediates are
    # tiny, and it makes the Pallas paged kernel (fp32 in VREGs throughout)
    # token-comparable to every XLA decode path — the property the
    # paged-pallas == paged-xla differential tests pin. The OUTPUT still
    # rounds to compute_dtype: the pools lay the same keys out at
    # different cache rows, and that single rounding is what absorbs the
    # sub-ulp fp32 summation-order differences so static == dense ==
    # paged stays token-exact across layouts. The paged branch gets fp32
    # at ANY S: its S > 1 case is the speculative-verify block, which must
    # stay token-comparable to the Pallas kernel exactly like S == 1
    # (other S > 1 paths are prefill, where bf16 probs are the contract).
    acc_dtype = jnp.float32 if decode else None
    probs_dtype = jnp.float32 if decode else compute_dtype
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=acc_dtype) * scale
    logits = softcap(logits, logit_softcap)
    logits = _mask_logits(
        logits.astype(jnp.float32), q_pos, k_pos,
        causal=causal, window=window, kv_valid_len=kv_len)
    probs = jax.nn.softmax(logits, axis=-1).astype(probs_dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                   preferred_element_type=acc_dtype).astype(compute_dtype)
    return _pin(o) if decode else o


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_block(S: int) -> int:
    """Query and key block of the training attention kernel at length S:
    the whole sequence up to 1024 rows, so one grid step holds all of a
    short sequence's scores for one head, and 1024-row blocks beyond."""
    return min(S, 1024)


def _flash_fits(q, q_pos, k_pos, logit_softcap, window) -> bool:
    """Whether `attend` gives q (B, S, H, hd) to the kernel: self-attention
    with no cache (k_pos None) at 1-D q_pos, on a TPU, bfloat16, no
    soft-cap or window, and S on the kernel's 128-row grid and at least
    FLASH_MIN_SEQ."""
    S = q.shape[1]
    return (k_pos is None and q_pos.ndim == 1 and _on_tpu()
            and q.dtype == jnp.bfloat16 and logit_softcap is None
            and window is None and S >= FLASH_MIN_SEQ and S % 128 == 0
            and S % flash_block(S) == 0)


def _flash_attend(q, k, v, *, causal, scale, block=None):
    """`_attend_block` of the no-cache self-attention by the fused Pallas
    kernel (kernels/flash_attention_kernel.py), which never writes the
    (B, H, S, S) scores to memory.

    q, k: (B, S, H, dk), v: (B, S, H, dv), k/v already repeated to H
    heads; the value width may differ from the key width. block
    defaults to `flash_block(S)`. Under a mesh of several devices the
    kernel runs on each device's shard of the batch (and of the heads over
    "model"), which a custom call cannot be partitioned into by itself.
    """
    def core(q, k, v):
        b = block or flash_block(q.shape[1])
        t = lambda a: a.swapaxes(1, 2)                  # (B, H, S, hd)
        return t(flash_attention(t(q), t(k), t(v), causal=causal,
                                 scale=scale, block_q=b, block_k=b,
                                 interpret=not _on_tpu()))

    mesh = ambient_mesh()
    if mesh.empty or mesh.size == 1:
        return core(q, k, v)
    batch = tuple(a for a in ("pod", "data") if a in mesh.shape) or None
    spec = fit_spec(mesh, q.shape, (batch, None, "model", None))
    return jax.shard_map(core, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


def attend(q, k, v, q_pos, k_pos=None, *, causal, scale, window=None,
           logit_softcap=None, kv_len=None, cached=False, decode=False,
           compute_dtype):
    """Attention of q (B, S, H, dk) over k (B, Sk, KV, dk) and v (B, Sk,
    KV, dv), KV dividing H. Returns (B, S, H, dv) in compute_dtype.

    q_pos (S,) or (B, S) and k_pos (Sk,) or (B, Sk) are absolute
    positions; a negative key position is never attended. k_pos None
    means the keys are the queries' own tokens: self-attention with no
    cache. `cached` says the keys are a serving cache's rows, of which
    each sequence attends its first kv_len (all where None); `decode`
    keeps the products in float32 (`_attend_block`).

    K/V heads are repeated up to H here, and the implementation is chosen
    here:
      - the fused kernel (`_flash_attend`) for self-attention at 1-D
        q_pos that `_flash_fits` takes: a TPU, bfloat16, no soft-cap or
        window, S on the kernel's grid and at least FLASH_MIN_SEQ;
      - else, uncached at S >= Q_CHUNK_MIN_SEQ and a multiple of Q_BLOCK,
        `_attend_block` over remat'd blocks of Q_BLOCK queries;
      - else `_attend_block` over all queries at once.
    """
    B, S, h, _ = q.shape
    if k.shape[2] != h:   # GQA: repeat kv heads up to h
        k = jnp.repeat(k, h // k.shape[2], axis=2)
        v = jnp.repeat(v, h // v.shape[2], axis=2)
    if _flash_fits(q, q_pos, k_pos, logit_softcap, window):
        # A 1-D q_pos without a cache counts 0..S-1 (plus an offset), so
        # the kernel's index order is the causal mask and no key is invalid.
        return _flash_attend(q, k, v, causal=causal, scale=scale)
    block = functools.partial(
        _attend_block, k=k, v=v, kv_len=kv_len, scale=scale, causal=causal,
        k_pos=q_pos if k_pos is None else k_pos, window=window, decode=decode,
        logit_softcap=logit_softcap, compute_dtype=compute_dtype)
    qb = Q_BLOCK
    if cached or S < Q_CHUNK_MIN_SEQ or S % qb:
        return block(q, q_pos)
    q_blocks = q.reshape(B, S // qb, qb, h, -1).swapaxes(0, 1)
    qpos_blocks = (q_pos.reshape(S // qb, qb) if q_pos.ndim == 1 else
                   q_pos.reshape(B, S // qb, qb).swapaxes(0, 1))  # per batch
    blk = jax.checkpoint(block)
    out = jax.lax.map(lambda args: blk(*args), (q_blocks, qpos_blocks))
    return out.swapaxes(0, 1).reshape(B, S, h, -1)


@jax.named_scope("attention")
def attn_apply(p, cfg: AttnConfig, x, *, kv_x=None, positions=None,
               cache=None, kv_cache=None, kv_valid_len=None,
               compute_dtype=jnp.bfloat16):
    """Returns (out, new_cache). new_cache is None unless cache is given.

    kv_x: cross-attention memory (B, Sm, d). positions: (B, S) or (S,)
    absolute positions of x. cache: dict(k, v, pos, index[, table]) for
    incremental decode. kv_cache: the serving twin of kv_x, cross K/V
    projected once and attended read-only (`_cross_kv`); mutually
    exclusive with kv_x and cache. kv_valid_len: (B,) valid cache length
    (incl. new tokens).
    """
    if kv_cache is not None and (cache is not None or kv_x is not None):
        raise ValueError("kv_cache is exclusive with cache/kv_x")
    B, S, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    scale = cfg.query_scale if cfg.query_scale is not None else 1.0 / math.sqrt(hd)

    q = _split_heads(dense_apply(p["wq"], x, compute_dtype), h, hd)
    k = v = None
    if kv_cache is None:
        src = x if kv_x is None else kv_x
        k = _split_heads(dense_apply(p["wk"], src, compute_dtype), kv, hd)
        v = _split_heads(dense_apply(p["wv"], src, compute_dtype), kv, hd)

    if cfg.qk_norm:
        q = rmsnorm_apply(p["q_norm"], q)
        if kv_cache is None:
            k = rmsnorm_apply(p["k_norm"], k)

    if positions is None:
        positions = jnp.arange(S)
    positions = jnp.broadcast_to(positions, (S,) if positions.ndim <= 1 else positions.shape)

    if cfg.rope and kv_x is None and kv_cache is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    causal = cfg.causal and kv_x is None and kv_cache is None
    if cache is None and kv_cache is None:
        k_pos = None if kv_x is None else jnp.arange(k.shape[1])
        out, new_cache = attend(
            q, k, v, positions, k_pos, causal=causal, scale=scale,
            window=cfg.sliding_window, logit_softcap=cfg.logit_softcap,
            compute_dtype=compute_dtype), None
    else:
        out, new_cache = _cache_attend(
            cfg, q, k, v, positions, cache, kv_cache, kv_valid_len,
            causal=causal, scale=scale, compute_dtype=compute_dtype)
    out = out.reshape(B, S, h * hd)
    return dense_apply(p["wo"], out, compute_dtype), new_cache


def _pin(a):
    """a (B, S, heads, hd) in the decode caches' layout: batch over "data",
    head_dim over "model". Decode attention stays so end to end: a query
    laid out otherwise makes GSPMD all-gather the whole cached K/V per
    layer per token (measured 31 GB/chip/token on gemma2 decode_32k)."""
    return maybe_constrain(a, "data", None, None, "model")


def _to_cache(a, like):
    """New K or V rows cast to the dtype of the cache `like` and pinned to
    its layout BEFORE the update: otherwise GSPMD reshards the whole cache
    through collectives every decode step."""
    return _pin(a.astype(like.dtype))


def _per_row(positions, B, S):
    """positions (S,) or (B, S) as (B, S) int32."""
    return (positions if positions.ndim == 2
            else jnp.broadcast_to(positions, (B, S))).astype(jnp.int32)


def _cache_attend(cfg, q, k, v, positions, cache, kv_cache, kv_valid_len, *,
                  causal, scale, compute_dtype):
    """`attn_apply`'s attention when it is given a cache or kv_cache: the
    layout's function writes the new rows and returns what `attend` reads,
    always with key positions, so never the training kernel. Returns
    (out (B, S, H, hd), new_cache)."""
    S = q.shape[1]
    kv_len, cached, decode, new_cache = None, False, False, None
    if kv_cache is not None:
        k, v, k_pos = _cross_kv(kv_cache, q.shape[0], compute_dtype)
        q_pos = positions
    elif "table" in cache:
        out, view, new_cache = _paged(cfg, q, k, v, positions, cache,
                                      kv_valid_len, scale, compute_dtype)
        if out is not None:
            return out, new_cache
        q, k, v, q_pos, k_pos = view
        kv_len, cached, decode = kv_valid_len, True, True
    elif S > 1 and S >= cache["k"].shape[1]:
        new_cache = _ring_prefill(k, v, positions, cache)
        q_pos = k_pos = positions
    else:
        (q, k, v, q_pos, k_pos, kv_len), new_cache = _contiguous(
            q, k, v, positions, cache, kv_valid_len, compute_dtype)
        cached, decode = True, S == 1
    return attend(q, k, v, q_pos, k_pos, causal=causal, scale=scale,
                  window=cfg.sliding_window, logit_softcap=cfg.logit_softcap,
                  kv_len=kv_len, cached=cached, decode=decode,
                  compute_dtype=compute_dtype), new_cache


def _cross_kv(kv_cache, B, compute_dtype):
    """Read-only cross K/V (serving): projected ONCE (at encdec admission)
    and attended every decode step — wk/wv never run and nothing is
    written back. Two forms: dense {"k","v"[,"pos"]} with k/v (B, Sm, n_kv,
    hd), or paged {"k","v","pos","table"} where k/v are (n_blocks,
    block_size, n_kv, hd) arenas gathered through a (B, max_blocks) table
    exactly like the paged self-attention read path. Pad rows carry pos -1
    and mask out, so the gathered padded attention is bitwise the dense
    one (exp of a masked logit is exactly 0.0 in fp32). Returns (k, v,
    k_pos)."""
    if "table" in kv_cache:
        # Paged cross arena (serving/cache_pool.EncDecCachePool): the
        # same fixed-shape gather the paged self-attention read path
        # uses — blocks churn, the jitted step never recompiles.
        tbl = kv_cache["table"]                    # (B, max_blocks)
        _, bsz, kv, hd = kv_cache["k"].shape
        mem_len = tbl.shape[1] * bsz
        k = kv_cache["k"][tbl].reshape(B, mem_len, kv, hd)
        v = kv_cache["v"][tbl].reshape(B, mem_len, kv, hd)
        k_pos = kv_cache["pos"][tbl].reshape(B, mem_len)
    else:
        k, v = kv_cache["k"], kv_cache["v"]
        k_pos = kv_cache.get("pos")
        if k_pos is None:
            k_pos = jnp.arange(k.shape[1])
    return k.astype(compute_dtype), v.astype(compute_dtype), k_pos


def _paged(cfg, q, k, v, positions, cache, kv_valid_len, scale,
           compute_dtype):
    """Paged self-attention (serving/cache_pool.PagedCachePool): cache k/v
    are (n_blocks, block_size, kv, hd) arenas, pos is (n_blocks,
    block_size), table is (B, max_blocks) int32 arena indices with 0
    pointing at the reserved null block (pos -1 everywhere, so its rows
    are structurally masked). Logical row r of slot b lives at
    arena[table[b, r // bsz], r % bsz]; r = cursor % ring_len gives the
    sliding-window layers their ring semantics for free. The host-side
    allocator guarantees the block being written is exclusively owned
    (shared prefix blocks are never in the write path), so the scatter
    cannot race between slots — inactive slots all write the null block
    with position -1, which keeps it invalid. Everything is a fixed-shape
    gather/scatter: the jitted step never recompiles as blocks churn.
    S > 1 is the speculative-verify step: the S draft tokens of slot b
    land at logical rows cursor..cursor+S-1 (lazy growth backs them
    before the step; rejected rows are invalidated by a pos scatter
    after).

    Returns (out, None, new_cache) from the fused kernel (decode_kernel
    "paged"), else (None, (q, k, v, q_pos, k_pos), new_cache) for
    `attend`."""
    B, S = q.shape[:2]
    idx = cache["index"]                       # (B,) local cursors
    tbl = cache["table"]                       # (B, max_blocks)
    bsz = cache["k"].shape[1]
    ring_len = tbl.shape[1] * bsz
    k_new, v_new = _to_cache(k, cache["k"]), _to_cache(v, cache["v"])
    q_pos = _per_row(positions, B, S)
    q = _pin(q)
    if cfg.decode_kernel == "paged":
        # Fused Pallas path: the block table rides into the kernel as
        # a scalar-prefetch operand, K/V blocks stream HBM->VMEM
        # directly — no (B, ring_len, kv, hd) materialization — and
        # the K/V/pos scatter happens in the kernel EPILOGUE: arenas
        # are aliased in/out and come back updated, so the three XLA
        # arena round-trips below never exist on this path. Token
        # output matches the XLA gather below to fp32 summation-order
        # tolerance (both accumulate in fp32); the returned arenas
        # match the XLA scatter bit-for-bit on every data block (the
        # fused kernel never writes the null block — invalid rows
        # write NOTHING instead of null row 0; both keep the null
        # block's positions -1, so attention cannot see the
        # difference). See kernels/paged_attention_kernel.py.
        if kv_valid_len is not None:
            raise NotImplementedError(
                "kv_valid_len is unsupported on the paged kernel path")
        from repro.kernels.paged_attention_kernel import (
            paged_attention_fused)
        out, k_arena, v_arena, pos_arena = paged_attention_fused(
            q, k_new, v_new, cache["k"], cache["v"], cache["pos"],
            tbl, q_pos, idx,
            scale=scale, causal=cfg.causal, window=cfg.sliding_window,
            softcap=cfg.logit_softcap, interpret=cfg.kernel_interpret)
        new_cache = {"k": k_arena, "v": v_arena, "pos": pos_arena,
                     "index": idx + S}
        return _pin(out.astype(compute_dtype)), None, new_cache
    if cfg.decode_kernel != "xla":
        raise ValueError(f"unknown decode_kernel {cfg.decode_kernel!r}")
    r = jax.lax.rem(idx[:, None] + jnp.arange(S, dtype=jnp.int32),
                    ring_len)                  # (B, S) logical rows
    blk = jnp.take_along_axis(tbl, r // bsz, axis=1)
    off = jax.lax.rem(r, bsz)
    # Rows with a negative feed position (inactive slots; the padding
    # rows of a budget-truncated verify block) are routed to the null
    # block BY THE SCATTER, not just by their table being empty: a
    # truncated verify block's pad rows sit past the slot's live
    # chain, where the ring may map them onto real blocks — shared
    # prompt blocks included — that growth never COWed because no
    # real write ever reaches them. The null block's row 0 takes all
    # such writes, value -1, and stays invalid.
    blk = jnp.where(q_pos >= 0, blk, 0)
    off = jnp.where(q_pos >= 0, off, 0)
    k_arena = cache["k"].at[blk, off].set(k_new)
    v_arena = cache["v"].at[blk, off].set(v_new)
    pos_arena = cache["pos"].at[blk, off].set(q_pos)
    new_cache = {"k": k_arena, "v": v_arena, "pos": pos_arena,
                 "index": idx + S}
    # block-table gather: (B, max_blocks, bsz, ...) -> (B, ring_len, ...)
    k, v = (a[tbl].reshape(B, ring_len, *a.shape[2:]).astype(compute_dtype)
            for a in (k_arena, v_arena))
    k_pos = pos_arena[tbl].reshape(B, ring_len)
    return None, (q, k, v, q_pos, k_pos), new_cache


def _ring_prefill(k, v, positions, cache):
    """Prefill longer than a ring cache (sliding-window layer): `attend`
    reads the in-flight k/v, and only the LAST cache_len rows are written,
    rolled so that slot == write_cursor % cache_len — the invariant later
    decode steps rely on. Assumes idx == 0 (prefill from scratch), which
    is the only way the engine uses it. Returns the new cache."""
    B, S = k.shape[:2]
    idx = cache["index"]
    W = cache["k"].shape[1]
    shift = (S - W) % W
    k_tail = jnp.roll(k[:, S - W:S].astype(cache["k"].dtype), shift, axis=1)
    v_tail = jnp.roll(v[:, S - W:S].astype(cache["v"].dtype), shift, axis=1)
    # positions may be per-batch (left-padded prefill: pads carry pos<0
    # and stay masked for the lifetime of the cache entry)
    pos_tail = jnp.roll(_per_row(positions, B, S)[:, S - W:S], shift, axis=1)
    if jnp.ndim(idx) == 0:     # single-stream cache: one position row
        pos_tail = pos_tail[0]
    return {"k": k_tail, "v": v_tail, "pos": pos_tail, "index": idx + S}


def _contiguous(q, k, v, positions, cache, kv_valid_len, compute_dtype):
    """Incremental decode / prefill-into-cache: write the S new k/v rows
    at the write cursor. Ring-buffer caches (cache_len < model max_len;
    sliding-window layers) wrap the write slot and track absolute
    positions in cache["pos"]. Pooled (continuous-batching) caches carry a
    per-slot write cursor index: (B,) and per-slot positions pos: (B,
    cache_len); the single-stream cache keeps the scalar index and a
    shared (cache_len,) pos. Returns ((q, k, v, q_pos, k_pos, kv_len),
    new_cache)."""
    B, S = q.shape[:2]
    idx = cache["index"]
    cache_len = cache["k"].shape[1]
    k_new, v_new = _to_cache(k, cache["k"]), _to_cache(v, cache["v"])
    if jnp.ndim(idx) == 1:
        # Per-slot scatter: slot b writes rows idx[b]..idx[b]+S-1 (mod
        # cache_len). RoPE/mask positions come from `positions`, which
        # the serving engine sets to each slot's LOCAL time — rows of
        # evicted/previous occupants are wiped by cache-pool insertion,
        # so `pos >= 0 and causal` is the complete validity rule.
        rows = jax.lax.rem(idx[:, None]
                           + jnp.arange(S, dtype=jnp.int32), cache_len)
        brow = jnp.arange(B)[:, None]
        k_cache = cache["k"].at[brow, rows].set(k_new)
        v_cache = cache["v"].at[brow, rows].set(v_new)
        q_pos = _per_row(positions, B, S)
        pos_new = cache["pos"].at[brow, rows].set(q_pos)
    else:
        slot = jax.lax.rem(idx, cache_len)
        k_cache = jax.lax.dynamic_update_slice(cache["k"], k_new,
                                               (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cache["v"], v_new,
                                               (0, slot, 0, 0))
        pos_new = jax.lax.dynamic_update_slice(
            cache["pos"], (idx + jnp.arange(S, dtype=jnp.int32)), (slot,))
        q_pos = idx + jnp.arange(S)
        if kv_valid_len is None:
            kv_valid_len = jnp.full((B,), idx + S, jnp.int32)
    q = _pin(q)
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos_new, "index": idx + S}
    return (q, k_cache.astype(compute_dtype), v_cache.astype(compute_dtype),
            q_pos, pos_new, kv_valid_len), new_cache


def init_paged_kv_cache(n_blocks: int, block_size: int, n_kv: int,
                        head_dim: int, dtype=jnp.bfloat16):
    """Block arena for the paged serving cache (one attention slot-type).

    Unlike `init_kv_cache` there is no batch dim: slots reference blocks
    through a (max_batch, max_blocks) int32 table kept NEXT to the cache
    (see serving/cache_pool.PagedCachePool). Block 0 is the reserved null
    block — its positions stay -1 so unbacked table entries gather rows
    that are structurally masked.
    """
    return {
        "k": jnp.zeros((n_blocks, block_size, n_kv, head_dim), dtype),
        "v": jnp.zeros((n_blocks, block_size, n_kv, head_dim), dtype),
        "pos": jnp.full((n_blocks, block_size), -1, jnp.int32),
    }


def init_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16, *, per_slot: bool = False):
    """Contiguous cache; pass max_len = sliding_window for ring-buffer layers.

    per_slot=True builds the pooled (continuous-batching) layout: one write
    cursor and one position row per batch slot, so slots admitted at
    different times decode through a single fixed-shape jitted step.
    """
    pos_shape = (batch, max_len) if per_slot else (max_len,)
    idx_shape = (batch,) if per_slot else ()
    return {
        "k": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "pos": jnp.full(pos_shape, -1, jnp.int32),
        "index": jnp.zeros(idx_shape, jnp.int32),
    }
