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

Shapes: x (B, S, d_model); cache k/v (B, max_len, n_kv, head_dim).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import NEG_INF  # single-sourced masked-logit value
from repro.models.common import (apply_rope, dense_apply, dense_init,
                                 maybe_constrain, rmsnorm_apply,
                                 rmsnorm_init, softcap)


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
    # Query-block chunking bound: sequences >= this use the remat-chunked
    # attention path (bounds the live S x S logits to q_block x S — the
    # XLA-level flash-attention analogue that makes prefill_32k fit).
    q_chunk_threshold: int = 4096
    q_block: int = 1024
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


@jax.named_scope("attention")
def attn_apply(
    p,
    cfg: AttnConfig,
    x,
    *,
    kv_x=None,                 # cross-attention memory (B, Sm, d)
    positions=None,            # (B, S) or (S,) absolute positions of x
    cache=None,                # dict(k, v, index) for incremental decode
    kv_cache=None,             # READ-ONLY precomputed cross K/V (serving)
    kv_valid_len=None,         # (B,) valid cache length (incl. new tokens)
    compute_dtype=jnp.bfloat16,
):
    """Returns (out, new_cache). new_cache is None unless cache is given.

    kv_cache is the serving twin of kv_x: the cross-attention K/V were
    projected ONCE (at encdec admission) and are attended read-only every
    decode step — wk/wv never run here and nothing is written back. Two
    forms: dense {"k","v"[,"pos"]} with k/v (B, Sm, n_kv, hd), or paged
    {"k","v","pos","table"} where k/v are (n_blocks, block_size, n_kv,
    hd) arenas gathered through a (B, max_blocks) table exactly like the
    paged self-attention read path. Pad rows carry pos -1 and mask out,
    so the gathered padded attention is bitwise the dense one (exp of a
    masked logit is exactly 0.0 in fp32). Mutually exclusive with kv_x
    and cache.
    """
    B, S, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    scale = cfg.query_scale if cfg.query_scale is not None else 1.0 / math.sqrt(hd)

    q = _split_heads(dense_apply(p["wq"], x, compute_dtype), h, hd)
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

    new_cache = None
    attend_cached = cache is not None
    # Pooled (continuous-batching) caches carry a per-slot write cursor
    # index: (B,) and per-slot positions pos: (B, cache_len); the classic
    # single-stream cache keeps the scalar index / shared (cache_len,) pos.
    # Paged pooled caches additionally carry a block table: k/v/pos are
    # block ARENAS shared by every slot, and "table" maps each slot's
    # logical rows onto arena blocks.
    pooled = cache is not None and jnp.ndim(cache["index"]) == 1
    paged = cache is not None and "table" in cache
    if kv_cache is not None:
        if cache is not None or kv_x is not None:
            raise ValueError("kv_cache is exclusive with cache/kv_x")
        if "table" in kv_cache:
            # Paged cross arena (serving/cache_pool.EncDecCachePool): the
            # same fixed-shape gather the paged self-attention read path
            # uses — blocks churn, the jitted step never recompiles.
            tbl = kv_cache["table"]                    # (B, max_blocks)
            bsz = kv_cache["k"].shape[1]
            mem_len = tbl.shape[1] * bsz
            k = kv_cache["k"][tbl].reshape(B, mem_len, kv, hd)
            v = kv_cache["v"][tbl].reshape(B, mem_len, kv, hd)
            k_pos = kv_cache["pos"][tbl].reshape(B, mem_len)
        else:
            k, v = kv_cache["k"], kv_cache["v"]
            k_pos = kv_cache.get("pos")
            if k_pos is None:
                k_pos = jnp.arange(k.shape[1])
        k = k.astype(compute_dtype)
        v = v.astype(compute_dtype)
        q_pos = positions
    elif paged:
        # Paged decode (serving/cache_pool.PagedCachePool): cache k/v are
        # (n_blocks, block_size, kv, hd) arenas, pos is (n_blocks,
        # block_size), table is (B, max_blocks) int32 arena indices with 0
        # pointing at the reserved null block (pos -1 everywhere, so its
        # rows are structurally masked). Logical row r of slot b lives at
        # arena[table[b, r // bsz], r % bsz]; r = cursor % ring_len gives
        # the sliding-window layers their ring semantics for free. The
        # host-side allocator guarantees the block being written is
        # exclusively owned (shared prefix blocks are never in the write
        # path), so the scatter below cannot race between slots —
        # inactive slots all write the null block with position -1, which
        # keeps it invalid. Everything is a fixed-shape gather/scatter:
        # the jitted step never recompiles as blocks churn. S > 1 is the
        # speculative-verify step: the S draft tokens of slot b land at
        # logical rows cursor..cursor+S-1 (lazy growth backs them before
        # the step; rejected rows are invalidated by a pos scatter after).
        idx = cache["index"]                       # (B,) local cursors
        tbl = cache["table"]                       # (B, max_blocks)
        bsz = cache["k"].shape[1]
        ring_len = tbl.shape[1] * bsz
        k_new = maybe_constrain(k.astype(cache["k"].dtype),
                                "data", None, None, "model")
        v_new = maybe_constrain(v.astype(cache["v"].dtype),
                                "data", None, None, "model")
        q_pos = (positions if positions.ndim == 2
                 else jnp.broadcast_to(positions, (B, S))).astype(jnp.int32)
        q = maybe_constrain(q, "data", None, None, "model")
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
            out = out.astype(compute_dtype)
            out = maybe_constrain(out, "data", None, None, "model")
            out = out.reshape(B, S, h * hd)
            return dense_apply(p["wo"], out, compute_dtype), new_cache
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
        k = k_arena[tbl].reshape(B, ring_len, kv, hd).astype(compute_dtype)
        v = v_arena[tbl].reshape(B, ring_len, kv, hd).astype(compute_dtype)
        k_pos = pos_arena[tbl].reshape(B, ring_len)
    elif cache is not None and S > 1 and S >= cache["k"].shape[1]:
        attend_cached = False  # attend in-flight; cache write is tail-only
        # Prefill longer than a ring cache (sliding-window layer): attend
        # the in-flight k/v (standard masking below) and write only the
        # LAST cache_len rows, rolled so that slot == write_cursor %
        # cache_len — the invariant later decode steps rely on. Assumes
        # idx == 0 (prefill from scratch), which is the only way the
        # engine uses it.
        idx = cache["index"]
        cache_len = cache["k"].shape[1]
        W = cache_len
        shift = (S - W) % cache_len
        k_tail = jnp.roll(k[:, S - W:S].astype(cache["k"].dtype), shift, axis=1)
        v_tail = jnp.roll(v[:, S - W:S].astype(cache["v"].dtype), shift, axis=1)
        # positions may be per-batch (left-padded prefill: pads carry pos<0
        # and stay masked for the lifetime of the cache entry)
        pos_src = (positions if positions.ndim == 2
                   else jnp.broadcast_to(positions, (B, S))).astype(jnp.int32)
        pos_tail = jnp.roll(pos_src[:, S - W:S], shift, axis=1)
        if not pooled:
            pos_tail = pos_tail[0]
        new_cache = {"k": k_tail, "v": v_tail, "pos": pos_tail,
                     "index": idx + S}
        k_pos = positions
        q_pos = positions
    elif cache is not None:
        # Incremental decode / prefill-into-cache: write the S new k/v rows
        # at the write cursor. Ring-buffer caches (cache_len < model max_len;
        # sliding-window layers) wrap the write slot and track absolute
        # positions in cache["pos"].
        idx = cache["index"]  # scalar int32, or (B,) per-slot cursors
        cache_len = cache["k"].shape[1]
        # Pin the incoming rows to the cache layout (batch over data, head_dim
        # over model) BEFORE the update: otherwise GSPMD reshards the whole
        # cache through collectives every decode step (EXPERIMENTS.md iter 4).
        k_new = maybe_constrain(k.astype(cache["k"].dtype),
                                "data", None, None, "model")
        v_new = maybe_constrain(v.astype(cache["v"].dtype),
                                "data", None, None, "model")
        if pooled:
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
            q_pos = (positions if positions.ndim == 2
                     else jnp.broadcast_to(positions, (B, S))).astype(jnp.int32)
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
        # Decode attention stays head_dim-sharded end to end: q must match,
        # else GSPMD all-gathers the whole cached K/V per layer per token
        # (measured 31 GB/chip/token on gemma2 decode_32k — iter 4).
        q = maybe_constrain(q, "data", None, None, "model")
        new_cache = {"k": k_cache, "v": v_cache, "pos": pos_new, "index": idx + S}
        k, v = k_cache.astype(compute_dtype), v_cache.astype(compute_dtype)
        k_pos = pos_new
    else:
        k_pos = jnp.arange(k.shape[1]) if kv_x is not None else positions
        q_pos = positions

    # GQA: repeat kv heads up to h.
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)

    causal = cfg.causal and kv_x is None and kv_cache is None
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
    decode = attend_cached and (S == 1 or paged)
    acc_dtype = jnp.float32 if decode else None
    probs_dtype = jnp.float32 if decode else compute_dtype

    def _attend_block(qb, q_pos_b, kv_len):
        logits = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                            preferred_element_type=acc_dtype) * scale
        logits = softcap(logits, cfg.logit_softcap)
        logits = _mask_logits(
            logits.astype(jnp.float32), q_pos_b, k_pos,
            causal=causal, window=cfg.sliding_window,
            kv_valid_len=kv_len)
        probs = jax.nn.softmax(logits, axis=-1).astype(probs_dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                       preferred_element_type=acc_dtype).astype(compute_dtype)
        if decode:
            # keep decode attention head_dim-sharded (see cache note above)
            o = maybe_constrain(o, "data", None, None, "model")
        return o

    kv_len = kv_valid_len if attend_cached else None
    qb = cfg.q_block
    if not attend_cached and S >= cfg.q_chunk_threshold and S % qb == 0:
        # remat-chunked query blocks: live logits bounded to (B,H,qb,S) and
        # the backward pass recomputes per-block probs instead of saving them.
        q_blocks = q.reshape(B, S // qb, qb, h, hd).swapaxes(0, 1)
        if q_pos.ndim == 2:   # per-batch positions (left-padded serving prefill)
            qpos_blocks = q_pos.reshape(B, S // qb, qb).swapaxes(0, 1)
        else:
            qpos_blocks = q_pos.reshape(S // qb, qb)
        blk = jax.checkpoint(lambda qq, pp: _attend_block(qq, pp, kv_len))
        out = jax.lax.map(lambda args: blk(*args), (q_blocks, qpos_blocks))
        out = out.swapaxes(0, 1).reshape(B, S, h, hd)
    else:
        out = _attend_block(q, q_pos, kv_len)
    out = out.reshape(B, S, h * hd)
    out = dense_apply(p["wo"], out, compute_dtype)
    return out, new_cache


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
