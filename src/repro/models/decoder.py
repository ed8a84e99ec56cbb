"""Unified decoder-only LM covering the dense, MoE, VLM and hybrid archs.

The layer stack is described as a repeating **superblock**: a short, fixed
list of (mixer, ffn) slots. Examples:

  dense / moe     : [("attn", "mlp"|"moe")]                       x n_layers
  moonlight (MLA) : [("mla", "moe")] x (n_layers - 1), after one dense
                    ("mla", "mlp") layer (`first_k_dense`)
  gemma2          : [("attn_local", "mlp"), ("attn", "mlp")]      x n_layers/2
  jamba (hybrid)  : [("attn", "moe"), ("mamba", "mlp"), ...]      x n_layers/8

Parameters for each slot are stacked over the repeat dimension and the
forward pass is a single `jax.lax.scan` over periods (leading dense
layers, stacked under "lead", run before it, unrolled) — one trace per slot
type regardless of depth, which keeps the HLO small enough to compile 72-layer
398B configs in the dry-run. Caches (attention KV / SSM state) are likewise
stacked per slot and threaded through the scan as xs/ys.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import mamba2 as mamba_lib
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models.attention import AttnConfig, attn_apply, attn_init
from repro.models.common import (
    dense_apply,
    dense_init,
    embed_apply,
    embed_attend,
    embed_init,
    mlp_apply,
    mlp_init,
    rmsnorm_apply,
    rmsnorm_init,
    softcap,
)


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None

    # attention variants
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    sliding_window: Optional[int] = None       # window for "attn_local" slots
    attn_softcap: Optional[float] = None       # gemma2: 50.0
    final_softcap: Optional[float] = None      # gemma2: 30.0
    post_block_norm: bool = False              # gemma2 pre+post norms
    attn_kernel: str = "xla"                   # paged decode: "xla" | "paged"
    kernel_interpret: Optional[bool] = None    # Pallas interpret override

    # ffn
    activation: str = "silu"
    gated_mlp: bool = True

    # moe
    n_experts: int = 0                         # experts held here
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_d_ff: Optional[int] = None             # expert width; None: d_ff
    router: str = "softmax"                    # "softmax" | "sigmoid"
    routed_scaling: float = 1.0
    router_width: Optional[int] = None         # experts scored; None: all
    first_expert: int = 0                      # router index of first held
    n_shared_experts: int = 0
    seq_aux: bool = False                      # sequence-wise balance loss
    aux_weight: float = 0.01                   # of the balance loss

    # latent attention ("mla" slots)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # mamba slots (hybrid archs)
    mamba_d_inner: Optional[int] = None
    mamba_headdim: int = 64
    mamba_dstate: int = 128
    mamba_chunk: int = 64

    # superblock: sequence of (mixer, ffn) slot descriptors.
    #   mixer in {"attn", "attn_local", "mamba"}; ffn in {"mlp", "moe"}
    superblock: Tuple[Tuple[str, str], ...] = (("attn", "mlp"),)
    # leading dense layers before the periods: the first slot's mixer and
    # a d_ff-wide "mlp" each
    first_k_dense: int = 0
    rms_norm_eps: float = 1e-6

    tie_embeddings: bool = True
    scale_embeds: bool = False                 # gemma2: x *= sqrt(d_model)
    remat: bool = True                         # checkpoint each period in bwd
    max_seq: int = 8192
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_periods(self) -> int:
        stacked = self.n_layers - self.first_k_dense
        assert stacked % len(self.superblock) == 0, (
            self.n_layers, self.first_k_dense, self.superblock)
        return stacked // len(self.superblock)

    def attn_cfg(self, local: bool) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.resolved_head_dim,
            qkv_bias=self.qkv_bias, qk_norm=self.qk_norm, rope=True,
            rope_theta=self.rope_theta, causal=True,
            sliding_window=self.sliding_window if local else None,
            logit_softcap=self.attn_softcap,
            decode_kernel=self.attn_kernel,
            kernel_interpret=self.kernel_interpret)

    def moe_cfg(self) -> moe_lib.MoeConfig:
        return moe_lib.MoeConfig(
            d_model=self.d_model, d_ff=self.moe_d_ff or self.d_ff,
            n_experts=self.n_experts, top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            activation=self.activation, gated=self.gated_mlp,
            router=self.router, routed_scaling=self.routed_scaling,
            router_width=self.router_width, first_expert=self.first_expert,
            n_shared=self.n_shared_experts, seq_aux=self.seq_aux)

    def mla_cfg(self) -> mla_lib.MlaConfig:
        return mla_lib.MlaConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta)

    def mamba_cfg(self) -> mamba_lib.MambaConfig:
        return mamba_lib.MambaConfig(
            d_model=self.d_model,
            d_inner=self.mamba_d_inner or 2 * self.d_model,
            headdim=self.mamba_headdim, dstate=self.mamba_dstate,
            chunk=self.mamba_chunk)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _slot_init(rng, cfg: DecoderConfig, mixer: str, ffn: str):
    ks = jax.random.split(rng, 6)
    p = {"pre_mixer_norm": rmsnorm_init(cfg.d_model, cfg.param_dtype),
         "pre_ffn_norm": rmsnorm_init(cfg.d_model, cfg.param_dtype)}
    if cfg.post_block_norm:
        p["post_mixer_norm"] = rmsnorm_init(cfg.d_model, cfg.param_dtype)
        p["post_ffn_norm"] = rmsnorm_init(cfg.d_model, cfg.param_dtype)
    if mixer in ("attn", "attn_local"):
        p["mixer"] = attn_init(ks[0], cfg.attn_cfg(mixer == "attn_local"),
                               dtype=cfg.param_dtype)
    elif mixer == "mamba":
        p["mixer"] = mamba_lib.mamba_init(ks[0], cfg.mamba_cfg(), cfg.param_dtype)
    elif mixer == "mla":
        p["mixer"] = mla_lib.mla_init(ks[0], cfg.mla_cfg(), cfg.param_dtype)
    else:
        raise ValueError(mixer)
    if ffn == "mlp":
        p["ffn"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, gated=cfg.gated_mlp,
                            dtype=cfg.param_dtype)
    elif ffn == "moe":
        p["ffn"] = moe_lib.moe_init(ks[1], cfg.moe_cfg(), cfg.param_dtype)
    elif ffn == "none":    # pure-SSM archs (mamba2): mixer-only blocks
        p.pop("pre_ffn_norm")
        if cfg.post_block_norm:
            p.pop("post_ffn_norm")
    else:
        raise ValueError(ffn)
    return p


def decoder_init(rng, cfg: DecoderConfig):
    ks = jax.random.split(rng, 2 + len(cfg.superblock))
    params = {
        "embed": embed_init(ks[0], cfg.vocab, cfg.d_model, cfg.param_dtype),
        "final_norm": rmsnorm_init(cfg.d_model, cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], cfg.d_model, cfg.vocab,
                                       dtype=cfg.param_dtype)
    # stacked slot params: vmap init over the period dimension
    for si, (mixer, ffn) in enumerate(cfg.superblock):
        slot_rngs = jax.random.split(ks[2 + si], cfg.n_periods)
        params[f"slot{si}"] = jax.vmap(
            lambda r: _slot_init(r, cfg, mixer, ffn))(slot_rngs)
    if cfg.first_k_dense:
        lead_rngs = jax.random.split(jax.random.fold_in(rng, 1),
                                     cfg.first_k_dense)
        params["lead"] = jax.vmap(lambda r: _slot_init(
            r, cfg, cfg.superblock[0][0], "mlp"))(lead_rngs)
    return params


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _run_slot(slot_params, cfg: DecoderConfig, mixer: str, ffn: str, x,
              positions, cache, kv_valid_len, valid=None):
    """One (mixer, ffn) slot. cache may be None. Returns (x, new_cache, aux).

    valid: optional (B, S) bool — False marks left-padding whose state
    contributions must be suppressed (attention masks pads by their
    negative positions; mamba/moe need the explicit mask)."""
    aux = {}
    eps = cfg.rms_norm_eps
    h = rmsnorm_apply(slot_params["pre_mixer_norm"], x, eps)
    if mixer in ("attn", "attn_local"):
        out, new_cache = attn_apply(
            slot_params["mixer"], cfg.attn_cfg(mixer == "attn_local"), h,
            positions=positions, cache=cache, kv_valid_len=kv_valid_len,
            compute_dtype=cfg.compute_dtype)
    elif mixer == "mla":
        out, new_cache = mla_lib.mla_apply(
            slot_params["mixer"], cfg.mla_cfg(), h, positions=positions,
            cache=cache, compute_dtype=cfg.compute_dtype)
    else:
        out, new_cache = mamba_lib.mamba_apply(
            slot_params["mixer"], cfg.mamba_cfg(), h, cache=cache,
            valid=valid, compute_dtype=cfg.compute_dtype)
    if cfg.post_block_norm:
        out = rmsnorm_apply(slot_params["post_mixer_norm"], out, eps)
    x = x + out

    if ffn == "none":
        return x, new_cache, aux

    h = rmsnorm_apply(slot_params["pre_ffn_norm"], x, eps)
    if ffn == "mlp":
        with jax.named_scope("ffn"):
            out = mlp_apply(slot_params["ffn"], h, activation=cfg.activation,
                            compute_dtype=cfg.compute_dtype)
    else:
        out, moe_aux = moe_lib.moe_apply(slot_params["ffn"], cfg.moe_cfg(), h,
                                         valid=valid,
                                         compute_dtype=cfg.compute_dtype)
        aux.update(moe_aux)
    if cfg.post_block_norm:
        out = rmsnorm_apply(slot_params["post_ffn_norm"], out, eps)
    x = x + out
    return x, new_cache, aux


def decoder_apply(params, cfg: DecoderConfig, tokens=None, *, embeds=None,
                  positions=None, caches=None, kv_valid_len=None,
                  return_hidden=False):
    """Forward pass.

    tokens: (B, S) int32, or embeds: (B, S, d) precomputed (VLM/audio stubs).
    caches: model cache from init_decoder_cache (decode) or None (train).
    Returns (logits, new_caches, aux_dict); with return_hidden=True the
    first element is the final-norm hidden states instead (big-vocab loss
    path computes logits chunkwise — see chunked_lm_loss).
    """
    assert (tokens is None) != (embeds is None)
    with jax.named_scope("embed"):
        if embeds is None:
            x = embed_apply(params["embed"], tokens, cfg.compute_dtype)
            if cfg.scale_embeds:
                x = x * jnp.asarray(jnp.sqrt(float(cfg.d_model)),
                                    cfg.compute_dtype)
        else:
            x = embeds.astype(cfg.compute_dtype)
    B, S = x.shape[:2]
    if positions is None:
        base = caches["index"] if caches is not None else 0
        if caches is not None and jnp.ndim(caches["index"]) == 1:
            positions = base[:, None] + jnp.arange(S)  # per-slot cursors (B, S)
        else:
            positions = base + jnp.arange(S)
    # Per-batch positions mark left-padding with negative values: attention
    # masks those keys structurally (k_pos >= 0); mamba/moe need the mask.
    valid = (positions >= 0) if jnp.ndim(positions) == 2 else None

    aux_acc = {"moe_aux_loss": jnp.zeros((), jnp.float32),
               "router_entropy": jnp.zeros((), jnp.float32)}

    # Paged serving cache: block tables are read-only in the model (the
    # host-side allocator owns them) and identical across periods, so they
    # ride into the scan as captured constants rather than scanned leaves.
    tables = caches.get("tables") if isinstance(caches, dict) else None

    def period_step(carry, xs):
        x = carry
        slot_params, slot_caches = xs
        new_caches = []
        aux_out = dict(aux_acc)
        for si, (mixer, ffn) in enumerate(cfg.superblock):
            cache_i = None
            if slot_caches is not None:
                cache_i = dict(slot_caches[si])
                cache_i["index"] = caches["index"]
                if tables is not None and tables[si] is not None:
                    cache_i["table"] = tables[si]
            x, nc, aux = _run_slot(
                slot_params[si], cfg, mixer, ffn, x, positions,
                cache_i, kv_valid_len, valid)
            if nc is not None:
                nc.pop("index")
                new_caches.append(nc)
            for k, v in aux.items():
                aux_out[k] = aux_out[k] + v
        ys = (tuple(new_caches) if new_caches else None, aux_out)
        return x, ys

    slot_param_stacks = tuple(params[f"slot{si}"]
                              for si in range(len(cfg.superblock)))
    slot_cache_stacks = None
    if caches is not None:
        slot_cache_stacks = tuple(caches["slots"][si]
                                  for si in range(len(cfg.superblock)))

    step = period_step
    if cfg.remat and caches is None:
        # full per-period rematerialization: only the carried activations
        # survive to the backward pass (the config every >10B framework uses)
        step = jax.checkpoint(period_step,
                              policy=jax.checkpoint_policies.nothing_saveable)
    with jax.named_scope("layers"):
        if cfg.first_k_dense:
            if caches is not None:
                raise NotImplementedError("leading dense layers: no cache")

            def lead(lead_params, x):
                return _run_slot(lead_params, cfg, cfg.superblock[0][0],
                                 "mlp", x, positions, None, None, valid)[0]

            if cfg.remat:
                lead = jax.checkpoint(
                    lead, policy=jax.checkpoint_policies.nothing_saveable)
            for i in range(cfg.first_k_dense):
                x = lead(jax.tree.map(lambda a: a[i], params["lead"]), x)
        x, (new_cache_stacks, aux_stacks) = jax.lax.scan(
            step, x, (slot_param_stacks, slot_cache_stacks))

    new_caches = None
    if caches is not None and new_cache_stacks is not None:
        new_caches = {"slots": tuple(new_cache_stacks),
                      "index": caches["index"] + S}
        if tables is not None:
            new_caches["tables"] = tables    # pass-through: host-owned

    aux = {k: jnp.sum(v) for k, v in aux_stacks.items()}

    with jax.named_scope("head"):
        x = rmsnorm_apply(params["final_norm"], x, cfg.rms_norm_eps)
    if return_hidden:
        return x, new_caches, aux
    logits = _head_logits(params, cfg, x)
    return logits, new_caches, aux


@jax.named_scope("head")
def _head_logits(params, cfg: DecoderConfig, x):
    if cfg.tie_embeddings:
        logits = embed_attend(params["embed"], x, cfg.compute_dtype)
    else:
        logits = dense_apply(params["lm_head"], x, cfg.compute_dtype)
    return softcap(logits.astype(jnp.float32), cfg.final_softcap)


def init_decoder_cache(cfg: DecoderConfig, batch: int, max_len: int,
                       dtype=jnp.bfloat16, *, per_slot: bool = False,
                       clamp_window: bool = True):
    """Stacked per-slot caches. attn_local slots get ring buffers of the
    window size — the memory win that makes long_500k viable for gemma2.

    per_slot=True builds the pooled continuous-batching layout: the write
    cursor becomes (batch,) and KV positions (batch, L), so each batch slot
    carries its own local timeline (see serving/cache_pool.py).

    clamp_window=False gives attn_local slots the FULL max_len rows too —
    the chunk-resumable prefill cache: every prompt chunk then lands in
    attention's incremental write path (never the roll-on-overflow branch,
    which assumes a from-scratch prefill and cannot resume), window
    locality is enforced by the mask instead of the ring, and the
    serving pool's insert picks the window tail out of the full-length
    rows (see serving/admission.py)."""
    slots = []
    for mixer, _ in cfg.superblock:
        if mixer == "mamba":
            one = mamba_lib.init_mamba_cache(batch, cfg.mamba_cfg())
        else:
            L = max_len
            if clamp_window and mixer == "attn_local" and cfg.sliding_window:
                L = min(max_len, cfg.sliding_window)
            one = attn_lib.init_kv_cache(batch, L, cfg.n_kv_heads,
                                         cfg.resolved_head_dim, dtype,
                                         per_slot=per_slot)
        one.pop("index")
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape).copy(), one)
        slots.append(stacked)
    index = (jnp.zeros((batch,), jnp.int32) if per_slot
             else jnp.zeros((), jnp.int32))
    return {"slots": tuple(slots), "index": index}


def paged_layout(cfg: DecoderConfig, max_len: int, block_size: int,
                 row_margin: int = 0):
    """Per-superblock-slot paged layout: [(slot_idx, ring_len) | None].

    Attention slots page their KV through a block arena; the entry gives
    the slot's logical ring length (max_len, or the sliding window for
    "attn_local" slots). Mamba slots return None: their state is O(1) per
    slot (a fixed SSM tensor + conv tail), so paging buys nothing and
    they stay slot-resident (see init_paged_decoder_cache).

    row_margin > 0 widens EVERY attention ring by that many rows
    (rounded up to whole blocks): the speculative verify step scatters
    its K rows BEFORE attention runs, so the ring must hold K - 1 rows
    beyond what any live query still attends to. Sliding-window rings
    need window + K - 1 or the burst overwrites in-window keys of
    earlier query rows; full rings need max_len + K - 1 because a
    budget-truncated final round still scatters (position -1) rows up to
    cursor + K - 1, which a bare max_len ring would wrap onto the
    slot's first prompt blocks mid-verify.
    """
    margin = -(-row_margin // block_size) * block_size if row_margin else 0
    out = []
    for si, (mixer, _) in enumerate(cfg.superblock):
        if mixer == "mamba":
            out.append(None)
            continue
        L = max_len + margin
        if mixer == "attn_local" and cfg.sliding_window:
            L = min(max_len, cfg.sliding_window) + margin
        if L % block_size != 0:
            raise ValueError(
                f"slot {si} ({mixer}): cache length {L} not a multiple of "
                f"block_size {block_size}")
        out.append((si, L))
    return out


def init_paged_decoder_cache(cfg: DecoderConfig, batch: int, max_len: int,
                             *, block_size: int, n_blocks,
                             dtype=jnp.bfloat16, row_margin: int = 0):
    """Paged continuous-batching cache: block arenas + per-slot tables.

    Layout (vs the dense per_slot layout of init_decoder_cache):
      attention slots: k/v/pos become (n_periods, n_blocks, block_size,
        ...) ARENAS with no batch dim; a (batch, ring_len // block_size)
        int32 block table per slot-type (under "tables", index 0 = the
        reserved null block) maps each decode slot's logical rows onto
        arena blocks, so identical prompt prefixes are stored once and
        shared across slots.
      mamba slots: unchanged (n_periods, batch, ...) slot-resident state.
      index: (batch,) per-slot LOCAL write cursors (== tokens seen, with
        no left-pad offset — the paged chain is position-aligned).

    n_blocks: data blocks per attention arena — an int (same for every
    attention slot-type) or a dict {slot_idx: int}. One extra null block
    is always added.
    """
    layouts = paged_layout(cfg, max_len, block_size, row_margin)
    slots, tables = [], []
    for si, (mixer, _) in enumerate(cfg.superblock):
        layout = layouts[si]
        if layout is None:
            one = mamba_lib.init_mamba_cache(batch, cfg.mamba_cfg())
            one.pop("index")
            stacked = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a, (cfg.n_periods,) + a.shape).copy(), one)
            slots.append(stacked)
            tables.append(None)
            continue
        _, ring_len = layout
        nb = n_blocks[si] if isinstance(n_blocks, dict) else n_blocks
        one = attn_lib.init_paged_kv_cache(
            nb + 1, block_size, cfg.n_kv_heads, cfg.resolved_head_dim,
            dtype)
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(
                a, (cfg.n_periods,) + a.shape).copy(), one)
        slots.append(stacked)
        tables.append(jnp.zeros((batch, ring_len // block_size), jnp.int32))
    return {"slots": tuple(slots), "tables": tuple(tables),
            "index": jnp.zeros((batch,), jnp.int32)}


# --------------------------------------------------------------------------
# losses / steps
# --------------------------------------------------------------------------

@jax.named_scope("loss")
def lm_loss(logits, labels, *, ignore_id: int = -100,
            moe_aux: Optional[jnp.ndarray] = None, aux_weight: float = 0.01):
    """Next-token cross entropy; labels already shifted by the data pipeline."""
    valid = labels != ignore_id
    labels_safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels_safe[..., None], axis=-1)[..., 0]
    loss = -jnp.sum(ll * valid) / jnp.maximum(jnp.sum(valid), 1)
    if moe_aux is not None:
        loss = loss + aux_weight * moe_aux
    return loss


def chunked_lm_loss(params, cfg: DecoderConfig, hidden, labels, *,
                    ignore_id: int = -100, chunk: int = 512,
                    moe_aux: Optional[jnp.ndarray] = None,
                    aux_weight: float = 0.01):
    """Cross entropy without materializing (B, S, V) logits.

    Scans remat'd sequence chunks: per-chunk logits peak at (B, chunk, V)
    and are recomputed in the backward pass — the memory fix that lets the
    152k/256k-vocab archs fit HBM at train_4k (see EXPERIMENTS.md §Perf).
    """
    B, S, _ = hidden.shape
    assert S % chunk == 0, (S, chunk)
    n = S // chunk
    h_chunks = hidden.reshape(B, n, chunk, -1).swapaxes(0, 1)
    l_chunks = labels.reshape(B, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    @jax.named_scope("loss")
    def one(h, lab):
        logits = _head_logits(params, cfg, h)
        valid = lab != ignore_id
        safe = jnp.where(valid, lab, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        return -jnp.sum(ll * valid), jnp.sum(valid)

    def body(carry, xs):
        tot, cnt = carry
        h, lab = xs
        s, c = one(h, lab)
        return (tot + s, cnt + c), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (h_chunks, l_chunks))
    loss = total / jnp.maximum(count, 1)
    if moe_aux is not None:
        loss = loss + aux_weight * moe_aux
    return loss
