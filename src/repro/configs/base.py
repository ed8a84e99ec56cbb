"""Arch abstraction: binds a model family to the assigned input shapes.

Every assigned architecture file exports ``ARCH = Arch(...)`` built from the
exact public config. ``Arch`` dispatches init / loss / prefill / decode on
the model kind and provides ShapeDtypeStruct ``input_specs`` for the
dry-run (no allocation, weak-type-correct).

The four assigned input shapes:
  train_4k     seq 4096    global_batch 256   -> train_step
  prefill_32k  seq 32768   global_batch 32    -> prefill_step
  decode_32k   seq 32768   global_batch 128   -> serve_step (1 new token)
  long_500k    seq 524288  global_batch 1     -> serve_step; sub-quadratic
                                                 archs only (see DESIGN.md)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import bert as bert_lib
from repro.models import decoder as dec_lib
from repro.models import encdec as ed_lib


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    kind: str                      # "decoder" | "encdec" | "bert"
    cfg: Any
    source: str                    # citation for the config
    zero3: bool = False            # FSDP over the data axis (>=100B params)
    zero1: bool = False            # ZeRO-1: shard only optimizer moments
    long_context_ok: bool = False  # sub-quadratic / windowed: run long_500k
    embeds_input: bool = False     # VLM/audio stub: model consumes embeddings
    train_microbatches: int = 4    # grad-accum splits of the global batch
    notes: str = ""

    # ---------------- model dispatch ----------------

    def init(self, rng):
        if self.kind == "decoder":
            return dec_lib.decoder_init(rng, self.cfg)
        if self.kind == "encdec":
            return ed_lib.encdec_init(rng, self.cfg)
        if self.kind == "bert":
            return bert_lib.bert_init(rng, self.cfg)
        raise ValueError(self.kind)

    def loss_fn(self, params, batch):
        """(loss, aux) for one batch — what train_step differentiates."""
        if self.kind == "decoder":
            big_vocab = self.cfg.vocab >= 65536
            seq = batch["labels"].shape[1]
            chunked = big_vocab and seq >= 1024 and seq % 512 == 0
            kw = (dict(embeds=batch["embeds"])
                  if self.embeds_input and "embeds" in batch
                  else dict(tokens=batch["tokens"]))
            if chunked:
                hidden, _, aux = dec_lib.decoder_apply(
                    params, self.cfg, return_hidden=True, **kw)
                loss = dec_lib.chunked_lm_loss(
                    params, self.cfg, hidden, batch["labels"],
                    moe_aux=aux.get("moe_aux_loss"),
                    aux_weight=self.cfg.aux_weight)
            else:
                logits, _, aux = dec_lib.decoder_apply(params, self.cfg, **kw)
                loss = dec_lib.lm_loss(logits, batch["labels"],
                                       moe_aux=aux.get("moe_aux_loss"),
                                       aux_weight=self.cfg.aux_weight)
            return loss, {"router_entropy": aux.get("router_entropy", 0.0)}
        if self.kind == "encdec":
            logits = ed_lib.encdec_apply(params, self.cfg,
                                         batch["frames"], batch["tokens"])
            loss = dec_lib.lm_loss(logits, batch["labels"])
            return loss, {}
        if self.kind == "bert":
            return bert_lib.bert_pretrain_loss(params, self.cfg, batch)
        raise ValueError(self.kind)

    # ---------------- serving ----------------

    def init_cache(self, batch: int, max_len: int, *, per_slot: bool = False,
                   clamp_window: bool = True):
        if self.kind == "decoder":
            return dec_lib.init_decoder_cache(self.cfg, batch, max_len,
                                              per_slot=per_slot,
                                              clamp_window=clamp_window)
        if self.kind == "encdec":
            return ed_lib.init_encdec_cache(self.cfg, batch, max_len,
                                            dtype=self.cfg.compute_dtype,
                                            per_slot=per_slot)
        raise ValueError(f"{self.kind} has no decode cache")

    def init_paged_cache(self, batch: int, max_len: int, *,
                         block_size: int = 16, n_blocks=None,
                         row_margin: int = 0):
        """Paged (block-arena) serving cache — decoder-only.

        n_blocks defaults to the dense-equivalent budget: `batch` slots'
        worth of blocks per attention slot-type (ring length // block
        size each), so a no-sharing workload fits exactly as many slots
        as the dense pool while shared prompt prefixes fit more.
        row_margin widens sliding-window rings for speculative K-row
        verify bursts — see models/decoder.paged_layout.
        """
        if self.kind != "decoder":
            raise NotImplementedError("paged serving is decoder-only")
        if n_blocks is None:
            layout = dec_lib.paged_layout(self.cfg, max_len, block_size,
                                          row_margin)
            n_blocks = {si: batch * (ring // block_size)
                        for si, ring in filter(None, layout)}
        return dec_lib.init_paged_decoder_cache(
            self.cfg, batch, max_len, block_size=block_size,
            n_blocks=n_blocks, row_margin=row_margin)

    def paged_cache_specs(self, shape_name: str, *, block_size: int = 16):
        """Abstract paged cache for the dry-run decode shapes — the HLO
        the production mesh actually serves (block-table gather included).

        Arenas are sized one null block short of the dense-equivalent
        budget so the total blocks dim stays divisible by the data axis —
        that is the dim the pool shards across chips."""
        shape = SHAPES[shape_name]
        layout = dec_lib.paged_layout(self.cfg, shape.seq_len, block_size)
        n_blocks = {si: shape.global_batch * (ring // block_size) - 1
                    for si, ring in filter(None, layout)}
        return jax.eval_shape(
            lambda: self.init_paged_cache(shape.global_batch, shape.seq_len,
                                          block_size=block_size,
                                          n_blocks=n_blocks))

    def prefill(self, params, batch, *, cache_len: Optional[int] = None,
                per_slot: bool = False, positions=None):
        """Full-sequence forward with cache writes -> (last_logits, cache).

        cache_len > prompt length leaves room for subsequent decode steps.
        per_slot=True uses the pooled cache layout (per-batch cursors);
        positions (B, S) overrides the default 0..S-1 timeline — left-padded
        batches pass local positions with pads < 0 so padding is masked out
        of attention/SSM/MoE state (left-pad invariant prefill).
        """
        if self.kind == "decoder":
            toks = batch["tokens"]
            cache = dec_lib.init_decoder_cache(
                self.cfg, toks.shape[0], cache_len or toks.shape[1],
                per_slot=per_slot)
            logits, cache, _ = dec_lib.decoder_apply(
                params, self.cfg, toks, caches=cache, positions=positions)
            return logits[:, -1:], cache
        if self.kind == "encdec":
            toks = batch["tokens"]
            if per_slot:
                # Pooled serving admission: encode + one-time cross K/V
                # projection + prompt prefill into per-slot caches.
                return ed_lib.prefill_serve(
                    params, self.cfg, toks, positions, batch["frames"],
                    cache_len or toks.shape[1])
            memory = ed_lib.encode(params, self.cfg, batch["frames"])
            cache = ed_lib.init_encdec_cache(
                self.cfg, toks.shape[0], cache_len or toks.shape[1])
            logits, cache = ed_lib.decode(params, self.cfg, toks, memory,
                                          caches=cache)
            return logits[:, -1:], cache
        raise ValueError(f"{self.kind} does not serve")

    def decode_step(self, params, batch, cache):
        """One new token against the cache -> (logits, new_cache).

        batch may carry "positions" (B, S) — per-slot local timelines for
        the pooled serving cache (defaults to the cache write cursor).
        """
        if self.kind == "decoder":
            logits, cache, _ = dec_lib.decoder_apply(
                params, self.cfg, batch["tokens"], caches=cache,
                positions=batch.get("positions"))
            return logits, cache
        if self.kind == "encdec":
            if "slots" in cache:
                # Pooled serving layout: cross K/V ride inside the cache
                # (dense or paged arena) — no per-step memory operand.
                return ed_lib.decode_serve(params, self.cfg,
                                           batch["tokens"],
                                           batch["positions"], cache)
            return ed_lib.decode(params, self.cfg, batch["tokens"],
                                 batch["memory"], caches=cache)
        raise ValueError(f"{self.kind} does not serve")

    def score(self, params, tokens, positions):
        """Batched scoring forward (BERT family) -> (mlm_ids, pooled).

        tokens/positions (B, S) left-padded (pads < 0): masked-LM argmax
        ids per position plus the fp32 tanh-pooled [CLS] embedding — the
        serving engine's score/embed step (no KV cache, no growth).
        """
        if self.kind != "bert":
            raise ValueError(f"{self.kind} has no scoring forward")
        return bert_lib.bert_serve_outputs(params, self.cfg, tokens,
                                           positions)

    # ---------------- dry-run input specs ----------------

    def supports(self, shape_name: str) -> bool:
        shape = SHAPES[shape_name]
        if self.kind == "bert" and shape.kind != "train":
            return False
        if shape.name == "long_500k":
            return self.long_context_ok
        return True

    def input_specs(self, shape_name: str) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of this shape."""
        shape = SHAPES[shape_name]
        B, S = shape.global_batch, shape.seq_len
        i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
        f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)

        if self.kind == "bert":
            return {"tokens": i32((B, S)), "token_types": i32((B, S)),
                    "mlm_labels": i32((B, S)), "nsp_labels": i32((B,))}

        if self.kind == "encdec":
            frames = f32((B, self.cfg.n_frames, self.cfg.d_model))
            if shape.kind == "train":
                return {"frames": frames, "tokens": i32((B, S)),
                        "labels": i32((B, S))}
            if shape.kind == "prefill":
                return {"frames": frames, "tokens": i32((B, S))}
            return {"tokens": i32((B, 1)),
                    "memory": f32((B, self.cfg.n_frames, self.cfg.d_model))}

        # decoder family
        if shape.kind == "train":
            batch = {"tokens": i32((B, S)), "labels": i32((B, S))}
            if self.embeds_input:
                batch = {"embeds": f32((B, S, self.cfg.d_model)),
                         "labels": i32((B, S))}
            return batch
        if shape.kind == "prefill":
            return {"tokens": i32((B, S))}
        return {"tokens": i32((B, 1))}

    def cache_specs(self, shape_name: str):
        shape = SHAPES[shape_name]
        return jax.eval_shape(
            lambda: self.init_cache(shape.global_batch, shape.seq_len))

    def abstract_params(self):
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    def param_count(self) -> int:
        import math
        return sum(math.prod(l.shape) for l in
                   jax.tree.leaves(self.abstract_params()))


def reduced_decoder(cfg: dec_lib.DecoderConfig, **over) -> dec_lib.DecoderConfig:
    """Smoke-test variant: one superblock period x2, d_model<=256, <=4 experts."""
    n_slots = len(cfg.superblock)
    small = dict(
        n_layers=(max(2, n_slots) if n_slots > 1 else 2) + cfg.first_k_dense,
        d_model=256,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2),
        head_dim=64,
        d_ff=512 if cfg.n_experts == 0 else 256,
        vocab=1024,
        n_experts=min(cfg.n_experts, 4),
        top_k=min(cfg.top_k, 2),
        moe_d_ff=cfg.moe_d_ff and 128,
        router_width=cfg.router_width and min(cfg.router_width, 4),
        kv_lora_rank=cfg.kv_lora_rank and 64,
        qk_nope_head_dim=cfg.qk_nope_head_dim and 32,
        qk_rope_head_dim=cfg.qk_rope_head_dim and 16,
        v_head_dim=cfg.v_head_dim and 32,
        mamba_d_inner=512 if cfg.mamba_d_inner else None,
        mamba_headdim=64,
        mamba_dstate=32,
        mamba_chunk=16,
        sliding_window=16 if cfg.sliding_window else None,
        max_seq=256,
        param_dtype=jnp.float32,  # smoke numerics even for bf16 prod configs
    )
    small.update(over)
    # superblock must still divide the stacked layers
    stacked = small["n_layers"] - cfg.first_k_dense
    if stacked % n_slots != 0:
        small["n_layers"] = (n_slots * max(1, stacked // n_slots)
                             + cfg.first_k_dense)
    return dataclasses.replace(cfg, **small)
