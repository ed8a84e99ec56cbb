"""Distributed train / prefill / serve step builders.

Each builder returns a pure function suitable for `jax.jit(...,
in_shardings=..., out_shardings=...)` under the production mesh, plus the
sharding pytrees for its inputs/outputs. The same builders drive the real
training loop (launch/train.py), the serving loop (launch/serve.py) and the
multi-pod dry-run (launch/dryrun.py).

Gradient accumulation: `microbatches > 1` runs a `lax.scan` over microbatch
slices, averaging gradients in fp32 — how the 96K global batch is fed
through a fixed device footprint, matching the paper's setup (96K sequences
over 1536 workers = 62.5/worker, accumulated).

Mixed precision: pass `policy=get_policy("fp16_mixed")` (or "bf16") and the
raw optimizer — the builder wraps it with `mixed_precision` (fp32 master
weights), scales the loss by the loss scale carried in the optimizer state
before `value_and_grad`, accumulates microbatch grads in fp32 as before, and
the wrapper's `lax.cond` skips the step + halves the scale on non-finite
grads. Metrics gain `loss_scale` / `overflow_count` / `grads_finite`.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.optim.base import apply_updates
from repro.distributed import sharding as shd

PyTree = Any


def build_train_step(
    loss_fn: Callable,           # (params, batch) -> (loss, aux_dict)
    tx,                          # GradientTransformation (raw, unwrapped)
    mesh: Mesh,
    *,
    microbatches: int = 1,
    zero3: bool = False,
    param_init_fn: Optional[Callable] = None,
    policy=None,                 # repro.precision.Policy or name, optional
    loss_scale=None,             # override the policy's default scaler
):
    """Returns (step_fn, init_fn, specs_for). loss_fn must be pure/jit-able.

    step_fn:   (params, opt_state, batch) -> (params, opt_state, metrics)
    init_fn:   rng -> (params, opt_state)
    specs_for: (params_like, opt_like) -> (params_pspec, opt_pspec)
    """
    if policy is not None:
        from repro import precision
        policy = precision.get_policy(policy)
        if policy.wants_wrapper:
            tx = precision.mixed_precision(tx, policy, loss_scale)
    mixed = policy is not None and policy.wants_wrapper

    def step_fn(params, opt_state, batch):
        if mixed:
            from repro.precision import loss_scale_value
            scale = loss_scale_value(opt_state)
        else:
            scale = None

        def grads_of(mb):
            def objective(p, b):
                loss, aux = loss_fn(p, b)
                if scale is None:
                    return loss, (loss, aux)
                # scale AFTER the fp32 loss reduction; grads flow scaled and
                # the mixed_precision wrapper divides the scale back out.
                return loss * scale.astype(loss.dtype), (loss, aux)

            (_, (loss, aux)), grads = jax.value_and_grad(
                objective, has_aux=True)(params, mb)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            return loss, aux, grads

        if microbatches == 1:
            loss, aux, grads = grads_of(batch)
        else:
            def slice_mb(i):
                return jax.tree.map(
                    lambda x: x.reshape((microbatches, -1) + x.shape[1:])[i]
                    if x.ndim >= 1 else x, batch)

            def body(carry, i):
                acc, loss_acc = carry
                loss, aux, grads = grads_of(slice_mb(i))
                acc = jax.tree.map(jnp.add, acc, grads)
                return (acc, loss_acc + loss), aux

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, loss_sum), auxs = jax.lax.scan(
                body, (zero, 0.0), jnp.arange(microbatches))
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
            loss = loss_sum / microbatches
            # auxs leaves are stacked (microbatches, ...): average numeric
            # aux over the whole global batch (reporting only the last
            # microbatch biased metrics like router_entropy); non-float aux
            # (counters, ids) keeps the final microbatch's value.
            aux = jax.tree.map(
                lambda a: jnp.mean(a, axis=0)
                if jnp.issubdtype(a.dtype, jnp.inexact) else a[-1], auxs)

        # Named scopes reach the compiled HLO's op_name metadata, so a
        # device profile can be split by them (bench/scopes.py).
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, opt_state, params)
            new_params = apply_updates(params, updates)

        with jax.named_scope("grad_norm"):
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                 for g in jax.tree.leaves(grads)))
            metrics = {"loss": loss, "grad_norm": gnorm, **aux}
            if mixed:
                from repro.precision import all_finite, overflow_count
                metrics["grad_norm"] = gnorm / scale   # report unscaled
                metrics["grads_finite"] = all_finite(grads)
                metrics["loss_scale"] = loss_scale_value(new_opt)
                metrics["overflow_count"] = overflow_count(new_opt)
        return new_params, new_opt, metrics

    def init_fn(rng):
        assert param_init_fn is not None
        params = param_init_fn(rng)
        if policy is not None:
            params = policy.cast_params(params)
        return params, tx.init(params)

    # sharding specs require a concrete/abstract params tree; caller supplies
    # them lazily via specs_for.
    def specs_for(params_like, opt_like):
        pspec = shd.params_pspec(params_like, mesh, zero3=zero3)
        ospec = shd.opt_state_pspec(opt_like, pspec)
        return pspec, ospec

    return step_fn, init_fn, specs_for


def _named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def batch_sharding(mesh: Mesh, batch_like):
    """The train step's input sharding for a batch like `batch_like` (its
    leading dim over the mesh's batch axes): where a batch put there by
    `jax.device_put` is taken by the compiled step without a reshard."""
    return _named(mesh, shd.batch_pspec(batch_like, mesh))


def jit_train_step(step_fn, mesh: Mesh, pspec, ospec, batch_like):
    return jax.jit(
        step_fn,
        in_shardings=(_named(mesh, pspec), _named(mesh, ospec),
                      batch_sharding(mesh, batch_like)),
        out_shardings=(_named(mesh, pspec), _named(mesh, ospec), None),
    )


def build_prefill_step(forward_with_cache: Callable, mesh: Mesh):
    """forward_with_cache(params, batch) -> (logits_last, cache)."""
    return forward_with_cache


def build_serve_step(decode_fn: Callable, mesh: Optional[Mesh] = None, *,
                     sampler=None, params_like=None, cache_like=None,
                     donate_cache=True):
    """Build the jitted serving decode step — the decode_32k / long_500k
    shapes lower exactly this function.

    decode_fn(params, batch, cache) -> (logits, new_cache), e.g.
    Arch.decode_step. Returns a jitted

        step(params, tokens (B, 1), positions (B, 1), cache)
            -> (next_tokens (B,), new_cache)

    or, with a non-greedy `sampler` (repro.serving.sampler.Sampler),

        step(params, tokens, positions, cache, keys (B, 2) uint32)
            -> (next_tokens (B,), new_cache)

    Sampling always reads fp32 logits regardless of the serving precision
    policy (bf16/fp16 models still pick tokens from fp32 logits) and the
    per-slot `positions` thread through to the pooled cache. A greedy
    sampler (temperature == 0) compiles the exact argmax step — bit-equal
    to sampler=None. Compiled exactly once per (B, cache shape): the
    continuous-batching engine reuses it for its whole lifetime, and the
    paged pool's block tables / cursors are VALUES inside `cache`, so
    block churn never recompiles (asserted in tests/test_paged_cache.py).

    With a multi-device mesh plus params_like/cache_like abstract trees, the
    step is pjit'ed with the production shardings (params per the param
    rules, cache batch over data / head_dim over model — or, for paged
    arenas, blocks over data); on a single device it is a plain jit.
    donate_cache hands the old cache's buffers to the new one — the KV
    pool is updated in place instead of being double-buffered.
    """
    sampled = sampler is not None and not sampler.greedy
    stable = (sampler is not None and sampler.greedy
              and sampler.stable_tiebreak)

    if sampled:
        def step(params, tokens, positions, cache, keys):
            logits, new_cache = decode_fn(
                params, {"tokens": tokens, "positions": positions}, cache)
            nxt = sampler.sample(logits[:, -1, :].astype(jnp.float32), keys)
            return nxt, new_cache
    elif stable:
        # greedy with the bf16-ulp tie band (sampler.stable_argmax):
        # cross-layout-invariant token picks for bf16 differentials
        def step(params, tokens, positions, cache):
            logits, new_cache = decode_fn(
                params, {"tokens": tokens, "positions": positions}, cache)
            nxt = sampler.sample(logits[:, -1, :].astype(jnp.float32), None)
            return nxt, new_cache
    else:
        def step(params, tokens, positions, cache):
            logits, new_cache = decode_fn(
                params, {"tokens": tokens, "positions": positions}, cache)
            return greedy_next(logits.astype(jnp.float32)), new_cache

    donate = (3,) if donate_cache else ()
    if mesh is None or mesh.devices.size <= 1 or params_like is None:
        return jax.jit(step, donate_argnums=donate)

    pspec, tok_sh, cspec = serve_shardings(mesh, params_like, cache_like)
    in_sh = (pspec, tok_sh, tok_sh, cspec) + ((tok_sh,) if sampled else ())
    return jax.jit(
        step,
        in_shardings=in_sh,
        out_shardings=(tok_sh, cspec),
        donate_argnums=donate,
    )


def serve_shardings(mesh: Mesh, params_like, cache_like):
    """(params, tokens, cache) NamedSharding trees for the live sharded
    serve/verify steps: params per the param rules, cache per cache_pspec
    (paged arenas blocks-over-data, head_dim over model, integer
    bookkeeping replicated). The token sharding names only the leading
    batch dim, so one spec covers (B, 1) decode tokens, (B, K) verify
    tokens, (B,) outputs and (B, ..., 2) sampler keys alike."""
    def shardings(spec_tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    pspec = shardings(shd.params_pspec(params_like, mesh))
    cspec = shardings(shd.cache_pspec(cache_like, mesh))
    # batch sharding must respect divisibility (long_500k serves B=1):
    # the pooled cache's per-slot cursor carries the batch size
    baxes = shd.batch_axes(mesh)
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]
    idx = cache_like["index"] if isinstance(cache_like, dict) else None
    B = idx.shape[0] if getattr(idx, "ndim", 0) == 1 else None
    tok_sh = NamedSharding(
        mesh, P(baxes) if B is not None and B % bsize == 0 else P())
    return pspec, tok_sh, cspec


def build_verify_step(decode_fn: Callable, mesh: Optional[Mesh] = None, *,
                      sampler=None, params_like=None, cache_like=None,
                      donate_cache=True):
    """Build the jitted speculative-verify step: K tokens per slot in one
    forward against the pooled/paged cache.

    decode_fn(params, batch, cache) -> (logits (B, K, V), new_cache).
    Returns a jitted

        step(params, tokens (B, K), positions (B, K), cache)
            -> (tokens (B, K) int32, new_cache)

    or, with a non-greedy sampler,

        step(params, tokens, positions, cache, keys (B, K, 2) uint32)
            -> (tokens (B, K) int32, new_cache)

    Output row (b, i) is the target model's pick for the position AFTER
    positions[b, i] — i.e. it verifies draft token i+1 and, when every
    draft is accepted, row K-1 is the bonus next token. Each row is
    sampled exactly as build_serve_step samples its single row (same
    fp32 cast, same per-row key), which is what makes a speculative
    stream bit-identical to the non-spec stream: token t of slot b is
    picked from the same logits row with the same
    fold(request_key, t) key regardless of which verify round emitted
    it. Compiled once per (B, K, cache shape); block tables / cursors
    are cache VALUES, so accept/reject churn never recompiles.

    With a multi-device mesh plus params_like/cache_like abstract trees
    the step is pjit'ed with the same shardings as build_serve_step
    (serve_shardings); otherwise it is a plain jit.
    """
    sampled = sampler is not None and not sampler.greedy
    stable = (sampler is not None and sampler.greedy
              and sampler.stable_tiebreak)

    if sampled:
        def step(params, tokens, positions, cache, keys):
            logits, new_cache = decode_fn(
                params, {"tokens": tokens, "positions": positions}, cache)
            B, K, V = logits.shape
            flat = sampler.sample(logits.reshape(B * K, V).astype(
                jnp.float32), keys.reshape(B * K, 2))
            return flat.reshape(B, K), new_cache
    elif stable:
        def step(params, tokens, positions, cache):
            logits, new_cache = decode_fn(
                params, {"tokens": tokens, "positions": positions}, cache)
            B, K, V = logits.shape
            flat = sampler.sample(
                logits.reshape(B * K, V).astype(jnp.float32), None)
            return flat.reshape(B, K), new_cache
    else:
        def step(params, tokens, positions, cache):
            logits, new_cache = decode_fn(
                params, {"tokens": tokens, "positions": positions}, cache)
            nxt = jnp.argmax(logits.astype(jnp.float32), axis=-1)
            return nxt.astype(jnp.int32), new_cache

    donate = (3,) if donate_cache else ()
    if mesh is None or mesh.devices.size <= 1 or params_like is None:
        return jax.jit(step, donate_argnums=donate)

    # same mesh path as build_serve_step: the (B, K) verify tokens and
    # keys shard on their leading batch dim exactly like (B, 1) decode
    # tokens, so the single-row and K-row steps share one sharding story
    pspec, tok_sh, cspec = serve_shardings(mesh, params_like, cache_like)
    in_sh = (pspec, tok_sh, tok_sh, cspec) + ((tok_sh,) if sampled else ())
    return jax.jit(
        step,
        in_shardings=in_sh,
        out_shardings=(tok_sh, cspec),
        donate_argnums=donate,
    )


def greedy_next(logits):
    """(B, 1, V) -> (B,) int32 greedy sample."""
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
