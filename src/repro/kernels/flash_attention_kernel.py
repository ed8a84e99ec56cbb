"""Pallas TPU kernel: blocked online-softmax ("flash") attention for training.

`flash_attention(q, k, v, causal=, scale=)` is the same attention as the
XLA path of `models/attention.attn_apply` (logits, mask, float32 softmax,
PV) without ever writing the (B, H, S, S) scores to memory, with its own
backward. QK^T, PV and every gradient product take bfloat16 operands and
accumulate in float32; the softmax statistics are float32.

Layout. Each grid step works on a (key block, query block) tile of scores
held TRANSPOSED, keys on sublanes and queries on lanes: the per-query
statistics (running max, sum, log-sum-exp, and the backward's
di = rowsum(dO * O)) are then (1, block) rows, stored lane-dense as
(B, H, 1, S) arrays, and every product is a plain (M, K) @ (K, N) matmul
on operands laid out for it outside the kernel (q, v and dO go in both as
(S, hd) and as (hd, S); the output and dQ come out as (hd, S)).

Widths. Queries and keys share one width and values may have another, as
latent attention (MLA) has them: keys 192 wide, values 128. Every tile of
q, k, dQ and dK is as wide as the keys, every tile of v, dO, the output
and dV as wide as the values; with equal widths both are the same blocks.

The keys are centred over the sequence before the kernel. Every score of
a query moves by the same amount, which the softmax cancels, so nothing
else changes; but dQ_i = sum_j dS_ij k_j no longer carries the keys'
common part, which (since sum_j dS_ij = 0) only multiplies rounding: the
bfloat16 rounding of each dS_ij, and di's, whose error is the same for
every key of a query. Where the keys share a large part, as BERT's key
bias makes them, that rounding swamps the true dQ.

Grids. Forward: (B, H, S/bq, S/bk), keys innermost, the running
statistics in VMEM scratch. Backward: (B, H, S/bk, S/bq), queries
innermost, dK and dV accumulated in scratch; with one key block (S <= bk)
the same grid step also writes dQ, else a second kernel accumulates dQ
over the keys. Causal calls skip the (query, key) blocks above the
diagonal, and their index maps stay on the last block needed so the
skipped steps fetch nothing.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import NEG_INF, default_interpret

F32 = jnp.float32


def _mask(sT, i, j, bq, bk):
    """Causal mask of a (bk, bq) transposed tile: key j*bk+r sees query
    i*bq+c only if it is not later."""
    rows = j * bk + jax.lax.broadcasted_iota(jnp.int32, sT.shape, 0)
    cols = i * bq + jax.lax.broadcasted_iota(jnp.int32, sT.shape, 1)
    return jnp.where(rows <= cols, sT, NEG_INF)


def _live(i, j, bq, bk):
    """Whether key block j has a key at or before query block i's last."""
    return j * bk <= i * bq + bq - 1


def _fwd_kernel(qT_ref, k_ref, vT_ref, oT_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, scale, causal, bq, bk, nk):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, F32)
        l_sc[...] = jnp.zeros(l_sc.shape, F32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, F32)

    def step():
        sT = jnp.dot(k_ref[0, 0], qT_ref[0, 0],
                     preferred_element_type=F32) * scale        # (bk, bq)
        if causal:
            sT = _mask(sT, i, j, bq, bk)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(sT, axis=0, keepdims=True))
        pT = jnp.exp(sT - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(pT, axis=0, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            vT_ref[0, 0], pT.astype(vT_ref.dtype),
            preferred_element_type=F32)                         # (dv, bq)
        m_sc[...] = m_new

    if causal:
        pl.when(_live(i, j, bq, bk))(step)
    else:
        step()

    @pl.when(j == nk - 1)
    def _():
        oT_ref[0, 0] = (acc_sc[...] / l_sc[...]).astype(oT_ref.dtype)
        lse_ref[0, 0] = m_sc[...] + jnp.log(l_sc[...])


def _grads_of_tile(qT_ref, k_ref, v_ref, doT_ref, lse_ref, di_ref, i, j, *,
                   scale, causal, bq, bk):
    """(pT, dsT) of one (bk, bq) tile: probabilities and the gradient of
    the scaled logits, times the scale (so dK = dsT @ q, dQ^T = k^T dsT)."""
    sT = jnp.dot(k_ref[0, 0], qT_ref[0, 0], preferred_element_type=F32) * scale
    if causal:
        sT = _mask(sT, i, j, bq, bk)
    pT = jnp.exp(sT - lse_ref[0, 0])
    dpT = jnp.dot(v_ref[0, 0], doT_ref[0, 0], preferred_element_type=F32)
    return pT, pT * (dpT - di_ref[0, 0]) * scale


def _dkv_kernel(*refs, scale, causal, bq, bk, nq, fused):
    (qT_ref, q_ref, k_ref, kT_ref, v_ref, doT_ref, do_ref, lse_ref, di_ref,
     dk_ref, dv_ref) = refs[:11]
    dqT_ref = refs[11] if fused else None
    dk_sc, dv_sc = refs[-2:]
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, F32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, F32)

    def step():
        pT, dsT = _grads_of_tile(qT_ref, k_ref, v_ref, doT_ref, lse_ref,
                                 di_ref, i, j, scale=scale, causal=causal,
                                 bq=bq, bk=bk)
        dt = q_ref.dtype
        dv_sc[...] += jnp.dot(pT.astype(dt), do_ref[0, 0],
                              preferred_element_type=F32)
        dk_sc[...] += jnp.dot(dsT.astype(dt), q_ref[0, 0],
                              preferred_element_type=F32)
        if dqT_ref is not None:       # one key block: dQ is whole here
            dqT_ref[0, 0] = jnp.dot(kT_ref[0, 0], dsT.astype(dt),
                                    preferred_element_type=F32
                                    ).astype(dqT_ref.dtype)

    if causal:
        pl.when(_live(i, j, bq, bk))(step)
    else:
        step()

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _dq_kernel(qT_ref, k_ref, kT_ref, v_ref, doT_ref, lse_ref, di_ref,
               dqT_ref, dq_sc, *, scale, causal, bq, bk, nk):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros(dq_sc.shape, F32)

    def step():
        _, dsT = _grads_of_tile(qT_ref, k_ref, v_ref, doT_ref, lse_ref,
                                di_ref, i, j, scale=scale, causal=causal,
                                bq=bq, bk=bk)
        dq_sc[...] += jnp.dot(kT_ref[0, 0], dsT.astype(kT_ref.dtype),
                              preferred_element_type=F32)

    if causal:
        pl.when(_live(i, j, bq, bk))(step)
    else:
        step()

    @pl.when(j == nk - 1)
    def _():
        dqT_ref[0, 0] = dq_sc[...].astype(dqT_ref.dtype)


def _specs(causal, bq, bk, dk, dv, *, keys_inner):
    """BlockSpecs over a (B, H, nq|nk, nk|nq) grid for the operand kinds:
    query rows (bq, dk), query columns (dk, bq), query statistics (1, bq),
    key rows (bk, dk), key columns (dk, bk), value rows (bk, dv), value
    columns (dv, bk), output rows (bq, dv) and output columns (dv, bq). A
    causal grid's skipped steps keep the block of the nearest step that
    runs."""
    def ij(g2, g3):
        i, j = (g2, g3) if keys_inner else (g3, g2)
        if causal and keys_inner:      # last key block query block i needs
            j = jnp.minimum(j, (i * bq + bq - 1) // bk)
        elif causal:                   # first query block key block j needs
            i = jnp.maximum(i, (j * bk) // bq)
        return i, j

    def q_rows(b, h, g2, g3):
        return b, h, ij(g2, g3)[0], 0

    def q_cols(b, h, g2, g3):
        return b, h, 0, ij(g2, g3)[0]

    def k_rows(b, h, g2, g3):
        return b, h, ij(g2, g3)[1], 0

    def k_cols(b, h, g2, g3):
        return b, h, 0, ij(g2, g3)[1]

    return {
        "q_rows": pl.BlockSpec((1, 1, bq, dk), q_rows),
        "q_cols": pl.BlockSpec((1, 1, dk, bq), q_cols),
        "q_stat": pl.BlockSpec((1, 1, 1, bq), q_cols),
        "k_rows": pl.BlockSpec((1, 1, bk, dk), k_rows),
        "k_cols": pl.BlockSpec((1, 1, dk, bk), k_cols),
        "v_rows": pl.BlockSpec((1, 1, bk, dv), k_rows),
        "v_cols": pl.BlockSpec((1, 1, dv, bk), k_cols),
        "o_rows": pl.BlockSpec((1, 1, bq, dv), q_rows),
        "o_cols": pl.BlockSpec((1, 1, dv, bq), q_cols),
    }


def _t(a):
    return a.swapaxes(-1, -2)


# A 1024 x 1024 tile's float32 scores, probabilities and their gradients
# take about 20 MB of VMEM, over the 16 MB a kernel gets by default.
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 2 ** 20)


def _forward(qT, k, vT, *, causal, scale, bq, bk, interpret):
    """(oT (B, H, dv, S) in qT's dtype, lse (B, H, 1, S) float32)."""
    B, H, dk, S = qT.shape
    dv = vT.shape[2]
    nq, nk = S // bq, S // bk
    sp = _specs(causal, bq, bk, dk, dv, keys_inner=True)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[sp["q_cols"], sp["k_rows"], sp["v_cols"]],
        out_specs=[sp["o_cols"], sp["q_stat"]],
        out_shape=[jax.ShapeDtypeStruct((B, H, dv, S), qT.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, S), F32)],
        scratch_shapes=[pltpu.VMEM((1, bq), F32), pltpu.VMEM((1, bq), F32),
                        pltpu.VMEM((dv, bq), F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="flash_attention_fwd",
    )(qT, k, vT)


def _backward(q, qT, k, kT, v, do, doT, lse, di, *, causal, scale, bq, bk,
              interpret):
    """(dq, dk, dv): dq and dk (B, H, S, dk), dv (B, H, S, dv), in q's
    dtype."""
    B, H, S, wk = q.shape
    wv = v.shape[3]
    nq, nk = S // bq, S // bk
    dt = q.dtype
    kv = _specs(causal, bq, bk, wk, wv, keys_inner=False)
    fused = nk == 1
    k_rows = jax.ShapeDtypeStruct((B, H, S, wk), dt)
    v_rows = jax.ShapeDtypeStruct((B, H, S, wv), dt)
    cols = jax.ShapeDtypeStruct((B, H, wk, S), dt)
    out = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, nq=nq, fused=fused),
        grid=(B, H, nk, nq),
        in_specs=[kv["q_cols"], kv["q_rows"], kv["k_rows"], kv["k_cols"],
                  kv["v_rows"], kv["o_cols"], kv["o_rows"], kv["q_stat"],
                  kv["q_stat"]],
        out_specs=[kv["k_rows"], kv["v_rows"]] + ([kv["q_cols"]] if fused
                                                   else []),
        out_shape=[k_rows, v_rows] + ([cols] if fused else []),
        scratch_shapes=[pltpu.VMEM((bk, wk), F32), pltpu.VMEM((bk, wv), F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qT, q, k, kT, v, doT, do, lse, di)
    if fused:
        dk, dv, dqT = out
    else:
        dk, dv = out
        qk = _specs(causal, bq, bk, wk, wv, keys_inner=True)
        dqT = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq,
                              bk=bk, nk=nk),
            grid=(B, H, nq, nk),
            in_specs=[qk["q_cols"], qk["k_rows"], qk["k_cols"],
                      qk["v_rows"], qk["o_cols"], qk["q_stat"],
                      qk["q_stat"]],
            out_specs=qk["q_cols"],
            out_shape=cols,
            scratch_shapes=[pltpu.VMEM((wk, bq), F32)],
            compiler_params=_PARAMS,
            interpret=interpret,
            name="flash_attention_bwd_dq",
        )(qT, k, kT, v, doT, lse, di)
    return _t(dqT), dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, bq, bk, interpret):
    return _flash_fwd(q, k, v, causal, scale, bq, bk, interpret)[0]


def _flash_fwd(q, k, v, causal, scale, bq, bk, interpret):
    oT, lse = _forward(_t(q), k, _t(v), causal=causal, scale=scale, bq=bq,
                       bk=bk, interpret=interpret)
    return _t(oT), (q, k, v, oT, lse)


def _flash_bwd(causal, scale, bq, bk, interpret, res, do):
    q, k, v, oT, lse = res
    doT = _t(do)
    di = jnp.sum(oT.astype(F32) * doT.astype(F32), axis=2,
                 keepdims=True)                                 # (B,H,1,S)
    return _backward(q, _t(q), k, _t(k), v, do, doT, lse, di, causal=causal,
                     scale=scale, bq=bq, bk=bk, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool, scale: float,
                    block_q: int, block_k: int,
                    interpret: Optional[bool] = None):
    """Softmax(scale * q k^T [causal mask]) v with a fused backward.

    q, k: (B, H, S, dk) and v: (B, H, S, dv), k and v with q's heads
    (repeat GQA heads first); the value width dv may differ from the key
    width dk. S must be a multiple of block_q and block_k, and they of
    128. Returns (B, H, S, dv) in q's dtype. interpret: Pallas interpret mode,
    None = off on a TPU, on elsewhere.
    """
    S = q.shape[2]
    if S % block_q or S % block_k or block_q % 128 or block_k % 128:
        raise ValueError(f"S={S} is not on the ({block_q}, {block_k}) grid "
                         f"of 128-multiples")
    if interpret is None:
        interpret = default_interpret()
    # keys centred over the sequence (module docstring)
    k = (k - jnp.mean(k.astype(F32), axis=2, keepdims=True)).astype(k.dtype)
    return _flash(q, k, v, causal, scale, block_q, block_k, interpret)
