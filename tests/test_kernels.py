"""Per-kernel shape/dtype sweeps against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import paged_attention_kernel as pak
from repro.kernels.paged_attention_kernel import (
    ensure_kernel_fit, paged_attention, paged_attention_fused,
    tile_alignment_problems, tuned_grid_order)

pytestmark = pytest.mark.kernels

SHAPES = [(1,), (7,), (128,), (300,), (129, 130), (8, 16, 32), (2, 3, 5, 7)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _mk(rng, shape, dtype):
    g = jnp.asarray(rng.normal(size=shape), dtype)
    x = jnp.asarray(rng.normal(size=shape), dtype)
    m = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v = jnp.asarray(np.abs(rng.normal(size=shape)), jnp.float32)
    return g, m, v, x


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_lans_sweep(rng, shape, dtype):
    g, m, v, x = _mk(rng, shape, dtype)
    got = ops.fused_lans_step(g, m, v, x, eta=0.02, step=4, lam=0.02)
    want = ref.lans_step_ref(g, m, v, x, eta=0.02, step=4, lam=0.02)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for a, b, nm in zip(got, want, "xmv"):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol, err_msg=f"{shape} {nm}")


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_lamb_sweep(rng, shape, dtype):
    g, m, v, x = _mk(rng, shape, dtype)
    got = ops.fused_lamb_step(g, m, v, x, eta=0.02, step=4, lam=0.02)
    want = ref.lamb_step_ref(g, m, v, x, eta=0.02, step=4, lam=0.02)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for a, b, nm in zip(got, want, "xmv"):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol, err_msg=f"{shape} {nm}")


@pytest.mark.parametrize("shape", SHAPES)
def test_block_sq_norm_sweep(rng, shape):
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    np.testing.assert_allclose(float(ops.block_sq_norm(x)),
                               float(ref.sq_norm_ref(x)), rtol=1e-5)


def test_fused_lans_zero_gradient_block(rng):
    """A zero gradient block must not produce NaNs (guarded normalization)."""
    shape = (64,)
    g = jnp.zeros(shape)
    m = jnp.zeros(shape)
    v = jnp.zeros(shape)
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    out = ops.fused_lans_step(g, m, v, x, eta=0.01, step=1)
    assert bool(jnp.all(jnp.isfinite(out.x)))
    want = ref.lans_step_ref(g, m, v, x, eta=0.01, step=1)
    np.testing.assert_allclose(np.asarray(out.x), np.asarray(want.x),
                               rtol=1e-5, atol=1e-6)


def test_fused_no_trust_variant(rng):
    g, m, v, x = _mk(rng, (40,), jnp.float32)
    got = ops.fused_lans_step(g, m, v, x, eta=0.01, step=2, lam=0.0,
                              apply_trust=False)
    want = ref.lans_step_ref(g, m, v, x, eta=0.01, step=2, lam=0.0,
                             apply_trust=False)
    np.testing.assert_allclose(np.asarray(got.x), np.asarray(want.x),
                               rtol=2e-5, atol=2e-6)


# --------------------------------------------------------------------------
# paged-attention decode kernel (kernels/paged_attention_kernel.py)
# --------------------------------------------------------------------------

def _paged_case(rng, *, B=4, h=4, n_kv=2, hd=16, bs=8, nb=5, n_blocks=12,
                dtype=jnp.bfloat16, max_pos=30):
    """Random decode-shaped inputs: arenas with a pos=-1 null block, random
    (possibly aliasing) block tables, one dead (all-null) slot."""
    q = jnp.asarray(rng.normal(size=(B, h, hd)), dtype)
    ka = jnp.asarray(rng.normal(size=(n_blocks, bs, n_kv, hd)), dtype)
    va = jnp.asarray(rng.normal(size=(n_blocks, bs, n_kv, hd)), dtype)
    pos = rng.integers(-1, max_pos, size=(n_blocks, bs)).astype(np.int32)
    pos[0] = -1                               # reserved null block
    tbl = rng.integers(0, n_blocks, size=(B, nb)).astype(np.int32)
    tbl[-1] = 0                               # dead slot: every entry null
    qpos = rng.integers(0, max_pos, size=(B,)).astype(np.int32)
    return q, ka, va, jnp.asarray(pos), jnp.asarray(tbl), jnp.asarray(qpos)


PAGED_VARIANTS = [
    dict(),                                   # plain causal GQA
    dict(window=8),                           # sliding-window mask
    dict(softcap=5.0),                        # gemma2-style logit cap
    dict(causal=False),                       # bidirectional
    dict(window=4, softcap=10.0),
]


@pytest.mark.parametrize("kwargs", PAGED_VARIANTS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_paged_attention_matches_ref(rng, kwargs, dtype):
    args = _paged_case(rng, dtype=dtype)
    got = paged_attention(*args, scale=0.25, **kwargs)
    want = ref.paged_attention_ref(*args, scale=0.25, **kwargs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("h,n_kv", [(4, 4), (8, 2), (6, 1)])
def test_paged_attention_gqa_head_mapping(rng, h, n_kv):
    """MHA / grouped / MQA head layouts all match the repeat-heads oracle."""
    args = _paged_case(rng, h=h, n_kv=n_kv, dtype=jnp.float32)
    got = paged_attention(*args, scale=0.125)
    want = ref.paged_attention_ref(*args, scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def test_paged_attention_pos_minus_one_masked_on_chip(rng):
    """THE masking property: pos == -1 rows (null block, unwritten ring
    slots) must contribute exactly nothing — huge garbage K/V planted in
    every masked row leaves the output bitwise unchanged, and a slot whose
    table references no valid key at all returns exactly 0, not NaN."""
    q, ka, va, pos, tbl, qpos = _paged_case(rng, dtype=jnp.float32)
    clean = paged_attention(q, ka, va, pos, tbl, qpos, scale=0.25)
    masked = np.asarray(pos) < 0
    garbage = jnp.where(jnp.asarray(masked)[:, :, None, None], 1e30, 0.0)
    out = paged_attention(q, ka + garbage, va + garbage, pos, tbl, qpos,
                          scale=0.25)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
    # dead slot (table all null-block): exact zeros, finite everywhere
    assert (np.asarray(out[-1]) == 0.0).all()
    assert np.isfinite(np.asarray(out)).all()


def test_paged_attention_causal_and_window_masking(rng):
    """Keys in the future of q_pos (and beyond the sliding window) are
    masked even when their positions are valid (>= 0)."""
    B, h, n_kv, hd, bs, nb = 2, 2, 2, 8, 4, 2
    rngs = np.random.default_rng(7)
    ka = jnp.asarray(rngs.normal(size=(1 + nb, bs, n_kv, hd)), jnp.float32)
    va = jnp.asarray(rngs.normal(size=(1 + nb, bs, n_kv, hd)), jnp.float32)
    q = jnp.asarray(rngs.normal(size=(B, h, hd)), jnp.float32)
    pos = np.concatenate([np.full((1, bs), -1, np.int32),
                          np.arange(nb * bs, dtype=np.int32).reshape(nb, bs)])
    tbl = jnp.asarray(np.tile(np.arange(1, 1 + nb, dtype=np.int32), (B, 1)))
    qpos = jnp.asarray(np.array([3, nb * bs - 1], np.int32))
    pos = jnp.asarray(pos)
    out = paged_attention(q, ka, va, pos, tbl, qpos, scale=0.5, window=4)
    # slot 0 sees positions 0..3 only; slot 1 the last 4 positions: editing
    # keys outside those windows must not change anything
    ka2 = ka.at[2:, :].add(100.0)            # positions >= bs: hidden from slot 0
    out2 = paged_attention(q, ka2, va, pos, tbl, qpos, scale=0.5, window=4)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out2[0]))
    assert not np.array_equal(np.asarray(out[1]), np.asarray(out2[1]))
    want = ref.paged_attention_ref(q, ka, va, pos, tbl, qpos, scale=0.5,
                                   window=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


# --------------------------------------------------------------------------
# scatter-in-epilogue fused kernel (paged_attention_fused)
# --------------------------------------------------------------------------

def _fused_case(rng, *, S=1, dtype=jnp.float32, wrap=False,
                B=3, h=4, n_kv=2, hd=16, bs=8, nb=5, n_blocks=12):
    """Pool-shaped decode state: slots own exclusive blocks, history fills
    the ring up to each cursor, destination rows are unwritten (pos -1) —
    or, under wrap, window-expired per the row_margin contract. Slot B-1
    is dead (all-null table, q_pos -1)."""
    ring = nb * bs
    ka = jnp.asarray(rng.normal(size=(n_blocks, bs, n_kv, hd)), dtype)
    va = jnp.asarray(rng.normal(size=(n_blocks, bs, n_kv, hd)), dtype)
    pos = np.full((n_blocks, bs), -1, np.int32)
    tbl = np.zeros((B, nb), np.int32)
    tbl[0] = np.arange(1, 1 + nb)
    tbl[1, :2] = [6, 7]                        # short chain, rest null
    if wrap:
        cur0, qbase = ring - 2, 3 * ring - 2   # dest rows straddle the wrap
        dests = {(cur0 + s) % ring for s in range(S)}
        for r in range(ring):
            if r not in dests:                 # stale wrapped rows stay,
                pos[tbl[0, r // bs], r % bs] = qbase - ((cur0 - r) % ring)
    else:
        cur0, qbase = 17, 17
        for r in range(cur0):
            pos[tbl[0, r // bs], r % bs] = r
    for r in range(9):
        pos[tbl[1, r // bs], r % bs] = r
    cursor = np.array([cur0, 9, 0][:B], np.int32)
    qpos = np.stack([qbase + np.arange(S), 9 + np.arange(S),
                     np.full(S, -1)][:B]).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(B, S, h, hd)), dtype)
    k_new = jnp.asarray(rng.normal(size=(B, S, n_kv, hd)), dtype)
    v_new = jnp.asarray(rng.normal(size=(B, S, n_kv, hd)), dtype)
    if S == 1:                                 # exercise the 3-D squeeze
        q, k_new, v_new, qpos = q[:, 0], k_new[:, 0], v_new[:, 0], qpos[:, 0]
    return (q, k_new, v_new, ka, va, jnp.asarray(pos), jnp.asarray(tbl),
            jnp.asarray(qpos), jnp.asarray(cursor))


FUSED_VARIANTS = [
    dict(S=1), dict(S=4), dict(S=1, softcap=5.0),
    dict(S=4, window=24, wrap=True), dict(S=1, window=24, wrap=True),
]


@pytest.mark.parametrize("kwargs", FUSED_VARIANTS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_paged_attention_fused_matches_oracle(rng, kwargs, dtype):
    """out matches the scatter-then-attend oracle; arenas are BIT-exact
    on every block (the oracle carries the write — kernels/ref.py)."""
    kw = dict(kwargs)
    case_kw = {k: kw.pop(k) for k in ("S", "wrap") if k in kw}
    args = _fused_case(rng, dtype=dtype, **case_kw)
    got = paged_attention_fused(*args, scale=0.25, **kw)
    want = ref.paged_attention_fused_ref(*args, scale=0.25, **kw)
    for g, w, name in zip(got[1:], want[1:], ("k", "v", "pos")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{name} arena not bit-exact")
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-6, atol=2e-6)


def test_paged_attention_fused_equals_scatter_then_kernel(rng):
    """The fused launch == XLA scatter followed by the read-side kernel:
    same arenas bit-for-bit, same attention to fp32 tolerance."""
    for S in (1, 4):
        args = _fused_case(rng, S=S)
        out_f, kf, vf, pf = paged_attention_fused(*args, scale=0.25)
        _, k2, v2, p2 = ref.paged_attention_fused_ref(*args, scale=0.25)
        np.testing.assert_array_equal(np.asarray(kf), np.asarray(k2))
        np.testing.assert_array_equal(np.asarray(vf), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(pf), np.asarray(p2))
        out_k = paged_attention(args[0], k2, v2, p2, args[6], args[7],
                                scale=0.25)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_k),
                                   rtol=2e-6, atol=2e-6)


def test_paged_attention_fused_rollback_churn_bit_equality(rng):
    """Speculative reject-after-fused-verify, at the kernel level: three
    S=4 verify rounds where each round's tail is REJECTED. Rollback is
    the engine's host-side op — invalidate the rejected rows' positions
    (pos=-1 scatter) and rewind the cursor — so the next fused launch
    re-writes rows the previous launch just wrote, over stale K/V bytes
    that only pos masks. After every round the fused arenas must stay
    bit-identical to scatter-then-kernel arenas evolved by the SAME
    churn, and the attention outputs must agree to fp32 tolerance."""
    S = 4
    q, k_new, v_new, ka, va, pos, tbl, qpos, cursor = _fused_case(rng, S=S)
    # lazy growth, done up front: back slot 1's chain with a free block
    # so the churn below never runs a dest row into the null block
    tbl = jnp.asarray(np.asarray(tbl)).at[1, 2].set(8)
    kb, vb, pb = ka, va, pos                   # oracle-evolved copies
    bs, nb = ka.shape[1], tbl.shape[1]
    ring = nb * bs
    cursor = np.asarray(cursor).copy()
    churn = np.random.default_rng(5)
    for acc in (2, 0, 3):                      # accepted proposals per round
        cur = jnp.asarray(cursor)
        out_f, kf, vf, pf = paged_attention_fused(
            q, k_new, v_new, ka, va, pos, tbl, qpos, cur, scale=0.25)
        out_r, k2, v2, p2 = ref.paged_attention_fused_ref(
            q, k_new, v_new, kb, vb, pb, tbl, qpos, cur, scale=0.25)
        for g, w, name in zip((kf, vf, pf), (k2, v2, p2), ("k", "v", "pos")):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w),
                err_msg=f"{name} arena diverged at acc={acc}")
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                                   rtol=2e-6, atol=2e-6)
        # rollback: keep acc accepted rows + the correction token, park
        # pos=-1 on the rejected tail of both arena lineages (K/V bytes
        # stay — exactly the stale-garbage state the next round masks)
        pn = np.asarray(pf).copy()
        qp = np.asarray(qpos)
        for b in range(2):                     # slot 2 is dead
            for s in range(acc + 1, S):
                r = int(qp[b, s]) % ring
                pn[tbl[b, r // bs], r % bs] = -1
            cursor[b] += acc + 1
        ka, va, pos = kf, vf, jnp.asarray(pn)
        kb, vb, pb = k2, v2, jnp.asarray(pn)
        qpos = jnp.asarray(np.stack(
            [qp[0, 0] + (acc + 1) + np.arange(S),
             qp[1, 0] + (acc + 1) + np.arange(S),
             np.full(S, -1)]).astype(np.int32))
        q = jnp.asarray(churn.normal(size=q.shape), q.dtype)
        k_new = jnp.asarray(churn.normal(size=k_new.shape), k_new.dtype)
        v_new = jnp.asarray(churn.normal(size=v_new.shape), v_new.dtype)


def test_paged_attention_fused_null_block_and_bystanders_immutable(rng):
    """Blocks the write never targets keep their exact input bytes: the
    null block (index 0 — which the XLA scatter would dirty with invalid
    rows' K/V), every unreferenced arena block, and every history block
    of live slots. Dead slots output exactly 0."""
    q, k_new, v_new, ka, va, pos, tbl, qpos, cursor = _fused_case(rng, S=4)
    out, kf, vf, pf = paged_attention_fused(
        q, k_new, v_new, ka, va, pos, tbl, qpos, cursor, scale=0.25)
    ring = tbl.shape[1] * ka.shape[1]
    dest = {(b, int((cursor[b] + s) % ring))
            for b in range(q.shape[0]) for s in range(q.shape[1])
            if int(qpos[b, s]) >= 0}
    dest_blocks = {int(tbl[b, r // ka.shape[1]]) for b, r in dest}
    for blk in range(ka.shape[0]):
        if blk in dest_blocks:
            continue
        np.testing.assert_array_equal(np.asarray(kf[blk]),
                                      np.asarray(ka[blk]), err_msg=f"k {blk}")
        np.testing.assert_array_equal(np.asarray(vf[blk]),
                                      np.asarray(va[blk]), err_msg=f"v {blk}")
        np.testing.assert_array_equal(np.asarray(pf[blk]),
                                      np.asarray(pos[blk]),
                                      err_msg=f"pos {blk}")
    assert 0 not in dest_blocks                # the null block is immutable
    assert (np.asarray(out[-1]) == 0.0).all()  # dead slot: exact zeros


def test_paged_attention_fused_grid_order_is_pure_schedule(rng):
    """grid_order='parallel' (megacore dimension semantics) is a schedule
    choice only: outputs and arenas identical to the sequential grid."""
    args = _fused_case(rng, S=4, wrap=True)
    a = paged_attention_fused(*args, scale=0.25, window=24,
                              grid_order="arbitrary")
    b = paged_attention_fused(*args, scale=0.25, window=24,
                              grid_order="parallel")
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    with pytest.raises(ValueError, match="grid_order"):
        paged_attention_fused(*args, scale=0.25, grid_order="bogus")


# --------------------------------------------------------------------------
# tile alignment / VMEM fit + the tuned-config table
# --------------------------------------------------------------------------

def test_tile_alignment_problems():
    """(block_size, head_dim) vs the TPU (8/16, 128) tile grid: clean
    production shapes pass, off-grid shapes name the failing dim; bf16
    needs the 16-row sublane where fp32 needs 8."""
    assert tile_alignment_problems(16, 128, jnp.float32) == []
    assert tile_alignment_problems(16, 128, jnp.bfloat16) == []
    probs = tile_alignment_problems(8, 64, jnp.bfloat16)
    assert len(probs) == 2                     # lane AND sublane off-grid
    assert any("head_dim" in p for p in probs)
    assert any("block_size" in p for p in probs)
    assert tile_alignment_problems(8, 128, jnp.float32) == []
    assert tile_alignment_problems(8, 128, jnp.bfloat16) != []


def test_block_rule_refuses_what_the_compiler_refuses():
    """The TPU compiler's BlockSpec rule: a (1, bs) row of a 2-D
    (n_blocks, bs) position arena is refused (sublane dim 1 is neither
    8-aligned nor the whole array), the (1, 1, bs) row of the
    (n_blocks, 1, bs) view is accepted, and every block of the fused
    kernel passes at qwen2.5-14b decode widths."""
    assert pak.block_rule_problems("pos", (1, 16), (512, 16))
    assert pak.block_rule_problems("x", (8, 64), (64, 256))   # lane dim
    assert pak.block_rule_problems("pos", (1, 1, 16), (512, 1, 16)) == []
    for S in (1, 4):
        for name, (block, array) in pak.fused_blocks(
                S, 40, 8, 128, 16).items():
            assert pak.block_rule_problems(name, block, array) == [], name
        assert pak.kernel_fit_problems(16, 128, 40, 8, jnp.bfloat16,
                                       S=S) == []
    assert pak.pos_rows(jnp.zeros((5, 16), jnp.int32)).shape == (5, 1, 16)


def test_ensure_kernel_fit_gates_compiled_only():
    """Problems raise only when the kernel would COMPILE (interpret
    False); the interpret escape hatch downgrades them to advisory."""
    probs = ensure_kernel_fit(8, 64, 8, 2, jnp.bfloat16, interpret=True)
    assert probs                               # advisory, returned
    with pytest.raises(ValueError, match="interpret"):
        ensure_kernel_fit(8, 64, 8, 2, jnp.bfloat16, interpret=False)
    assert ensure_kernel_fit(16, 128, 8, 2, jnp.bfloat16,
                             interpret=False) == []
    # VMEM gate: production head counts must fit the scratch budget
    big = pak.kernel_fit_problems(2048, 128, 128, 8, jnp.bfloat16, S=16)
    assert any("VMEM" in p for p in big)


def test_tuned_table_lookup_and_fallback(monkeypatch):
    """Exact (backend, head_dim, n_kv, block_size, S) hits return the
    recorded winner; ANY miss — key, backend, or absent table — falls
    back to the documented sequential 'arbitrary' grid."""
    fake = {"cpu": {"hd64_kv2": {"bs16_S1": {"grid_order": "parallel",
                                             "us": 1.0}}}}
    monkeypatch.setattr(pak, "tuned_table", lambda: fake)
    assert tuned_grid_order("cpu", 64, 2, 16, 1) == "parallel"
    assert tuned_grid_order("cpu", 64, 2, 16, 4) == "arbitrary"
    assert tuned_grid_order("cpu", 128, 2, 16, 1) == "arbitrary"
    assert tuned_grid_order("tpu", 64, 2, 16, 1) == "arbitrary"
    monkeypatch.setattr(pak, "tuned_table", dict)
    assert tuned_grid_order("cpu", 64, 2, 16, 1) == "arbitrary"


def test_checked_in_tuned_table_is_consistent():
    """The committed autotuner table parses and every entry is a valid
    grid order under a well-formed key — the contract paged_attention's
    trace-time lookup relies on."""
    table = pak.tuned_table()
    assert table, "src/repro/configs/paged_attn_tuned.json missing/empty"
    for backend, groups in table.items():
        for gkey, entries in groups.items():
            assert gkey.startswith("hd") and "_kv" in gkey, gkey
            for ekey, entry in entries.items():
                assert ekey.startswith("bs") and "_S" in ekey, ekey
                assert entry["grid_order"] in ("arbitrary", "parallel")
                assert entry["us"] > 0


def test_kv_valid_len_guard_on_fused_path(rng):
    """attn_apply's fused-kernel branch refuses kv_valid_len loudly (the
    kernel has no valid-length operand); the XLA branch accepts it."""
    from repro.models.attention import AttnConfig, attn_apply, attn_init
    cfg = AttnConfig(d_model=16, n_heads=2, n_kv_heads=1, head_dim=8,
                     decode_kernel="paged")
    p = attn_init(jax.random.PRNGKey(0), cfg)
    B, bs, nb, n_blocks = 2, 4, 2, 5
    cache = {
        "k": jnp.zeros((n_blocks, bs, 1, 8)),
        "v": jnp.zeros((n_blocks, bs, 1, 8)),
        "pos": jnp.full((n_blocks, bs), -1, jnp.int32),
        "table": jnp.asarray([[1, 2], [3, 4]], jnp.int32),
        "index": jnp.zeros((B,), jnp.int32),
    }
    x = jnp.asarray(rng.normal(size=(B, 1, 16)), jnp.float32)
    positions = jnp.zeros((B, 1), jnp.int32)
    with pytest.raises(NotImplementedError, match="kv_valid_len"):
        attn_apply(p, cfg, x, positions=positions, cache=cache,
                   kv_valid_len=jnp.ones((B,), jnp.int32))
    out, new_cache = attn_apply(p, cfg, x, positions=positions, cache=cache)
    assert out.shape == (B, 1, 16)
    xla_cfg = AttnConfig(d_model=16, n_heads=2, n_kv_heads=1, head_dim=8)
    out2, _ = attn_apply(p, xla_cfg, x, positions=positions, cache=cache,
                         kv_valid_len=jnp.ones((B,), jnp.int32))
    assert out2.shape == (B, 1, 16)


def test_multi_step_trajectory_parity(rng):
    """5 fused steps == 5 reference steps (state threading correct)."""
    g0, m, v, x = _mk(rng, (96,), jnp.float32)
    xr, mr, vr = x, m, v
    xk, mk, vk = x, m, v
    for step in range(1, 6):
        g = jnp.asarray(rng.normal(size=(96,)), jnp.float32)
        outk = ops.fused_lans_step(g, mk, vk, xk, eta=0.05, step=step)
        outr = ref.lans_step_ref(g, mr, vr, xr, eta=0.05, step=step)
        xk, mk, vk = outk
        xr, mr, vr = outr
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xr),
                               rtol=1e-4, atol=1e-5)
