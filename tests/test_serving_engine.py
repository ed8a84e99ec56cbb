"""Continuous-batching serving engine: correctness against the static path.

The load-bearing claims, each asserted here:

  * the continuous engine — PAGED pool (the default) and the dense PR 2
    pool alike — emits token-identical greedy output to the static
    lockstep baseline for the same request set, under fp32 and bf16
    policies, across the three decoder families (dense+sliding window,
    pure-SSM, MoE), including requests that share a prompt prefix (whose
    KV blocks the paged pool stores once);
  * slots are safely reused after eviction (later occupants see none of
    the previous request's KV/SSM state);
  * requests admitted mid-stream (while other slots keep decoding)
    produce the same tokens as running alone;
  * batched left-padded prefill is pad-invariant: a request's tokens do
    not depend on its batch-mates' prompt lengths.
"""
import jax
import numpy as np
import pytest

from conftest import make_serving_requests as make_requests
from conftest import setup_serving_arch as setup_arch
from repro.serving import (CachePool, ContinuousEngine, Request, Scheduler,
                           ServeEngine, pad_prompts, throughput_probe)

pytestmark = pytest.mark.serving

# dense + sliding-window / pure-SSM / mixture-of-experts
ARCHS = ["gemma2-2b", "mamba2-130m", "granite-moe-3b-a800m"]
MAX_LEN = 48


SPEC = [(7, 4), (11, 6), (5, 1), (9, 3), (11, 4)]


def _run_both(name, policy):
    arch, params = setup_arch(name)
    a = make_requests(arch, SPEC)
    b = make_requests(arch, SPEC)
    ServeEngine(arch, params, max_len=MAX_LEN, policy=policy).run_batch(a)
    # max_batch < len(requests): admission + slot reuse are on the path
    ContinuousEngine(arch, params, max_batch=2, max_len=MAX_LEN,
                     policy=policy).run_batch(b)
    return a, b


def _run_trio(name, policy, prefix=0):
    """static / dense-pool / paged-pool over the same workload. prefix
    puts shared-prefix blocks on the paged decode path."""
    arch, params = setup_arch(name)
    outs = []
    for build in (
            lambda: ServeEngine(arch, params, max_len=MAX_LEN,
                                policy=policy),
            lambda: ContinuousEngine(arch, params, max_batch=2,
                                     max_len=MAX_LEN, policy=policy,
                                     cache="dense", prefill_bucket=8),
            lambda: ContinuousEngine(arch, params, max_batch=3,
                                     max_len=MAX_LEN, policy=policy,
                                     cache="paged", block_size=8,
                                     prefill_bucket=8)):
        reqs = make_requests(arch, SPEC, prefix=prefix)
        engine = build()
        engine.run_batch(reqs)
        outs.append((engine, reqs))
    return outs


@pytest.mark.parametrize("name", ARCHS)
def test_continuous_matches_static_fp32(name):
    a, b = _run_both(name, None)
    for ra, rb in zip(a, b):
        assert ra.generated.shape == (ra.max_new_tokens,)
        np.testing.assert_array_equal(ra.generated, rb.generated)


@pytest.mark.slow
@pytest.mark.parametrize("name", ARCHS)
def test_continuous_matches_static_bf16(name):
    """Precision-aware decode: bf16 param/compute cast, fp32 greedy — the
    cast must not desynchronize the two engines."""
    a, b = _run_both(name, "bf16")
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.generated, rb.generated)


@pytest.mark.paged
@pytest.mark.parametrize("name", ARCHS)
def test_paged_matches_dense_and_static_shared_prefix_fp32(name):
    """The differential harness of this PR: the paged engine is token-
    identical to the dense PR 2 engine and the static baseline, with
    every request carrying a 16-token shared prefix whose KV the paged
    pool stores once (shared_hits > 0 on attention archs — pure-SSM
    state is slot-resident, nothing to share)."""
    (s_eng, a), (d_eng, b), (p_eng, c) = _run_trio(name, None, prefix=16)
    for ra, rb, rc in zip(a, b, c):
        assert ra.generated.shape == (ra.max_new_tokens,)
        np.testing.assert_array_equal(ra.generated, rb.generated)
        np.testing.assert_array_equal(ra.generated, rc.generated)
    if p_eng.pool.maps:
        assert p_eng.pool.shared_hits > 0
    p_eng.pool.check_invariants()
    assert all(m.alloc.n_live == 0 for m in p_eng.pool.maps.values())


@pytest.mark.slow
@pytest.mark.paged
@pytest.mark.parametrize("name", ARCHS)
def test_paged_matches_dense_and_static_shared_prefix_bf16(name):
    """Same trio under the bf16 policy: the cast must not perturb block
    contents differently across pool layouts."""
    (_, a), (_, b), (p_eng, c) = _run_trio(name, "bf16", prefix=16)
    for ra, rb, rc in zip(a, b, c):
        np.testing.assert_array_equal(ra.generated, rb.generated)
        np.testing.assert_array_equal(ra.generated, rc.generated)
    p_eng.pool.check_invariants()


def test_bf16_policy_casts_params_and_matches_static():
    """Tier-1 single-arch version of the bf16 matrix: policy actually
    changes the parameter copy AND the engines still agree."""
    import jax.numpy as jnp
    from repro.serving.engine import apply_serving_policy
    arch, params = setup_arch("gemma2-2b")
    cast_arch, cast = apply_serving_policy(arch, params, "bf16")
    dtypes = {str(l.dtype) for l in jax.tree.leaves(cast)}
    assert "bfloat16" in dtypes        # matmul weights cast
    assert "float32" in dtypes         # LN/bias overrides kept fp32
    assert cast_arch.cfg.compute_dtype == jnp.bfloat16
    a, b = _run_both("gemma2-2b", "bf16")
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.generated, rb.generated)


@pytest.mark.parametrize("name", ARCHS)
def test_left_pad_invariance(name):
    """A short request batched with a longer one (forcing left-padding)
    generates the same tokens as when it runs alone."""
    arch, params = setup_arch(name)
    engine = ServeEngine(arch, params, max_len=MAX_LEN)
    solo = make_requests(arch, [(5, 4)])
    engine.run_batch(solo)
    pair = make_requests(arch, [(5, 4), (13, 4)])
    engine.run_batch(pair)
    np.testing.assert_array_equal(solo[0].generated, pair[0].generated)


def test_slot_reuse_after_eviction():
    """max_batch=1: every request reuses the single slot; the second and
    third must not see the first's cache rows."""
    arch, params = setup_arch("gemma2-2b")
    spec = [(9, 5), (6, 3), (11, 4)]
    solos = make_requests(arch, spec)
    static = ServeEngine(arch, params, max_len=MAX_LEN)
    for r in solos:
        static.run_batch([r])
    eng = ContinuousEngine(arch, params, max_batch=1, max_len=MAX_LEN)
    reqs = make_requests(arch, spec)
    eng.run(reqs)
    assert eng.scheduler.completed == reqs  # FIFO order preserved
    for solo, r in zip(solos, reqs):
        np.testing.assert_array_equal(solo.generated, r.generated)


def test_mid_stream_admission():
    """A request submitted while others are mid-decode joins a freed slot
    and still matches its solo output."""
    arch, params = setup_arch("gemma2-2b")
    static = ServeEngine(arch, params, max_len=MAX_LEN)
    spec = [(7, 8), (9, 2), (6, 5)]
    solos = make_requests(arch, spec)
    for r in solos:
        static.run_batch([r])

    eng = ContinuousEngine(arch, params, max_batch=2, max_len=MAX_LEN)
    r0, r1, r2 = make_requests(arch, spec)
    eng.submit(r0)
    eng.submit(r1)
    for _ in range(3):        # r1 (2 tokens) completes during these steps
        eng.step()
    assert r1.generated is not None and len(eng.scheduler.active) == 1
    eng.submit(r2)            # admitted mid-stream into r1's old slot
    while eng.step():
        pass
    for solo, r in zip(solos, (r0, r1, r2)):
        np.testing.assert_array_equal(solo.generated, r.generated)
    assert eng.steps_run < 8 + 2 + 5  # slots overlapped, not serialized


def test_one_token_request_completes_at_admission():
    arch, params = setup_arch("gemma2-2b")
    eng = ContinuousEngine(arch, params, max_batch=2, max_len=MAX_LEN)
    reqs = make_requests(arch, [(6, 1), (6, 1), (6, 1)])
    eng.run(reqs)
    assert all(r.generated.shape == (1,) for r in reqs)
    assert eng.steps_run == 0  # never needed a decode step


def test_request_validation():
    arch, params = setup_arch("gemma2-2b")
    eng = ContinuousEngine(arch, params, max_batch=1, max_len=16)
    with pytest.raises(ValueError):
        eng.submit(make_requests(arch, [(15, 4)])[0])   # 15 + 4 > 16
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=np.arange(4, dtype=np.int32),
                           max_new_tokens=0))


def test_cache_pool_insert_evict_roundtrip():
    arch, params = setup_arch("gemma2-2b")
    pool = CachePool(arch, max_batch=3, max_len=MAX_LEN)
    _, req_cache = arch.prefill(
        params, {"tokens": np.arange(5, 13, dtype=np.int32)[None]},
        cache_len=MAX_LEN, per_slot=True)
    pool.insert(req_cache, 1)
    assert pool.lengths().tolist() == [0, 8, 0]
    # the occupied slot's first 8 positions are live, the rest invalid
    pos = np.asarray(pool.cache["slots"][1]["pos"])  # full-attn slot
    assert (pos[:, 1, :8] >= 0).all() and (pos[:, 1, 8:] == -1).all()
    assert (pos[:, 0] == -1).all() and (pos[:, 2] == -1).all()
    pool.evict(1)
    assert pool.lengths().tolist() == [0, 0, 0]
    assert (np.asarray(pool.cache["slots"][1]["pos"]) == -1).all()
    with pytest.raises(IndexError):
        pool.insert(req_cache, 3)


def test_pad_prompts_layout():
    tokens, positions, lens = pad_prompts(
        [np.array([3, 4, 5], np.int32), np.array([7], np.int32)],
        granularity=4)
    assert tokens.shape == (2, 4)
    assert tokens[0].tolist() == [0, 3, 4, 5]
    assert positions[0].tolist() == [-1, 0, 1, 2]
    assert positions[1].tolist() == [-3, -2, -1, 0]
    assert lens.tolist() == [3, 1]
    with pytest.raises(ValueError):
        pad_prompts([np.arange(5, dtype=np.int32)], pad_len=4)


def test_scheduler_fifo_and_invariants():
    sched = Scheduler(2)
    for i in range(5):
        sched.submit(f"r{i}")
    pairs = sched.assign()
    assert [r for _, r in pairs] == ["r0", "r1"]
    assert sched.assign() == []           # pool full
    sched.check_invariants()
    slot0 = pairs[0][0]
    assert sched.complete(slot0) == "r0"
    pairs2 = sched.assign()
    assert [r for _, r in pairs2] == ["r2"] and pairs2[0][0] == slot0
    sched.check_invariants()
    # drain everything FIFO
    done = []
    while sched.has_work:
        for slot in sorted(sched.active):
            done.append(sched.complete(slot))
        sched.assign()
        sched.check_invariants()
    assert sorted(sched.completed) == [f"r{i}" for i in range(5)]
    from repro.serving import SchedulerError
    with pytest.raises(SchedulerError):
        sched.complete(0)                 # all slots free: nothing to release


def test_throughput_probe_excludes_compile():
    arch, params = setup_arch("gemma2-2b")
    engine = ServeEngine(arch, params, max_len=MAX_LEN)
    reqs = make_requests(arch, [(6, 3), (8, 3)])
    stats = throughput_probe(engine, reqs)
    assert stats["warmup"] is True
    assert stats["tokens"] == 6 and stats["tokens_per_s"] > 0
    # warmed-up runs should not include multi-second jit compiles
    assert stats["wall_s"] < 5.0


def test_chunked_attention_accepts_per_batch_positions(monkeypatch):
    """Regression (review finding): the remat-chunked query-block path must
    handle 2-D (B, S) positions — a batched left-padded serving prefill
    long enough to trip Q_CHUNK_MIN_SEQ used to crash on the reshape."""
    import jax.numpy as jnp
    from repro.models import attention
    from repro.models.attention import AttnConfig, attn_apply, attn_init
    cfg = AttnConfig(d_model=32, n_heads=2, n_kv_heads=2, head_dim=16)
    p = attn_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32), jnp.float32)
    pos = jnp.stack([jnp.arange(8) - 3, jnp.arange(8)])  # row 0 left-padded
    monkeypatch.setattr(attention, "Q_CHUNK_MIN_SEQ", 10 ** 9)
    out_ref, _ = attn_apply(p, cfg, x, positions=pos,
                            compute_dtype=jnp.float32)
    monkeypatch.setattr(attention, "Q_CHUNK_MIN_SEQ", 8)
    monkeypatch.setattr(attention, "Q_BLOCK", 4)
    out_chunked, _ = attn_apply(p, cfg, x, positions=pos,
                                compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out_chunked), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-5)
