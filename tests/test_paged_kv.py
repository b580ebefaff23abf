"""Paged KV cache + bucketed prefill: parity, boundaries, trace counts.

The contract mirrors the paper's losslessness claim at the cache layer:
swapping the contiguous per-slot reservation for the shared block pool
(and padding prefill up to buckets) must change NOTHING observable --
token streams, per-request stats and SparCE skip accounting are
bit-identical -- while the pool reserves measurably less HBM.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.sparse_ops import SparsityConfig
from repro.models import model as model_lib
from repro.runtime.paging import (
    BlockAllocator, blocks_needed, default_buckets, pick_bucket,
    resolve_buckets,
)
from repro.runtime.server import Request, ServeConfig, Server
from serving_harness import (
    Traffic, make_traffic, oracle_outputs, run_and_check, run_server,
)


def _setup(arch="smollm-135m", relu=False):
    cfg = get_config(arch).reduced()
    if relu:
        cfg = dataclasses.replace(cfg, mlp_act="relu")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _paged(max_len=64, block=8, pool=None, **kw):
    return ServeConfig(max_len=max_len, kv_block_size=block,
                       kv_pool_blocks=pool, **kw)


def _contig(max_len=64, **kw):
    return ServeConfig(max_len=max_len, kv_block_size=0, **kw)


# ------------------------------------------------------------- host utils
def test_block_allocator_invariants():
    a = BlockAllocator(5)
    got = a.alloc(3)
    assert len(set(got)) == 3 and 0 not in got
    assert a.available == 2 and a.in_use == 3
    a.free(got[:2])
    a.check()
    assert a.available == 4
    with pytest.raises(RuntimeError, match="double-free"):
        a.free([got[0]])
    with pytest.raises(RuntimeError, match="exhausted"):
        a.alloc(5)
    a.check()


def test_bucket_resolution():
    assert default_buckets(64) == (4, 8, 16, 32, 64)
    assert resolve_buckets(None, 64) == (4, 8, 16, 32, 64)
    # user buckets are clipped and max_len always appended
    assert resolve_buckets((8, 128, 24), 64) == (8, 24, 64)
    assert resolve_buckets((), 64) == ()  # bucketing disabled
    assert pick_bucket(5, (4, 8, 16)) == 8
    assert pick_bucket(8, (4, 8, 16)) == 8  # exact boundary: no padding
    assert blocks_needed(0, 8) == 0
    assert blocks_needed(8, 8) == 1
    assert blocks_needed(9, 8) == 2


# ---------------------------------------------------------------- parity
def test_paged_matches_contiguous_tokens_stats_and_skips():
    """Identical seeded traffic through both layouts: token streams,
    per-request stats and SparCE tile-skip counts must be EQUAL, and the
    paged pool must report its reservation telemetry."""
    cfg, params = _setup(relu=True)
    traffic = Traffic(n_requests=6, prompt_lens=(2, 12), max_new=(1, 8),
                      seed=3)
    sp = SparsityConfig(enabled=True, mode="reference", block_m=1,
                        block_k=128)
    done_c, m_c, _ = run_and_check(
        cfg, params, _contig(batch_slots=3, sparsity=sp),
        make_traffic(cfg, traffic))
    done_p, m_p, _ = run_and_check(
        cfg, params, _paged(batch_slots=3, sparsity=sp),
        make_traffic(cfg, traffic))
    out_c = {r.uid: r for r in done_c}
    for r in done_p:
        np.testing.assert_array_equal(r.out, out_c[r.uid].out)
        assert r.stats["tokens"] == out_c[r.uid].stats["tokens"]
        assert r.stats["decode_ticks"] == out_c[r.uid].stats["decode_ticks"]
    # Same prefill buckets + same tick schedule => identical skip work.
    assert m_p["skipped_tile_dots"] == m_c["skipped_tile_dots"]
    assert m_p["total_tile_dots"] == m_c["total_tile_dots"]
    assert m_p["decode_tokens"] == m_c["decode_tokens"]
    assert m_p["kv_paged"] == 1.0 and m_c["kv_paged"] == 0.0
    assert m_p["kv_blocks_peak_in_use"] > 0
    assert 0.0 < m_p["kv_pool_peak_occupancy"] <= 1.0


def test_paged_with_eos_traffic_matches_contiguous():
    """EOS-bearing traffic exercises early release + block reuse; both
    layouts must still agree with each other and the oracle."""
    cfg, params = _setup()
    traffic = Traffic(n_requests=5, prompt_lens=(2, 10), max_new=(2, 8),
                      seed=7, eos_prob=0.6)
    reqs = make_traffic(cfg, traffic)
    done_c, _, _ = run_and_check(cfg, params, _contig(batch_slots=2), reqs)
    done_p, _, _ = run_and_check(
        cfg, params, _paged(batch_slots=2), make_traffic(cfg, traffic))
    out_c = {r.uid: r.out for r in done_c}
    for r in done_p:
        np.testing.assert_array_equal(r.out, out_c[r.uid])


def test_oversubscribed_pool_shares_hbm_and_stays_exact():
    """A pool SMALLER than slots x max_len (the whole point of paging):
    admission waits on the free list instead of a slot, long and short
    requests share the same physical blocks, outputs stay oracle-exact,
    and the reservation telemetry shows the saving."""
    cfg, params = _setup()
    traffic = Traffic(n_requests=6, prompt_lens=(2, 10), max_new=(2, 10),
                      seed=11)
    # 3 slots x max_len=64 / block=8 would be 24 blocks; give it 8.
    done, m, _ = run_and_check(
        cfg, params, _paged(batch_slots=3, block=8, pool=8),
        make_traffic(cfg, traffic))
    assert len(done) == 6
    assert m["kv_blocks_peak_in_use"] <= 8
    assert m["kv_bytes_saved_frac"] > 0.6  # 8 blocks vs 24 reserved
    assert m["kv_bytes_reserved"] < m["kv_bytes_reserved_contiguous"]
    assert m["kv_reserved_bytes_per_token"] > 0


# ------------------------------------------------------------- boundaries
def test_request_ending_exactly_on_block_edge():
    """rows = prompt + max_new - 1 lands exactly on a block boundary: the
    engine must NOT allocate (or touch) a block past the edge."""
    cfg, params = _setup()
    # prompt 4 rows + 4 decode writes = 8 rows = exactly 2 blocks of 4.
    done, m, _ = run_and_check(
        cfg, params, _paged(batch_slots=1, block=4, max_len=32),
        [Request(uid=0, prompt=np.array([1, 2, 3, 4]), max_new=5)])
    assert len(done[0].out) == 5
    assert m["kv_blocks_peak_in_use"] == 2.0
    # One more token crosses the edge: the third block is claimed lazily.
    done, m, _ = run_and_check(
        cfg, params, _paged(batch_slots=1, block=4, max_len=32),
        [Request(uid=0, prompt=np.array([1, 2, 3, 4]), max_new=6)])
    assert len(done[0].out) == 6
    assert m["kv_blocks_peak_in_use"] == 3.0


def test_prompt_exactly_equal_to_block_size_starts_fresh_block():
    """First decode write of a block-aligned prompt opens a NEW block on
    the first tick (the lazy-growth edge case)."""
    cfg, params = _setup()
    done, m, _ = run_and_check(
        cfg, params, _paged(batch_slots=1, block=4, max_len=32),
        [Request(uid=0, prompt=np.array([5, 6, 7, 8]), max_new=2)])
    assert len(done[0].out) == 2
    # prompt fills block 1 exactly; tick 1 writes row 4 -> block 2.
    assert m["kv_blocks_peak_in_use"] == 2.0


def test_prompt_exactly_equal_to_bucket_size():
    """A prompt that IS a bucket length takes the no-padding path and
    still matches the oracle and the bucketing-disabled engine."""
    cfg, params = _setup()
    req = [Request(uid=0, prompt=np.arange(8) % cfg.vocab_size, max_new=4)]
    done_b, _, srv = run_and_check(
        cfg, params, _paged(batch_slots=1), list(req))
    done_e, _, _ = run_and_check(
        cfg, params, _paged(batch_slots=1, prefill_buckets=()), list(req))
    np.testing.assert_array_equal(done_b[0].out, done_e[0].out)
    # no padding happened: a prefill trace exists at exactly S=8
    assert any(s[1:] == (cfg.frontend, 8) for s in srv._prefill_shapes)


def test_admission_with_exactly_enough_blocks():
    """Free list holding EXACTLY the worst-case blocks admits; one block
    short refuses up front (it could never be served)."""
    cfg, params = _setup()
    # prompt 5 + max_new 4 -> worst 8 rows -> exactly 2 blocks of 4.
    req = lambda: [Request(uid=0, prompt=np.array([1, 2, 3, 4, 5]),
                           max_new=4)]
    done, m, _ = run_and_check(
        cfg, params, _paged(batch_slots=2, block=4, pool=2, max_len=32),
        req())
    assert len(done[0].out) == 4
    assert m["kv_pool_peak_occupancy"] == 1.0  # used every block it had
    srv = Server(cfg, params,
                 _paged(batch_slots=2, block=4, pool=1, max_len=32))
    with pytest.raises(ValueError, match="KV blocks"):
        srv.generate(req())


def test_second_request_waits_for_free_blocks_not_free_slot():
    """Two free SLOTS but pool room for one worst-case request: the
    second admits only after the first releases its blocks -- admission
    is gated on blocks now, and nothing deadlocks or corrupts."""
    cfg, params = _setup()
    reqs = [
        Request(uid=0, prompt=np.array([1, 2, 3]), max_new=6),  # 2 blocks
        Request(uid=1, prompt=np.array([7, 8, 9]), max_new=6),  # 2 blocks
    ]
    done, m, _ = run_and_check(
        cfg, params, _paged(batch_slots=2, block=4, pool=2, max_len=32),
        reqs)
    assert sorted(r.uid for r in done) == [0, 1]
    assert m["admitted"] == 2 and m["completed"] == 2
    # Never more blocks in flight than the pool owns.
    assert m["kv_blocks_peak_in_use"] <= 2.0


# ------------------------------------------------------- bucketed prefill
def test_masked_prefill_matches_exact_length_within_f32_rounding():
    """Padded-to-bucket prefill with advance/last-real-logit gather
    matches the exact-length prefill: same cache lengths, and the same
    last-position logits up to float32 rounding. The padded run reduces
    over 16 positions where the exact one reduces over 5, so XLA may
    order the sums differently (2.7e-7 apart on its CPU backend); the
    tolerance is a few float32 ulps of logits of magnitude ~1."""
    import jax.numpy as jnp
    for arch in ("smollm-135m", "musicgen-large"):
        cfg, params = _setup(arch)
        rng = np.random.default_rng(0)
        S, pad_to = 5, 16
        if cfg.frontend == "codes":
            toks = rng.integers(0, cfg.vocab_size,
                                (1, cfg.num_codebooks, S)).astype(np.int32)
            padded = np.zeros((1, cfg.num_codebooks, pad_to), np.int32)
            padded[..., :S] = toks
        else:
            toks = rng.integers(0, cfg.vocab_size, (1, S)).astype(np.int32)
            padded = np.zeros((1, pad_to), np.int32)
            padded[..., :S] = toks
        lg_e, c_e, _ = model_lib.forward(
            params, cfg, {"tokens": jnp.asarray(toks)},
            model_lib.init_caches(cfg, 1, pad_to), last_only=True)
        lg_b, c_b, _ = model_lib.forward(
            params, cfg,
            {"tokens": jnp.asarray(padded),
             "advance": jnp.asarray([S], jnp.int32)},
            model_lib.init_caches(cfg, 1, pad_to), last_only=True)
        np.testing.assert_allclose(
            np.asarray(lg_e), np.asarray(lg_b), rtol=1e-5, atol=1e-5)
        assert int(c_e["stack"].length[0][0]) == S
        assert int(c_b["stack"].length[0][0]) == S


def test_trace_count_bounded_by_buckets_under_random_lengths():
    """50 random prompt lengths compile at most len(buckets) prefill
    traces (jit-cache probe) -- the seed engine compiled one per DISTINCT
    length. max_new=1 keeps this prefill-only and fast."""
    cfg, params = _setup()
    rng = np.random.default_rng(0)
    reqs = [
        Request(uid=i,
                prompt=rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(1, 61))),
                max_new=1)
        for i in range(50)
    ]
    done, m, srv = run_server(
        cfg, params, _paged(batch_slots=4, max_len=64), reqs)
    assert len(done) == 50
    buckets = srv._buckets
    assert len(buckets) == 5  # (4, 8, 16, 32, 64)
    assert srv.prefill_trace_count() <= len(buckets)
    assert m["prefill_traces"] <= len(buckets)
    # Sanity: the traffic really did span many distinct lengths.
    assert len({int(np.asarray(r.prompt).shape[-1]) for r in reqs}) > 20
    # Spot-check correctness of a few against the oracle.
    want = oracle_outputs(params, cfg, reqs[:5])
    for r in done:
        if r.uid < 5:
            np.testing.assert_array_equal(r.out, want[r.uid])


# ------------------------------------------------------- property testing
@pytest.mark.slow
def test_random_admit_release_never_leaks_or_double_allocates():
    """Hypothesis: any interleaving of alloc/free on the pool preserves
    the partition invariant -- no block is ever lost or handed out twice
    (the failure modes that silently corrupt neighbouring requests)."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=200, deadline=None)
    @given(st.lists(st.tuples(st.booleans(), st.integers(0, 6)),
                    max_size=60))
    def run(ops):
        a = BlockAllocator(12)
        held = []
        for is_alloc, n in ops:
            if is_alloc:
                if n <= a.available:
                    got = a.alloc(n)
                    assert len(set(got)) == len(got)
                    assert not (set(got) & set(held)), "double allocation"
                    held.extend(got)
                else:
                    with pytest.raises(RuntimeError):
                        a.alloc(n)
            elif held:
                k = min(n, len(held))
                to_free, held = held[:k], held[k:]
                a.free(to_free)
            a.check()
            assert a.available + a.in_use == a.num_blocks
        a.free(held)
        a.check()
        assert a.available == a.num_blocks, "leaked blocks"

    run()


@pytest.mark.slow
def test_random_traffic_paged_parity_property():
    """Hypothesis sweep: random seeded traffic shapes keep paged ==
    contiguous token parity (the end-to-end no-leak/no-corruption
    witness: a lost or double-mapped block WOULD change tokens)."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cfg, params = _setup()

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(0, 10_000),
           pool=st.integers(6, 12))
    def run(seed, pool):
        traffic = Traffic(n_requests=4, prompt_lens=(1, 10),
                          max_new=(1, 6), seed=seed, eos_prob=0.3)
        done_c, _, _ = run_server(
            cfg, params, _contig(batch_slots=2),
            make_traffic(cfg, traffic))
        done_p, _, _ = run_server(
            cfg, params, _paged(batch_slots=2, block=4, pool=pool),
            make_traffic(cfg, traffic))
        out_c = {r.uid: r.out for r in done_c}
        for r in done_p:
            np.testing.assert_array_equal(r.out, out_c[r.uid])

    run()
