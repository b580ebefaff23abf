"""Paged decode-attention kernel: parity, fetch elision, engine wiring.

Three layers of contract, mirroring the paper's losslessness claim:

  * kernel vs oracle -- the Pallas kernel over the raw pool must match
    the gathered-view masked softmax (the gather path's dataflow) for
    GQA and MLA, over ragged lengths, block edges, chunk edges and dead
    slots (GQA also with NaN-filled fresh VMEM and a race detector);
  * fetch elision is REAL -- NaN-poisoned pool blocks outside every
    slot's live table prefix never reach the output (the PR 2 poisoned
    technique), and no page copy of the GQA kernel (host mirror
    ``gqa_chunk_fetch_ids``) nor index-map step of the MLA kernel
    (``clamped_block_ids``) ever ADDRESSES such a block;
  * engine parity -- ``ServeConfig.attn_kernel='paged'`` serves the
    seeded harness traffic token-identically to the gather oracle path,
    including SparCE skip statistics and the attention fetch telemetry.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.sparse_ops import SparsityConfig
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels import paged_decode_attn as pda
from repro.kernels.paged_decode_attn import (
    clamped_block_ids, decode_attn_block_counts, decode_attn_chunks,
    decode_attn_savings, gqa_chunk_fetch_ids,
)
from repro.models import model as model_lib
from repro.runtime.server import Request, ServeConfig, Server
from serving_harness import Traffic, make_traffic, run_and_check, run_server

BS = 4  # pool rows per block in the kernel-level tests


def _rand_tables(rng, B, max_blocks, lengths, nb):
    """Non-overlapping random live block assignments; dead tail = null."""
    tables = np.zeros((B, max_blocks), np.int32)
    ids = rng.permutation(np.arange(1, nb))
    nxt = 0
    for b in range(B):
        live = -(-int(lengths[b]) // BS)
        tables[b, :live] = ids[nxt:nxt + live]
        nxt += live
    return tables


def _gqa_case(rng, lengths, max_blocks=6, KV=2, g=2, D=16):
    B = len(lengths)
    nb = B * max_blocks + 1
    q = jnp.asarray(rng.normal(size=(B, KV, g, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, BS, KV, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, BS, KV, D)), jnp.float32)
    tables = _rand_tables(rng, B, max_blocks, lengths, nb)
    return q, kp, vp, tables, np.asarray(lengths, np.int32)


# ------------------------------------------------------- kernel vs oracle
@pytest.mark.parametrize("lengths", [
    [1, 9, 24, 13],  # ragged, mid-block
    [8, 16, 4, 12],  # exact block edges
    [1, 1, 1, 1],    # single-row (first-tick prompts)
    [24, 0, 7, 0],   # dead slots interleaved
])
def test_gqa_kernel_matches_gather_oracle(lengths):
    rng = np.random.default_rng(0)
    q, kp, vp, tables, ln = _gqa_case(rng, lengths)
    got = kops.paged_decode_attn(q, kp, vp, jnp.asarray(tables),
                                 jnp.asarray(ln))
    want = kref.paged_gqa_decode_attn_ref(q, kp, vp, jnp.asarray(tables),
                                          jnp.asarray(ln))
    live = np.asarray(ln) > 0
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5)
    # Dead slots produce exactly zero (nothing fetched, nothing dotted).
    assert np.all(np.asarray(got)[~live] == 0.0)


CHUNK_P, CHUNK_MAX_BLOCKS = 3, 8  # a table width that P does not divide


@pytest.fixture
def chunk_p3(monkeypatch):
    """The GQA kernel at P = 3 pool blocks a chunk (the rows it aims for
    cut to 3 blocks), traced afresh on entry and on exit."""
    monkeypatch.setattr(pda, "_CHUNK_ROWS", CHUNK_P * BS)
    pda.paged_gqa_decode_attn.clear_cache()
    assert pda.gqa_chunk_blocks(CHUNK_MAX_BLOCKS, BS, 2, 16, 4) == CHUNK_P
    yield
    pda.paged_gqa_decode_attn.clear_cache()


@pytest.mark.parametrize("edge", [
    1, BS, CHUNK_P * BS - 1, CHUNK_P * BS, CHUNK_P * BS + 1,
    CHUNK_MAX_BLOCKS * BS, 0])
def test_gqa_kernel_chunk_edges_nan_memory_no_races(chunk_p3, edge):
    """Lengths on every chunk edge, a partial last chunk, a dead slot
    between live ones (it only prefetches its neighbour's first chunk),
    under an interpreter whose fresh VMEM is NaN and which watches the
    double buffer for races: stale rows of a partial chunk, or a chunk
    read before its copy lands, would show as NaN or as a race."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    rng = np.random.default_rng(edge)
    q, kp, vp, tables, ln = _gqa_case(
        rng, [edge, CHUNK_P * BS + 2, 0, edge],
        max_blocks=CHUNK_MAX_BLOCKS)
    got = pda.paged_gqa_decode_attn(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(ln),
        interpret=pltpu.InterpretParams(
            uninitialized_memory="nan", detect_races=True))
    assert not interpret_pallas_call.races.races_found
    want = kref.paged_gqa_decode_attn_ref(q, kp, vp, jnp.asarray(tables),
                                          jnp.asarray(ln))
    live = ln > 0
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(got)[~live] == 0.0)


@pytest.mark.parametrize("lengths", [[1, 9, 24, 13], [8, 0, 16, 1]])
def test_mla_kernel_matches_gather_oracle(lengths):
    rng = np.random.default_rng(1)
    B, h, r, rope, max_blocks = len(lengths), 4, 16, 8, 6
    nb = B * max_blocks + 1
    ql = jnp.asarray(rng.normal(size=(B, h, r)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(B, h, rope)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(nb, BS, r)), jnp.float32)
    cr = jnp.asarray(rng.normal(size=(nb, BS, rope)), jnp.float32)
    tables = _rand_tables(rng, B, max_blocks, lengths, nb)
    ln = jnp.asarray(lengths, jnp.int32)
    got = kops.paged_mla_decode_attn(ql, qr, cc, cr, jnp.asarray(tables),
                                     ln, scale=0.25, feat_align=128)
    want = kref.paged_mla_decode_attn_ref(ql, qr, cc, cr,
                                          jnp.asarray(tables), ln,
                                          scale=0.25)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(got)[~live] == 0.0)


def test_kernel_handles_ragged_table_width_and_bf16():
    """max_blocks needs no tile alignment (widths 1, 3, 5, 7), the
    opt-in ``feat_align`` lane padding keeps odd head dims exact, and
    bf16 pools run the same code path at bf16 tolerance."""
    rng = np.random.default_rng(2)
    for max_blocks in (1, 3, 5, 7):
        L = max_blocks * BS
        lengths = [min(L, v) for v in (1, L, max(1, L - 2), L // 2 + 1)]
        q, kp, vp, tables, ln = _gqa_case(
            rng, lengths, max_blocks=max_blocks, D=24)  # 24: not a lane
        got = kops.paged_decode_attn(q, kp, vp, jnp.asarray(tables),
                                     jnp.asarray(ln), feat_align=128)
        want = kref.paged_gqa_decode_attn_ref(
            q, kp, vp, jnp.asarray(tables), jnp.asarray(ln))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    q, kp, vp, tables, ln = _gqa_case(rng, [5, 17, 0, 23])
    got = kops.paged_decode_attn(
        q.astype(jnp.bfloat16), kp.astype(jnp.bfloat16),
        vp.astype(jnp.bfloat16), jnp.asarray(tables), jnp.asarray(ln))
    want = kref.paged_gqa_decode_attn_ref(
        q.astype(jnp.bfloat16), kp.astype(jnp.bfloat16),
        vp.astype(jnp.bfloat16), jnp.asarray(tables), jnp.asarray(ln))
    live = np.asarray(ln) > 0  # dead slots: kernel 0s, oracle uniform-p
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live],
        np.asarray(want, np.float32)[live], rtol=3e-2, atol=3e-2)
    assert np.all(np.asarray(got, np.float32)[~live] == 0.0)


# --------------------------------------------------------- fetch elision
def test_nan_poisoned_dead_blocks_never_reach_output():
    """Poison every pool block OUTSIDE the live table prefixes with NaN
    (freed blocks, blocks past each live length, unreferenced pool
    tail): outputs must be bit-identical to the clean pool -- a masked
    fetch would still propagate NaN through 0 * NaN, so this proves the
    dead data never enters the accumulator at all."""
    rng = np.random.default_rng(3)
    q, kp, vp, tables, ln = _gqa_case(rng, [9, 0, 24, 3])
    base = kops.paged_decode_attn(q, kp, vp, jnp.asarray(tables),
                                  jnp.asarray(ln))
    P = pda.gqa_chunk_blocks(tables.shape[1], BS, 2, 16, 4)
    live_ids = {i for chunks in gqa_chunk_fetch_ids(tables, ln, BS, P)
                for chunk in chunks for i in chunk}
    dead = np.array([i for i in range(kp.shape[0]) if i not in live_ids])
    assert dead.size > 0
    kp2 = np.asarray(kp).copy()
    vp2 = np.asarray(vp).copy()
    kp2[dead] = np.nan
    vp2[dead] = np.nan
    poisoned = kops.paged_decode_attn(
        jnp.asarray(q), jnp.asarray(kp2), jnp.asarray(vp2),
        jnp.asarray(tables), jnp.asarray(ln))
    assert np.all(np.isfinite(np.asarray(poisoned)))
    np.testing.assert_array_equal(np.asarray(base), np.asarray(poisoned))


def test_nan_poison_past_live_length_in_same_block():
    """Rows past the live length INSIDE the last live block are fetched
    (block granularity) but must be masked out of the softmax."""
    rng = np.random.default_rng(4)
    q, kp, vp, tables, ln = _gqa_case(rng, [6, 2])  # mid-block lengths
    base = kops.paged_decode_attn(q, kp, vp, jnp.asarray(tables),
                                  jnp.asarray(ln))
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    for b in range(2):
        last_blk = tables[b, (int(ln[b]) - 1) // BS]
        kp2[last_blk, int(ln[b]) % BS:] = 1e9  # huge, not NaN: masked by
        vp2[last_blk, int(ln[b]) % BS:] = -1e9  # -inf scores, exp -> 0
    poisoned = kops.paged_decode_attn(
        jnp.asarray(q), jnp.asarray(kp2), jnp.asarray(vp2),
        jnp.asarray(tables), jnp.asarray(ln))
    np.testing.assert_allclose(np.asarray(base), np.asarray(poisoned),
                               rtol=1e-5, atol=1e-5)


def test_index_map_clamp_never_addresses_dead_blocks():
    """The MLA kernel's fetch-elision contract in closed form: for EVERY
    grid step the clamped index map resolves to a block inside the slot's live
    table prefix (or the slot's entry 0 when dead) -- a dead/null/
    past-length table entry is never even addressed, which is what
    distinguishes fetch elision from post-fetch masking."""
    rng = np.random.default_rng(5)
    B, max_blocks = 5, 8
    lengths = np.array([0, 1, BS, 3 * BS - 1, max_blocks * BS], np.int32)
    tables = _rand_tables(rng, B, max_blocks, lengths, B * max_blocks + 1)
    # Dead table entries deliberately point at poisoned ids: if the
    # clamp ever consulted them, the assertion below would catch it.
    poison = 10_000
    for b in range(B):
        live = -(-int(lengths[b]) // BS)
        tables[b, live:] = poison
    ids = clamped_block_ids(tables, lengths, BS)
    for b in range(B):
        live = max(1, -(-int(lengths[b]) // BS))
        allowed = set(tables[b, :live].tolist()) | {int(tables[b, 0])}
        assert set(ids[b].tolist()) <= allowed
        assert poison not in set(ids[b].tolist()) or lengths[b] == 0


@pytest.mark.parametrize("P", [1, 3, 8])
def test_gqa_chunk_copies_never_address_dead_blocks(P):
    """The GQA kernel's page copies in closed form: every chunk copy
    names a table entry inside the slot's live prefix, in table order,
    each live entry exactly once, ``P`` to a chunk with only the last
    one partial; a dead slot names nothing. Entries past the prefix
    point at a poison id that no copy may name."""
    rng = np.random.default_rng(P)
    B, max_blocks = 6, 10
    lengths = np.array([0, 1, BS, P * BS + 1, 3 * BS - 1,
                        max_blocks * BS], np.int32)
    tables = _rand_tables(rng, B, max_blocks, lengths, B * max_blocks + 1)
    poison = 10_000
    for b in range(B):
        tables[b, -(-int(lengths[b]) // BS):] = poison
    copies = gqa_chunk_fetch_ids(tables, lengths, BS, P)
    for b in range(B):
        live = -(-int(lengths[b]) // BS)
        chunks = copies[b]
        assert [i for c in chunks for i in c] == tables[b, :live].tolist()
        assert all(len(c) == P for c in chunks[:-1])
        assert len(chunks) == -(-live // P)
    assert copies[0] == []
    assert decode_attn_chunks(lengths, BS, P) == sum(map(len, copies))


def test_block_savings_accounting():
    fetched, total = decode_attn_block_counts([0, 1, 8, 9], 6, BS)
    assert (fetched, total) == (0 + 1 + 2 + 3, 4 * 6)
    assert decode_attn_savings([0, 1, 8, 9], 6, BS) == 1.0 - 6 / 24
    assert decode_attn_savings([], 6, BS) == 0.0
    assert decode_attn_chunks([0, 1, 8, 9], BS, 2) == 0 + 1 + 1 + 2


# ---------------------------------------------------------- engine parity
def _serve(cfg, params, attn_kernel, traffic, sp=None, block=8, slots=3,
           oracle=True):
    sc = ServeConfig(batch_slots=slots, max_len=64, kv_block_size=block,
                     sparsity=sp, attn_kernel=attn_kernel)
    check = run_and_check if oracle else run_server
    return check(cfg, params, sc, make_traffic(cfg, traffic))


def _engine_parity(arch, relu=False, eos_prob=0.0, seed=3, oracle=True):
    cfg = get_config(arch).reduced()
    sp = None
    if relu:
        cfg = dataclasses.replace(cfg, mlp_act="relu")
        sp = SparsityConfig(enabled=True, mode="reference", block_m=1,
                            block_k=128)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    traffic = Traffic(n_requests=5, prompt_lens=(2, 12), max_new=(1, 8),
                      seed=seed, eos_prob=eos_prob)
    done_g, m_g, _ = _serve(cfg, params, "gather", traffic, sp,
                            oracle=oracle)
    done_p, m_p, _ = _serve(cfg, params, "paged", traffic, sp,
                            oracle=oracle)
    out_g = {r.uid: r.out for r in done_g}
    for r in done_p:
        np.testing.assert_array_equal(r.out, out_g[r.uid])
    assert m_p["skipped_tile_dots"] == m_g["skipped_tile_dots"]
    assert m_p["total_tile_dots"] == m_g["total_tile_dots"]
    assert m_p["decode_tokens"] == m_g["decode_tokens"]
    return m_g, m_p


def test_engine_gqa_paged_kernel_token_identical_with_skip_stats():
    """GQA serving (oracle-checked by the harness) is token-identical
    across attention kernels, SparCE MLP skip stats included, and the
    paged run reports realized fetch telemetry."""
    m_g, m_p = _engine_parity("smollm-135m", relu=True)
    assert m_p["attn_kernel_paged"] == 1.0 and m_g["attn_kernel_paged"] == 0.0
    assert 0.0 < m_p["attn_block_skip_fraction"] < 1.0
    assert m_p["attn_bytes_paged"] < m_p["attn_bytes_gather"]
    assert m_p["modeled_attn_bytes_saved"] > 0
    # The gather engine reports what the kernel WOULD skip but realizes
    # no saving; the block accounting itself is schedule-identical.
    assert m_g["modeled_attn_bytes_saved"] == 0.0
    assert m_g["attn_blocks_fetched"] == m_p["attn_blocks_fetched"]
    # One chunk covers a whole table here (P = max_blocks), so the GQA
    # kernel runs one chunk step per live slot a tick; the gather path
    # runs none.
    assert m_p["attn_chunk_blocks"] == 64 // 8
    assert m_p["attn_chunks"] == m_p["decode_tokens"]
    assert m_g["attn_chunks"] == 0.0


def test_engine_mla_paged_kernel_token_identical():
    """DeepSeek MLA absorbed decode through the paged kernel: the
    latent-space pool path must reproduce the gather engine exactly.
    (No cache-free oracle here: MoE capacity routing is batch-shape
    dependent by design -- see test_server._setup -- so the contract is
    engine-vs-engine parity, the tentpole invariant.)"""
    _engine_parity("deepseek-v3-671b", oracle=False)


def test_engine_paged_kernel_with_eos_midstream():
    """EOS releases mid-stream free blocks while neighbours keep
    decoding over them -- the paged kernel must track the shrinking
    live tables tick by tick."""
    _engine_parity("smollm-135m", eos_prob=0.6, seed=7)


def test_engine_single_block_and_block_edge_prompts():
    """Single-block prompts (the whole request lives in one block) and a
    prompt landing exactly on a block edge, through the paged kernel."""
    cfg = get_config("smollm-135m").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    reqs = [
        Request(uid=0, prompt=np.array([1, 2]), max_new=1),  # one block
        Request(uid=1, prompt=np.array([3, 4, 5, 6, 7, 8, 9, 10]),
                max_new=5),  # prompt == 8 rows == exactly 2 blocks of 4
    ]
    sc = ServeConfig(batch_slots=2, max_len=32, kv_block_size=4,
                     attn_kernel="paged")
    done, m, _ = run_and_check(cfg, params, sc, reqs)
    assert len(done) == 2
    assert m["attn_blocks_fetched"] > 0


def test_attn_kernel_paged_requires_paged_layout():
    cfg = get_config("smollm-135m").reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="paged KV layout"):
        Server(cfg, params, ServeConfig(kv_block_size=0,
                                        attn_kernel="paged"))
    with pytest.raises(ValueError, match="attn_kernel"):
        Server(cfg, params, ServeConfig(attn_kernel="flash"))


# ------------------------------------------------------- property testing
@pytest.mark.slow
def test_random_block_tables_kernel_parity_property():
    """Hypothesis sweep: random lengths/table permutations keep the
    kernel equal to the gathered-view oracle -- a wrong clamp or a
    misrouted block WOULD change the output."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 10_000),
           max_blocks=st.integers(1, 8),
           data=st.data())
    def run(seed, max_blocks, data):
        rng = np.random.default_rng(seed)
        L = max_blocks * BS
        lengths = data.draw(
            st.lists(st.integers(0, L), min_size=3, max_size=3))
        q, kp, vp, tables, ln = _gqa_case(
            rng, lengths, max_blocks=max_blocks)
        got = kops.paged_decode_attn(q, kp, vp, jnp.asarray(tables),
                                     jnp.asarray(ln))
        want = kref.paged_gqa_decode_attn_ref(
            q, kp, vp, jnp.asarray(tables), jnp.asarray(ln))
        live = np.asarray(ln) > 0
        np.testing.assert_allclose(
            np.asarray(got)[live], np.asarray(want)[live],
            rtol=2e-5, atol=2e-5)
        assert np.all(np.asarray(got)[~live] == 0.0)

    run()
