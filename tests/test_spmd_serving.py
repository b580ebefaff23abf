"""SPMD sharded serving: multi-device parity against the single-host
engine, which is itself oracle-checked (tests/serving_harness.py).

Contract (the paper's losslessness claim, lifted to a device mesh):
sharding is an EXECUTION detail, never a semantics change. For every
(data, model) mesh shape the sharded engine must reproduce the
single-host run token for token, and the psum'd skip statistics --
MLP tile-dots skipped/issued, attention pool blocks fetched/total --
must equal the single-host counters EXACTLY (each shard's bitmap gates
its own stripes, the per-core SpRF; the engine psums them back into one
ledger, the paper's aggregated telemetry). The NaN-poison tests port
the PR 2/5 fetch-elision proofs to the shard_map'd kernels: a dead
block or stripe on ANY shard must never reach an accumulator.

Runs on the conftest-forced 8-device CPU host (cpu_mesh fixture).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core.sparse_ops import SparsityConfig
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.paged_decode_attn import (
    gqa_chunk_blocks, gqa_chunk_fetch_ids,
)
from repro.parallel import sharding as shard_lib
from repro.runtime.server import ServeConfig
from serving_harness import Traffic, make_traffic, run_server

pytestmark = pytest.mark.mesh

MESHES = [(2, 1), (1, 2), (2, 2)]  # data x model

# Exact-parity fields: token counters and the psum'd skip/fetch ledger.
PARITY_KEYS = (
    "prefill_tokens", "decode_tokens", "ticks", "admitted", "completed",
    "skipped_tile_dots", "total_tile_dots", "mlp_skip_fraction",
    "attn_blocks_fetched", "attn_blocks_total", "attn_block_skip_fraction",
    "kv_blocks_peak_in_use", "modeled_hbm_bytes_saved",
    "modeled_attn_bytes_saved",
)


def _relu_setup():
    """Reduced dense arch on the paper's sparsity source (relu MLP),
    reference-mode SparCE with mesh-divisible tiles (block_m=1 divides
    every per-shard slot count; block_k=32 divides d_ff/tp = 64)."""
    cfg = dataclasses.replace(
        get_config("smollm-135m").reduced(), mlp_act="relu")
    params = jax.tree.map(
        lambda x: x,  # fresh copy per call: Server may device_put params
        _relu_setup.params) if hasattr(_relu_setup, "params") else None
    if params is None:
        from repro.models import model as model_lib
        params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
        _relu_setup.params = params
    sp = SparsityConfig(enabled=True, mode="reference",
                        block_m=1, block_k=32)
    return cfg, params, sp


def _serve_cfg(sp, mesh=None, **kw):
    base = dict(batch_slots=4, max_len=64, temperature=0.0, seed=0,
                sparsity=sp, kv_block_size=8, attn_kernel="paged",
                mesh=mesh)
    base.update(kw)
    return ServeConfig(**base)


def _run(cfg, params, scfg, traffic):
    done, metrics, srv = run_server(
        cfg, params, scfg, make_traffic(cfg, traffic))
    toks = {r.uid: np.asarray(r.out).copy() for r in done}
    return toks, metrics


# --------------------------------------------------- engine token parity
TRAFFIC = Traffic(n_requests=6, prompt_lens=(2, 12), max_new=(1, 8),
                  seed=0, eos_prob=0.3)  # EOS midstream -> slot release


@pytest.fixture(scope="module")
def relu_baseline(cpu_mesh):
    """Single-host oracle run the mesh shapes are compared against."""
    cfg, params, sp = _relu_setup()
    toks, m = _run(cfg, params, _serve_cfg(sp), TRAFFIC)
    return cfg, params, sp, toks, m


@pytest.mark.parametrize("mesh", MESHES)
def test_sharded_engine_token_and_skip_parity(relu_baseline, mesh):
    """Token-identical rollouts AND exact psum'd skip-stat parity on
    every mesh shape, over paged attention, reference SparCE and
    EOS-midstream slot release."""
    cfg, params, sp, want_toks, want_m = relu_baseline
    toks, m = _run(cfg, params, _serve_cfg(sp, mesh=mesh), TRAFFIC)
    assert toks.keys() == want_toks.keys()
    for uid in want_toks:
        np.testing.assert_array_equal(
            toks[uid], want_toks[uid],
            err_msg=f"mesh={mesh} diverged from single-host on uid={uid}")
    for k in PARITY_KEYS:
        assert m[k] == want_m[k], (
            f"mesh={mesh}: psum'd {k}={m[k]} != single-host {want_m[k]}")
    assert (m.mesh_data, m.mesh_model) == tuple(map(float, mesh))
    assert want_m.mesh_data == want_m.mesh_model == 0.0
    # The workload actually exercised what it claims to.
    assert want_m.skipped_tile_dots > 0 and want_m.attn_blocks_total > 0
    assert want_m.completed == TRAFFIC.n_requests


def test_sharded_engine_glu_fused_parity(cpu_mesh):
    """Gated-GLU arch with the FUSED megakernel on the full (2, 2) mesh:
    the gate bitmap is emitted per model shard (its own d_ff/tp columns)
    yet the psum'd skip ledger must match single-host exactly."""
    cfg = get_config("smollm-135m").reduced()  # silu: gated GLU
    from repro.models import model as model_lib
    params = model_lib.init_params(cfg, jax.random.PRNGKey(1))
    sp = SparsityConfig(enabled=True, mode="fused", block_m=1,
                        block_k=32, gate_threshold=0.0)
    traffic = Traffic(n_requests=5, prompt_lens=(2, 10), max_new=(1, 6),
                      seed=2, eos_prob=0.2)
    want_toks, want_m = _run(cfg, params, _serve_cfg(sp), traffic)
    toks, m = _run(cfg, params, _serve_cfg(sp, mesh=(2, 2)), traffic)
    for uid in want_toks:
        np.testing.assert_array_equal(toks[uid], want_toks[uid])
    for k in PARITY_KEYS:
        assert m[k] == want_m[k], (k, m[k], want_m[k])


# ----------------------------------------------------------- prefix cache
def _prefix_traffic(cfg, n=6, shared_len=16, seed=3):
    """Traffic whose prompts share a seeded 16-token prefix (2 full
    8-row blocks), the workload the prefix cache is built for."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, shared_len)
    reqs = make_traffic(cfg, Traffic(
        n_requests=n, prompt_lens=(1, 8), max_new=(1, 6), seed=seed))
    return [dataclasses.replace(
        r, prompt=np.concatenate([shared, np.asarray(r.prompt)]))
        for r in reqs]


def _run_reqs(cfg, params, scfg, reqs):
    done, metrics, _ = run_server(
        cfg, params, scfg,
        [dataclasses.replace(r) for r in reqs])
    return {r.uid: np.asarray(r.out).copy() for r in done}, metrics


def test_prefix_cache_exact_parity_on_model_mesh(cpu_mesh):
    """On a pure tensor-parallel mesh (1, 2) there is ONE data shard, so
    the prefix index is byte-identical to single-host: hits, shared
    blocks, CoW forks and the modeled savings must all match exactly."""
    cfg, params, sp = _relu_setup()
    reqs = _prefix_traffic(cfg)
    base = dict(prefix_cache=True)
    want_toks, want_m = _run_reqs(
        cfg, params, _serve_cfg(sp, **base), reqs)
    toks, m = _run_reqs(
        cfg, params, _serve_cfg(sp, mesh=(1, 2), **base), reqs)
    for uid in want_toks:
        np.testing.assert_array_equal(toks[uid], want_toks[uid])
    assert want_m.prefix_hits > 0 and want_m.prefix_blocks_shared > 0
    for k in ("prefix_lookups", "prefix_hits", "prefix_matched_tokens",
              "prefix_blocks_shared", "prefix_cow_forks",
              "prefill_ticks_saved", "prefill_tokens", "decode_tokens",
              "skipped_tile_dots", "total_tile_dots"):
        assert m[k] == want_m[k], (k, m[k], want_m[k])


@pytest.mark.parametrize("mesh", [(2, 1), (2, 2)])
def test_prefix_cache_token_parity_on_data_mesh(cpu_mesh, mesh):
    """With dp > 1 each data shard keeps its OWN prefix index (blocks
    cannot be shared across sub-pools), so hit counts legitimately
    differ -- but tokens must still match the cache-OFF single-host run
    exactly, and the DECODE-phase skip ledger must be identical (only
    suffix prefills shrink; see ServeMetrics.prefill_*_tile_dots)."""
    cfg, params, sp = _relu_setup()
    reqs = _prefix_traffic(cfg)
    want_toks, want_m = _run_reqs(cfg, params, _serve_cfg(sp), reqs)
    toks, m = _run_reqs(
        cfg, params, _serve_cfg(sp, mesh=mesh, prefix_cache=True), reqs)
    for uid in want_toks:
        np.testing.assert_array_equal(toks[uid], want_toks[uid])
    assert m.prefix_hits > 0  # per-shard indexes still hit
    decode_skip = m.skipped_tile_dots - m.prefill_skipped_tile_dots
    want_decode_skip = (want_m.skipped_tile_dots
                        - want_m.prefill_skipped_tile_dots)
    assert decode_skip == want_decode_skip


# ----------------------------------- sharded-kernel fetch elision (NaN)
BS = 4  # pool rows per block


def _sharded_gqa_case(rng, dp, lengths_per_shard, max_blocks=4, KV=4,
                      g=2, D=16):
    """Engine-layout GQA case: dp sub-pools (each with its own null
    block 0), shard-LOCAL tables, per-shard live lengths."""
    shard_nb = max(
        sum(-(-int(ln) // BS) for ln in lens) + 1
        for lens in lengths_per_shard) + 1
    B = sum(len(lens) for lens in lengths_per_shard)
    nb = dp * shard_nb
    q = jnp.asarray(rng.normal(size=(B, KV, g, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, BS, KV, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, BS, KV, D)), jnp.float32)
    tables = np.zeros((B, max_blocks), np.int32)
    lengths = np.zeros((B,), np.int32)
    row = 0
    for lens in lengths_per_shard:
        nxt = 1  # local id 0 is this shard's null block
        for ln in lens:
            live = -(-int(ln) // BS)
            tables[row, :live] = np.arange(nxt, nxt + live)
            lengths[row] = ln
            nxt += live
            row += 1
    return q, kp, vp, tables, lengths, shard_nb


def test_sharded_paged_attn_nan_poisoned_blocks_never_fetched(cpu_mesh):
    """shard_map'd paged decode attention on a (2, 2) mesh: slots and
    the pool shard over 'data' (shard-local tables + per-shard null
    block), KV heads over 'model'. NaN-poisoning every block outside
    the live table prefixes OF EACH SHARD leaves the output
    bit-identical -- per-shard fetch elision survives sharding."""
    dp, tp = 2, 2
    mesh = shard_lib.serve_mesh((dp, tp))
    rng = np.random.default_rng(5)
    q, kp, vp, tables, lengths, shard_nb = _sharded_gqa_case(
        rng, dp, [[1, 9, 0, 13], [4, 0, 16, 3]])

    def body(q, kp, vp, tables, lengths):
        return kops.paged_decode_attn(q, kp, vp, tables, lengths)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", "model"), P("data", None, "model"),
                  P("data", None, "model"), P("data"), P("data")),
        out_specs=P("data", "model"), check_vma=False))
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths))
    base = np.asarray(fn(*args))
    # Oracle: the same case through the gather reference with GLOBAL ids.
    B = tables.shape[0]
    g_tables = tables + (np.arange(B)[:, None] // (B // dp)) * shard_nb
    want = kref.paged_gqa_decode_attn_ref(
        q, kp, vp, jnp.asarray(g_tables), jnp.asarray(lengths))
    live = lengths > 0
    np.testing.assert_allclose(base[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    # Poison every block no shard's page copies ever name.
    chunk = gqa_chunk_blocks(tables.shape[1], BS, kp.shape[2] // tp,
                             kp.shape[3], kp.dtype.itemsize)
    live_ids = set()
    for sh in range(dp):
        rows = slice(sh * (B // dp), (sh + 1) * (B // dp))
        for chunks in gqa_chunk_fetch_ids(tables[rows], lengths[rows], BS,
                                          chunk):
            live_ids |= {i + sh * shard_nb for c in chunks for i in c}
    dead = np.array([i for i in range(kp.shape[0]) if i not in live_ids])
    assert dead.size > 0
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    kp2[dead] = np.nan
    vp2[dead] = np.nan
    poisoned = np.asarray(fn(q, jnp.asarray(kp2), jnp.asarray(vp2),
                             jnp.asarray(tables), jnp.asarray(lengths)))
    assert np.all(np.isfinite(poisoned))
    np.testing.assert_array_equal(base, poisoned)


def test_sharded_glu_mlp_dead_stripes_skip_both_fetches(cpu_mesh):
    """shard_map'd fused GLU MLP, w_gate/w_in column- and w_out
    row-sharded over 'model' with a psum closing the partial sums: a
    dead gate stripe ON EACH SHARD must skip that shard's w_in AND
    w_out stripe fetches (two-sided NaN poison, bit-identical psum)."""
    tp = 2
    mesh = shard_lib.serve_mesh((1, tp))
    M, K, F, N, bm, bf = 32, 64, 512, 64, 16, 128
    x = jax.random.normal(jax.random.PRNGKey(6), (M, K))
    w_gate = jax.random.normal(jax.random.PRNGKey(7), (K, F)) / np.sqrt(K)
    w_in = jax.random.normal(jax.random.PRNGKey(8), (K, F)) / np.sqrt(K)
    w_out = jax.random.normal(jax.random.PRNGKey(9), (F, N)) / np.sqrt(F)
    # Kill local f-stripe 1 on BOTH shards: global cols [128:256) land on
    # shard 0, [384:512) on shard 1 (contiguous column chunking).
    dead_cols = np.r_[128:256, 384:512]
    w_gate = w_gate.at[:, dead_cols].multiply(1e-4)
    tau = 0.05

    def body(x, wg, wi, wo):
        y, bmp = kops.sparce_glu_mlp_fused(
            x, wg, wi, wo, block_m=bm, block_f=bf, act="silu", tau=tau)
        return jax.lax.psum(y, "model"), bmp.bits

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, "model"), P(None, "model"),
                  P("model", None)),
        out_specs=(P(), P(None, "model")), check_vma=False))
    y0, bits0 = fn(x, w_gate, w_in, w_out)
    bits0 = np.asarray(bits0)
    # Local stripe 1 of each shard (global stripe 1 and 3) is dead.
    assert (bits0[:, 1] == 1).all() and (bits0[:, 3] == 1).all()
    w_in_p = w_in.at[:, dead_cols].set(jnp.nan)
    w_out_p = w_out.at[dead_cols, :].set(jnp.nan)
    y1, bits1 = fn(x, w_gate, w_in_p, w_out_p)
    np.testing.assert_array_equal(bits0, np.asarray(bits1))
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    assert not np.any(np.isnan(np.asarray(y1)))


# ------------------------------------------------------------ guard rails
def test_mesh_rejects_nondivisible_tiles(cpu_mesh):
    """The engine refuses mesh shapes that would silently break exact
    skip-stat parity (block_k not dividing the per-shard d_ff)."""
    cfg, params, sp = _relu_setup()
    bad = SparsityConfig(enabled=True, mode="reference",
                         block_m=1, block_k=128)  # d_ff/tp = 64 < 128
    with pytest.raises(ValueError, match="block_k"):
        from repro.runtime.server import Server
        Server(cfg, params, _serve_cfg(bad, mesh=(1, 2)))
