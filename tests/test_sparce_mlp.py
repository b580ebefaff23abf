"""Fused SparCE MLP megakernel: parity vs oracles, skip accounting,
planner v2, and the compacted nnz==0 regression. All interpret mode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cost_model, sasa, sparse_ops, sprf
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels import sparce_gemm as sgk
from repro.models import layers

F32_TOL = dict(rtol=1e-4, atol=1e-4)
BF16_TOL = dict(rtol=3e-2, atol=3e-2)


def _mlp_oracle(x, w_in, w_out, block, act="relu"):
    """Composed reference: dense up-proj, relu bitmap, masked down-proj."""
    h = jnp.dot(
        x.astype(jnp.float32), w_in.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    a, bits = kref.relu_bitmap_ref(h, block)
    if act == "relu2":
        a = a * a
    y = kref.sparce_gemm_ref(
        a.astype(x.dtype), w_out, bits_lhs=bits,
        block_m=block[0], block_k=block[1], block_n=w_out.shape[1],
        out_dtype=x.dtype,
    )
    return y, bits


def _sparse_rows_input(key, m, k, sparsity, bm, dtype=jnp.float32):
    """Nonnegative x with whole zero row-tiles => the activated
    intermediate realizes ``sparsity`` at (bm, *) block granularity."""
    return jnp.abs(
        sprf.random_sparse(key, (m, k), sparsity, dtype=dtype,
                           cluster=(bm, k))
    )


# ------------------------------------------------------------ kernel parity
@pytest.mark.parametrize("sparsity", [0.0, 0.5, 1.0])
@pytest.mark.parametrize("act", ["relu", "relu2"])
def test_fused_mlp_matches_oracle(sparsity, act):
    M, K, F, N, bm, bf = 64, 128, 256, 128, 16, 128
    x = _sparse_rows_input(jax.random.PRNGKey(0), M, K, sparsity, bm)
    w_in = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (K, F))) * 0.1
    w_out = jax.random.normal(jax.random.PRNGKey(2), (F, N)) * 0.1
    y, bmp = kops.sparce_mlp_fused(
        x, w_in, w_out, block_m=bm, block_f=bf, act=act)
    want, bits = _mlp_oracle(x, w_in, w_out, (bm, bf), act=act)
    np.testing.assert_array_equal(np.asarray(bmp.bits), np.asarray(bits))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), **F32_TOL)


def test_fused_mlp_bf16_tolerance():
    M, K, F, N, bm, bf = 32, 128, 256, 128, 16, 128
    x = _sparse_rows_input(
        jax.random.PRNGKey(3), M, K, 0.5, bm, dtype=jnp.bfloat16)
    w_in = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (K, F))).astype(
        jnp.bfloat16) * 0.1
    w_out = (jax.random.normal(jax.random.PRNGKey(5), (F, N)) * 0.1).astype(
        jnp.bfloat16)
    y, bmp = kops.sparce_mlp_fused(
        x, w_in, w_out, block_m=bm, block_f=bf)
    want, bits = _mlp_oracle(x, w_in, w_out, (bm, bf))
    np.testing.assert_array_equal(np.asarray(bmp.bits), np.asarray(bits))
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(want, np.float32), **BF16_TOL)


def test_fused_mlp_odd_patterns():
    """All-zero row-tile, fully dense, and a single nonzero element."""
    M, K, F, N, bm, bf = 48, 64, 256, 64, 16, 128
    w_in = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (K, F))) * 0.1
    w_out = jax.random.normal(jax.random.PRNGKey(7), (F, N)) * 0.1

    # one dead row-tile in the middle, rest dense
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (M, K)))
    x = x.at[16:32].set(0.0)
    y, bmp = kops.sparce_mlp_fused(
        x, w_in, w_out, block_m=bm, block_f=bf)
    want, bits = _mlp_oracle(x, w_in, w_out, (bm, bf))
    np.testing.assert_array_equal(np.asarray(bmp.bits), np.asarray(bits))
    assert float(jnp.abs(y[16:32]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), **F32_TOL)

    # fully dense: no bit set, still numerically correct
    xd = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (M, K))) + 0.1
    y, bmp = kops.sparce_mlp_fused(
        xd, w_in, w_out, block_m=bm, block_f=bf)
    assert int(np.asarray(bmp.bits).sum()) == 0
    want, _ = _mlp_oracle(xd, w_in, w_out, (bm, bf))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), **F32_TOL)

    # single nonzero element: exactly one live row-tile of bits
    xs = jnp.zeros((M, K)).at[3, 5].set(2.0)
    y, bmp = kops.sparce_mlp_fused(
        xs, w_in, w_out, block_m=bm, block_f=bf)
    bits = np.asarray(bmp.bits)
    assert (bits[1:] == 1).all() and (bits[0] == 0).any()
    want, _ = _mlp_oracle(xs, w_in, w_out, (bm, bf))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), **F32_TOL)


def test_fused_mlp_ragged_dims_padded():
    """The ops wrapper pads M and F; padding must not leak into y/bits."""
    M, K, F, N, bm, bf = 40, 64, 200, 64, 16, 128
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(10), (M, K)))
    w_in = jnp.abs(jax.random.normal(jax.random.PRNGKey(11), (K, F))) * 0.1
    w_out = jax.random.normal(jax.random.PRNGKey(12), (F, N)) * 0.1
    y, bmp = kops.sparce_mlp_fused(
        x, w_in, w_out, block_m=bm, block_f=bf)
    assert y.shape == (M, N)
    want, bits = _mlp_oracle(x, w_in, w_out, (bm, bf))
    np.testing.assert_array_equal(np.asarray(bmp.bits), np.asarray(bits))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), **F32_TOL)


def test_fused_skips_are_real():
    """Dishonest-by-construction check: poison w_out stripes whose tiles
    are all zero -- the fused kernel must never have fetched them."""
    M, K, F, N, bm, bf = 32, 64, 256, 64, 16, 128
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(13), (M, K)))
    w_in = jnp.abs(jax.random.normal(jax.random.PRNGKey(14), (K, F))) * 0.1
    # kill f-stripe 1 for every row: negative pre-activation
    w_in = w_in.at[:, 128:256].set(-1.0)
    w_out = jax.random.normal(jax.random.PRNGKey(15), (F, N)) * 0.1
    y0, bmp = kops.sparce_mlp_fused(
        x, w_in, w_out, block_m=bm, block_f=bf)
    assert (np.asarray(bmp.bits)[:, 1] == 1).all()
    w_poison = w_out.at[128:256].set(jnp.nan)  # stripe must not be read
    y1, _ = kops.sparce_mlp_fused(
        x, w_in, w_poison, block_m=bm, block_f=bf)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    assert not np.any(np.isnan(np.asarray(y1)))


# ------------------------------------------------- skip-count property test
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("act", ["relu", "relu2"])
def test_fused_aux_skip_counts_equal_reference(seed, act):
    """mlp_fwd's [skipped, total] accounting must be identical between
    mode='fused' and mode='reference' on the same inputs."""
    d, ff, bm, bk = 64, 256, 8, 128
    key = jax.random.PRNGKey(seed)
    params = {
        "w_in": jax.random.normal(key, (d, ff)) * 0.3 - 0.1,
        "w_out": jax.random.normal(jax.random.PRNGKey(seed + 10), (ff, d)),
    }
    x = jax.random.normal(jax.random.PRNGKey(seed + 20), (3, 8, d))
    x = x.at[0].set(0.0)  # dead serving slot
    base = sparse_ops.SparsityConfig(enabled=True, block_m=bm, block_k=bk)
    y_ref, s_ref = layers.mlp_fwd(
        params, x, act, dataclasses.replace(base, mode="reference"))
    y_fus, s_fus = layers.mlp_fwd(
        params, x, act, dataclasses.replace(base, mode="fused"))
    np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_fus))
    assert float(np.asarray(s_ref)[1]) > 0
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_fus),
                               **F32_TOL)


def test_fused_mlp_grads_match_dense():
    d, ff = 64, 128
    params = {
        "w_in": jax.random.normal(jax.random.PRNGKey(0), (d, ff)) * 0.2,
        "w_out": jax.random.normal(jax.random.PRNGKey(1), (ff, d)) * 0.2,
    }
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, d))
    cfg = sparse_ops.SparsityConfig(
        enabled=True, mode="fused", block_m=8, block_k=128)

    def loss_fused(p):
        y, _ = layers.mlp_fwd(p, x, "relu", cfg)
        return jnp.sum(y * y)

    def loss_dense(p):
        a = jnp.maximum(x.reshape(-1, d) @ p["w_in"], 0)
        return jnp.sum((a @ p["w_out"]) ** 2)

    g1 = jax.grad(loss_fused)(params)
    g2 = jax.grad(loss_dense)(params)
    for k in g1:
        np.testing.assert_allclose(
            np.asarray(g1[k]), np.asarray(g2[k]), rtol=1e-3, atol=1e-3)


# --------------------------------------------- compacted nnz==0 regression
def test_compacted_all_skip_bits_yield_exact_zero():
    """nnz == 0 row-tiles: the clamped idx still points at tile 0, so the
    first-step predicate must hold the MXU off -- dishonest all-ones bits
    over a fully NONZERO x must produce exactly zero output."""
    M, K, N, bm, bk, bn = 128, 256, 128, 64, 128, 128
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(M, K)), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N))
    bits = jnp.ones((M // bm, K // bk), jnp.int32)
    got = sgk.sparce_gemm_compacted(
        x, w, bits, block_m=bm, block_k=bk, block_n=bn, interpret=True)
    assert float(jnp.abs(got).max()) == 0.0


def test_compacted_mixed_nnz_zero_rows():
    """Rows alternate nnz==0 / dense; garbage (NaN) lives in the skipped
    tiles to prove the guarded first step never touches tile 0."""
    M, K, N, bm, bk, bn = 192, 256, 128, 64, 128, 128
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (M, K))) + 0.1
    bits = jnp.zeros((M // bm, K // bk), jnp.int32)
    bits = bits.at[1, :].set(1)  # middle row-tile: nnz == 0
    x = x.at[64:128, :].set(jnp.nan)  # garbage where the bits skip
    w = jax.random.normal(jax.random.PRNGKey(3), (K, N))
    got = sgk.sparce_gemm_compacted(
        x, w, bits, block_m=bm, block_k=bk, block_n=bn, interpret=True)
    assert float(jnp.abs(got[64:128]).max()) == 0.0
    assert not np.any(np.isnan(np.asarray(got)))
    want = kref.sparce_gemm_ref(
        jnp.nan_to_num(x), w, bits_lhs=bits, block_m=bm, block_k=bk,
        block_n=bn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **F32_TOL)


# ------------------------------------------------------------- planner v2
def test_plan_mlp_prefers_fused_and_models_bytes():
    plan = sasa.plan_mlp(64, 256, 512, 256, measured_block_sparsity=0.5)
    assert plan.variant == "fused"
    by = plan.modeled()
    assert by["fused"] < by["two_kernel"]
    assert 1.0 - by["fused"] / by["two_kernel"] >= 0.30


def test_plan_mlp_falls_back_when_vmem_exceeded():
    # K and N huge: one row-tile + w_out stripe cannot be VMEM-resident.
    plan = sasa.plan_mlp(64, 32768, 65536, 32768,
                         measured_block_sparsity=0.6)
    assert plan.variant == "two_kernel"


def test_plan_mlp_cached_identity():
    sasa.plan_cache_clear()
    a = sasa.plan_mlp_cached(64, 128, 256, 128, measured_block_sparsity=0.41)
    b = sasa.plan_mlp_cached(64, 128, 256, 128, measured_block_sparsity=0.41)
    assert a is b
    st = sasa.plan_cache_stats()
    assert st["hits"] >= 1 and st["misses"] >= 1


def test_mlp_hbm_bytes_fused_saves_30pct_at_half_sparsity():
    by = cost_model.mlp_hbm_bytes(
        64, 576, 1536, 576, block_sparsity=0.5, block_m=64)
    assert by["fused_saved_frac_vs_two_kernel"] >= 0.30
    # more sparsity, fewer fused bytes; two-kernel unchanged
    by9 = cost_model.mlp_hbm_bytes(
        64, 576, 1536, 576, block_sparsity=0.9, block_m=64)
    assert by9["fused"] < by["fused"]
    assert by9["two_kernel"] == by["two_kernel"]


def test_sparsity_ema_bucketing():
    ema = sasa.SparsityEMA(alpha=0.5)
    assert ema.bucketed() == 0.0
    for _ in range(8):
        ema.update(9.0, 10.0)
    assert abs(ema.value - 0.9) < 0.05
    assert ema.bucketed() in (0.875, 1.0)
    ema.update(0.0, 0.0)  # empty tick: no update
    assert ema.updates == 8


# ------------------------------------------------------- serving end-to-end
def test_server_fused_mode_matches_reference_engine():
    """Greedy decode through the continuous batcher must be identical
    between mode='fused' (megakernel + EMA autotune/replan) and
    mode='reference', including the realized skip fractions."""
    from repro.configs import get_config
    from repro.models import model as model_lib
    from repro.runtime.server import Request, ServeConfig, Server

    cfg = dataclasses.replace(
        get_config("smollm-135m").reduced(), mlp_act="relu")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))

    def serve(mode, autotune=False):
        srv = Server(cfg, params, ServeConfig(
            batch_slots=2, max_len=32,
            sparsity=sparse_ops.SparsityConfig(
                enabled=True, mode=mode, block_m=1, block_k=128,
                autotune=autotune)))
        rng = np.random.default_rng(1)
        reqs = [
            Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 5),
                    max_new=4)
            for i in range(3)
        ]
        done = srv.generate(reqs)
        return {r.uid: r.out.tolist() for r in done}, srv.metrics

    out_ref, m_ref = serve("reference")
    out_fus, m_fus = serve("fused", autotune=True)
    assert out_ref == out_fus
    assert m_ref["mlp_skip_fraction"] == pytest.approx(
        m_fus["mlp_skip_fraction"])
    assert m_fus["replans"] >= 1  # EMA crossed a bucket and replanned
    assert m_fus["modeled_hbm_bytes_saved"] > 0


def test_measuring_autotuner_returns_timed_plan():
    plan, timings = sasa.autotune_mlp_plan(
        32, 64, 256, 64, measured_block_sparsity=0.5)
    assert plan.variant in ("fused", "two_kernel")
    assert set(timings) == {"fused", "two_kernel"}
    assert all(t > 0 for t in timings.values())
    again, _ = sasa.autotune_mlp_plan(
        32, 64, 256, 64, measured_block_sparsity=0.5)
    assert again is plan  # memoised process-wide
