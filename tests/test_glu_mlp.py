"""Gated-GLU fetch-skipping megakernel: parity vs oracles, two-sided
skip proof, activation-precision convention, planner, serving e2e on the
DEFAULT (silu) config, and the spurious-replan regression. All interpret
mode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cost_model, sasa, sparse_ops, sprf
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models import layers

F32_TOL = dict(rtol=1e-4, atol=1e-4)
BF16_TOL = dict(rtol=3e-2, atol=3e-2)


def _weights(key, k, f, n, dtype=jnp.float32):
    kg, ki, ko = jax.random.split(key, 3)
    return (
        (jax.random.normal(kg, (k, f)) * 0.1).astype(dtype),
        (jax.random.normal(ki, (k, f)) * 0.1).astype(dtype),
        (jax.random.normal(ko, (f, n)) * 0.1).astype(dtype),
    )


# ------------------------------------------------------------ kernel parity
@pytest.mark.parametrize("act", ["silu", "gelu"])
@pytest.mark.parametrize("tau", [0.0, 0.05])
def test_glu_fused_matches_oracle(act, tau):
    M, K, F, N, bm, bf = 64, 128, 256, 128, 16, 128
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K))
    w_gate, w_in, w_out = _weights(jax.random.PRNGKey(1), K, F, N)
    y, bmp = kops.sparce_glu_mlp_fused(
        x, w_gate, w_in, w_out, block_m=bm, block_f=bf, act=act, tau=tau)
    want, bits = kref.glu_mlp_ref(
        x, w_gate, w_in, w_out, act=act, tau=tau, block_m=bm, block_f=bf)
    np.testing.assert_array_equal(np.asarray(bmp.bits), np.asarray(bits))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), **F32_TOL)


def test_glu_tau0_exact_vs_dense():
    """tau=0 is the exact all-zero test: zero x row-tiles produce dead
    gate tiles, and dropping exactly-zero contributions is lossless --
    the fused result must match the DENSE (undropped) GLU."""
    M, K, F, N, bm, bf = 48, 64, 256, 64, 16, 128
    x = jax.random.normal(jax.random.PRNGKey(2), (M, K))
    x = x.at[16:32].set(0.0)  # dead serving slot rows
    w_gate, w_in, w_out = _weights(jax.random.PRNGKey(3), K, F, N)
    y, bmp = kops.sparce_glu_mlp_fused(
        x, w_gate, w_in, w_out, block_m=bm, block_f=bf, act="silu",
        tau=0.0)
    bits = np.asarray(bmp.bits)
    assert (bits[1] == 1).all()  # the zero row-tile is dead across F
    assert (bits[0] == 0).all() and (bits[2] == 0).all()
    ga = kref.glu_act_ref(jnp.dot(x, w_gate), "silu")
    dense = jnp.dot(ga * jnp.dot(x, w_in), w_out)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), **F32_TOL)
    assert float(jnp.abs(y[16:32]).max()) == 0.0


def test_glu_relu_gate_degenerates_to_exact_zero_test():
    """relu-gated GLU at tau=0: dead bits are exactly the all-zero tiles
    of relu(g) -- relu_bitmap_ref semantics on the gate."""
    M, K, F, N, bm, bf = 32, 64, 256, 64, 16, 128
    x = jax.random.normal(jax.random.PRNGKey(4), (M, K))
    w_gate, w_in, w_out = _weights(jax.random.PRNGKey(5), K, F, N)
    # Drive one gate f-stripe all-negative so relu kills it exactly.
    w_gate = jnp.abs(w_gate).at[:, 128:].multiply(-1.0)
    x = jnp.abs(x)
    y, bmp = kops.sparce_glu_mlp_fused(
        x, w_gate, w_in, w_out, block_m=bm, block_f=bf, act="relu",
        tau=0.0)
    _, want_bits = kref.relu_bitmap_ref(jnp.dot(x, w_gate), (bm, bf))
    np.testing.assert_array_equal(np.asarray(bmp.bits),
                                  np.asarray(want_bits))
    assert (np.asarray(bmp.bits)[:, 1] == 1).all()
    want, _ = kref.glu_mlp_ref(
        x, w_gate, w_in, w_out, act="relu", tau=0.0, block_m=bm,
        block_f=bf)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), **F32_TOL)


def test_glu_dead_stripes_skip_both_fetches():
    """Two-sided NaN-poison proof: a dead gate stripe's w_in AND w_out
    stripes must never be DMA'd -- poisoning both leaves the output
    bit-identical and NaN-free."""
    M, K, F, N, bm, bf = 32, 64, 256, 64, 16, 128
    x = jax.random.normal(jax.random.PRNGKey(6), (M, K))
    w_gate, w_in, w_out = _weights(jax.random.PRNGKey(7), K, F, N)
    # Tiny gate weights on f-stripe 1: |silu(g)| <= |g|/2 stays under
    # tau, exercising the value-approximate (tau > 0) path.
    w_gate = w_gate.at[:, 128:].multiply(1e-4)
    tau = 0.05
    y0, bmp = kops.sparce_glu_mlp_fused(
        x, w_gate, w_in, w_out, block_m=bm, block_f=bf, act="silu",
        tau=tau)
    assert (np.asarray(bmp.bits)[:, 1] == 1).all()
    w_in_p = w_in.at[:, 128:].set(jnp.nan)
    w_out_p = w_out.at[128:, :].set(jnp.nan)
    y1, bmp1 = kops.sparce_glu_mlp_fused(
        x, w_gate, w_in_p, w_out_p, block_m=bm, block_f=bf, act="silu",
        tau=tau)
    np.testing.assert_array_equal(np.asarray(bmp.bits),
                                  np.asarray(bmp1.bits))
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    assert not np.any(np.isnan(np.asarray(y1)))


def test_glu_fused_bf16_bits_exact_values_close():
    """bf16: bits must be EXACTLY the oracle's (both sides round g and
    act(g) through the input dtype before thresholding); values within
    bf16 tolerance."""
    M, K, F, N, bm, bf = 32, 128, 256, 128, 16, 128
    x = jax.random.normal(
        jax.random.PRNGKey(8), (M, K)).astype(jnp.bfloat16)
    x = x.at[:16].set(jnp.bfloat16(0))
    w_gate, w_in, w_out = _weights(
        jax.random.PRNGKey(9), K, F, N, dtype=jnp.bfloat16)
    y, bmp = kops.sparce_glu_mlp_fused(
        x, w_gate, w_in, w_out, block_m=bm, block_f=bf, act="silu",
        tau=0.0)
    want, bits = kref.glu_mlp_ref(
        x, w_gate, w_in, w_out, act="silu", tau=0.0, block_m=bm,
        block_f=bf)
    np.testing.assert_array_equal(np.asarray(bmp.bits), np.asarray(bits))
    assert (np.asarray(bmp.bits)[0] == 1).all()
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(want, np.float32),
        **BF16_TOL)


def test_glu_fused_ragged_dims_padded():
    """The ops wrapper pads M and F; padding stripes (zero gate weights)
    must be born dead and never leak into y or the bitmap."""
    M, K, F, N, bm, bf = 40, 64, 200, 64, 16, 128
    x = jax.random.normal(jax.random.PRNGKey(10), (M, K))
    w_gate, w_in, w_out = _weights(jax.random.PRNGKey(11), K, F, N)
    y, bmp = kops.sparce_glu_mlp_fused(
        x, w_gate, w_in, w_out, block_m=bm, block_f=bf, act="gelu",
        tau=0.02)
    assert y.shape == (M, N)
    want, bits = kref.glu_mlp_ref(
        x, w_gate, w_in, w_out, act="gelu", tau=0.02, block_m=bm,
        block_f=bf)
    np.testing.assert_array_equal(np.asarray(bmp.bits), np.asarray(bits))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), **F32_TOL)


# --------------------------------------------- activation precision parity
def test_activate_uses_f32_upcast_convention_bf16():
    """layers._activate must compute smooth activations in f32 and cast
    back (the moe.py shared-expert convention), not natively in bf16."""
    h = (jax.random.normal(jax.random.PRNGKey(12), (64, 256)) * 3
         ).astype(jnp.bfloat16)
    cfg = sparse_ops.SparsityConfig()
    for act, fn in (("silu", jax.nn.silu), ("gelu", jax.nn.gelu)):
        got, bmp = layers._activate(h, act, cfg)
        assert bmp is None and got.dtype == jnp.bfloat16
        want = fn(h.astype(jnp.float32)).astype(jnp.bfloat16)
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32))


# ------------------------------------------------- layer-level skip parity
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("act", ["silu", "gelu"])
def test_glu_fwd_skip_counts_equal_reference(seed, act):
    """mlp_fwd's [skipped, total] accounting must be identical between
    mode='fused' and mode='reference' on the same GLU inputs."""
    d, ff, bm, bk = 64, 256, 8, 128
    params = layers.mlp_init(jax.random.PRNGKey(seed), d, ff, act,
                             jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 20), (3, 8, d))
    x = x.at[0].set(0.0)  # dead serving slot
    base = sparse_ops.SparsityConfig(
        enabled=True, block_m=bm, block_k=bk, gate_threshold=0.0)
    y_ref, s_ref = layers.mlp_fwd(
        params, x, act, dataclasses.replace(base, mode="reference"))
    y_fus, s_fus = layers.mlp_fwd(
        params, x, act, dataclasses.replace(base, mode="fused"))
    np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_fus))
    stats = np.asarray(s_ref)
    assert stats[1] > 0 and stats[0] > 0  # dead slot realizes skips
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_fus),
                               **F32_TOL)


def test_glu_fwd_grads_match_dense():
    d, ff = 64, 128
    params = layers.mlp_init(jax.random.PRNGKey(0), d, ff, "silu",
                             jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, d))
    cfg = sparse_ops.SparsityConfig(
        enabled=True, mode="fused", block_m=8, block_k=128,
        gate_threshold=0.0)

    def loss_fused(p):
        y, _ = layers.mlp_fwd(p, x, "silu", cfg)
        return jnp.sum(y * y)

    def loss_dense(p):
        x2 = x.reshape(-1, d)
        ga = kref.glu_act_ref(x2 @ p["w_gate"], "silu")
        return jnp.sum((ga * (x2 @ p["w_in"]) @ p["w_out"]) ** 2)

    g1 = jax.grad(loss_fused)(params)
    g2 = jax.grad(loss_dense)(params)
    for k in g1:
        np.testing.assert_allclose(
            np.asarray(g1[k]), np.asarray(g2[k]), rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------- planner v2
def test_plan_glu_mlp_prefers_fused_at_half_sparsity():
    plan = sasa.plan_glu_mlp(
        128, 256, 512, 256, measured_block_sparsity=0.5, block_m=32,
        block_f=128, block_n=128)
    assert plan.variant == "fused"
    by = plan.modeled()
    assert 1.0 - by["fused"] / by["unfused"] >= 0.30


def test_plan_glu_mlp_honest_fallbacks():
    # Large M at low sparsity: the per-row-tile weight re-fetch makes
    # fused a net loss and sub-threshold sparsity is not worth gating.
    plan = sasa.plan_glu_mlp(
        1024, 256, 512, 256, measured_block_sparsity=0.0, block_m=16,
        block_f=128, block_n=128)
    assert plan.variant == "dense"
    plan = sasa.plan_glu_mlp(
        1024, 256, 512, 256, measured_block_sparsity=0.25, block_m=16,
        block_f=128, block_n=128)
    assert plan.variant == "unfused"
    # VMEM blown: double-buffered stripes cannot fit.
    plan = sasa.plan_glu_mlp(
        64, 32768, 65536, 32768, measured_block_sparsity=0.9)
    assert plan.variant != "fused"


def test_plan_glu_mlp_cached_identity():
    sasa.plan_cache_clear()
    a = sasa.plan_glu_mlp_cached(64, 128, 256, 128,
                                 measured_block_sparsity=0.5)
    b = sasa.plan_glu_mlp_cached(64, 128, 256, 128,
                                 measured_block_sparsity=0.5)
    assert a is b


def test_glu_hbm_bytes_fused_saves_30pct_at_half_sparsity():
    by = cost_model.glu_mlp_hbm_bytes(
        128, 256, 512, 256, block_sparsity=0.5, block_m=32)
    assert by["fused_saved_frac_vs_unfused"] >= 0.30
    by9 = cost_model.glu_mlp_hbm_bytes(
        128, 256, 512, 256, block_sparsity=0.9, block_m=32)
    assert by9["fused"] < by["fused"]
    assert by9["unfused"] == by["unfused"]


# ----------------------------------------------- replan regression (bugfix)
def test_sparsity_config_snaps_expected_sparsity_to_ema_grid():
    cfg = sparse_ops.SparsityConfig(expected_sparsity=0.3)
    assert cfg.expected_sparsity == 0.25  # round(0.3 * 8) / 8
    assert sparse_ops.SparsityConfig(
        expected_sparsity=0.5).expected_sparsity == 0.5
    with pytest.raises(ValueError):
        sparse_ops.SparsityConfig(gate_threshold=-0.1)


def test_server_no_spurious_replan_on_stable_workload():
    """All slots stay live => measured sparsity sits in bucket 0.0; an
    off-grid expected_sparsity (0.03) must snap at config time instead
    of forcing a needless retrace on the first EMA comparison."""
    from repro.configs import get_config
    from repro.models import model as model_lib
    from repro.runtime.server import Request, ServeConfig, Server

    cfg = dataclasses.replace(
        get_config("smollm-135m").reduced(), mlp_act="relu")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    sp = sparse_ops.SparsityConfig(
        enabled=True, mode="reference", block_m=1, block_k=128,
        autotune=True, expected_sparsity=0.03)
    assert sp.expected_sparsity == 0.0
    srv = Server(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, sparsity=sp))
    rng = np.random.default_rng(3)
    reqs = [
        Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 5),
                max_new=6)
        for i in range(2)  # exactly fills the slots: no dead decode rows
    ]
    srv.generate(reqs)
    assert srv.metrics["replans"] == 0


# ------------------------------------------------------- serving end-to-end
def test_server_glu_fused_mode_matches_reference_engine():
    """DEFAULT config (silu GLU MLP), tau=0: greedy decode through the
    continuous batcher must be token-identical between mode='fused' and
    mode='reference' with identical realized skip stats, and dead slots
    must produce REAL skips (their embeddings are zeroed, attention over
    null blocks returns 0, silu(0) == 0 exactly)."""
    from repro.configs import get_config
    from repro.models import model as model_lib
    from repro.runtime.server import Request, ServeConfig, Server

    cfg = get_config("smollm-135m").reduced()
    assert cfg.mlp_act == "silu"  # the default family this PR closes
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))

    def serve(mode):
        # expected_sparsity=0.5 (on the EMA grid): without a sparsity
        # hint the honest GLU planner resolves to the dense variant at
        # these decode shapes and reports no realized skips at all.
        srv = Server(cfg, params, ServeConfig(
            batch_slots=4, max_len=32,
            sparsity=sparse_ops.SparsityConfig(
                enabled=True, mode=mode, block_m=1, block_k=128,
                gate_threshold=0.0, expected_sparsity=0.5)))
        rng = np.random.default_rng(1)
        reqs = [
            Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 5),
                    max_new=4)
            for i in range(2)  # 2 of 4 slots live: dead-slot sparsity
        ]
        done = srv.generate(reqs)
        return {r.uid: r.out.tolist() for r in done}, srv.metrics

    out_ref, m_ref = serve("reference")
    out_fus, m_fus = serve("fused")
    assert out_ref == out_fus
    assert m_ref["skipped_tile_dots"] == pytest.approx(
        m_fus["skipped_tile_dots"])
    assert m_ref["total_tile_dots"] == pytest.approx(
        m_fus["total_tile_dots"])
    assert m_fus["skipped_tile_dots"] > 0  # dead slots really skip
    # The GLU cost model is consulted (nonzero either way); the SIGN is
    # the model being honest -- at these tiny decode shapes the per-row
    # weight re-fetch makes fusion a net loss, which is exactly why the
    # planner served the unfused variant above.
    assert m_fus["modeled_hbm_bytes_saved"] != 0.0
