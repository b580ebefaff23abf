"""Per-kernel validation: shape/dtype/sparsity sweeps vs ref.py oracles,
all in interpret mode (CPU container; TPU is the deployment target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sprf
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels import relu_bitmap as rbk
from repro.kernels import sparce_gemm as sgk
from repro.core.sasa import SkipPlan

F32_TOL = dict(rtol=1e-4, atol=1e-4)
BF16_TOL = dict(rtol=3e-2, atol=3e-2)


def _mats(key, M, K, N, sparsity, dtype, cluster):
    kx, kw = jax.random.split(key)
    x = sprf.random_sparse(kx, (M, K), sparsity, dtype=dtype, cluster=cluster)
    w = jax.random.normal(kw, (K, N), jnp.float32).astype(dtype)
    return x, w


@pytest.mark.parametrize("M,K,N,bm,bk,bn", [
    (128, 256, 128, 64, 128, 128),
    (256, 512, 384, 64, 128, 128),
    (64, 128, 256, 8, 128, 128),
    (512, 256, 128, 128, 128, 128),
])
@pytest.mark.parametrize("sparsity", [0.0, 0.3, 0.7, 0.95])
def test_gated_gemm_matches_oracle(M, K, N, bm, bk, bn, sparsity):
    x, w = _mats(jax.random.PRNGKey(0), M, K, N, sparsity, jnp.float32,
                 cluster=(bm, bk))
    bits = sprf.compute_bitmap(x, (bm, bk)).bits
    got = sgk.sparce_gemm_gated(
        x, w, bits, block_m=bm, block_k=bk, block_n=bn, interpret=True)
    want = kref.sparce_gemm_ref(
        x, w, bits_lhs=bits, block_m=bm, block_k=bk, block_n=bn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **F32_TOL)


@pytest.mark.parametrize("sparsity", [0.0, 0.5, 0.9])
def test_compacted_gemm_matches_oracle(sparsity):
    M, K, N, bm, bk, bn = 256, 512, 256, 64, 128, 128
    x, w = _mats(jax.random.PRNGKey(1), M, K, N, sparsity, jnp.float32,
                 cluster=(bm, bk))
    bits = sprf.compute_bitmap(x, (bm, bk)).bits
    got = sgk.sparce_gemm_compacted(
        x, w, bits, block_m=bm, block_k=bk, block_n=bn, interpret=True)
    want = kref.sparce_gemm_ref(
        x, w, bits_lhs=bits, block_m=bm, block_k=bk, block_n=bn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **F32_TOL)


def test_compacted_all_zero_row():
    """A row-tile whose every k-tile is zero must produce exact zeros."""
    M, K, N, bm, bk, bn = 128, 256, 128, 64, 128, 128
    x = jnp.zeros((M, K)).at[64:, :].set(1.0)  # first row-tile all zero
    w = jax.random.normal(jax.random.PRNGKey(2), (K, N))
    bits = sprf.compute_bitmap(x, (bm, bk)).bits
    got = sgk.sparce_gemm_compacted(
        x, w, bits, block_m=bm, block_k=bk, block_n=bn, interpret=True)
    assert float(jnp.abs(got[:64]).max()) == 0.0
    want = kref.sparce_gemm_ref(
        x, w, bits_lhs=bits, block_m=bm, block_k=bk, block_n=bn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **F32_TOL)


@pytest.mark.parametrize("gate", ["lhs", "rhs"])
def test_gated_gemm_rhs_and_dishonest_bits(gate):
    """Dishonest bits (set on nonzero tiles) prove skipping really happens:
    the kernel must match the MASKED oracle, not the dense product."""
    M, K, N, bm, bk, bn = 128, 256, 256, 64, 128, 128
    x = jax.random.normal(jax.random.PRNGKey(3), (M, K))
    w = jax.random.normal(jax.random.PRNGKey(4), (K, N))
    if gate == "lhs":
        bits = jnp.zeros((M // bm, K // bk), jnp.int32).at[0, 1].set(1)
        got = sgk.sparce_gemm_gated(
            x, w, bits, gate=gate, block_m=bm, block_k=bk, block_n=bn,
            interpret=True)
        want = kref.sparce_gemm_ref(
            x, w, bits_lhs=bits, block_m=bm, block_k=bk, block_n=bn)
    else:
        bits = jnp.zeros((K // bk, N // bn), jnp.int32).at[1, 0].set(1)
        got = sgk.sparce_gemm_gated(
            x, w, bits, gate=gate, block_m=bm, block_k=bk, block_n=bn,
            interpret=True)
        want = kref.sparce_gemm_ref(
            x, w, bits_rhs=bits, block_m=bm, block_k=bk, block_n=bn)
    dense = jnp.dot(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **F32_TOL)
    assert float(jnp.abs(got - dense).max()) > 1e-3  # gating had an effect


def test_gated_both_operands():
    M, K, N, bm, bk, bn = 128, 256, 256, 64, 128, 128
    key = jax.random.PRNGKey(5)
    x = sprf.random_sparse(key, (M, K), 0.5, cluster=(bm, bk))
    w = sprf.random_sparse(jax.random.PRNGKey(6), (K, N), 0.5,
                           cluster=(bk, bn))
    lb = sprf.compute_bitmap(x, (bm, bk)).bits
    rb = sprf.compute_bitmap(w, (bk, bn)).bits
    got = sgk.sparce_gemm_gated_both(
        x, w, lb, rb, block_m=bm, block_k=bk, block_n=bn, interpret=True)
    want = kref.sparce_gemm_ref(
        x, w, bits_lhs=lb, bits_rhs=rb, block_m=bm, block_k=bk, block_n=bn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **F32_TOL)


@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, F32_TOL), (jnp.bfloat16, BF16_TOL),
])
def test_gemm_dtypes(dtype, tol):
    M, K, N, bm, bk, bn = 128, 256, 128, 64, 128, 128
    x, w = _mats(jax.random.PRNGKey(7), M, K, N, 0.5, dtype, cluster=(bm, bk))
    bits = sprf.compute_bitmap(x, (bm, bk)).bits
    got = sgk.sparce_gemm_gated(
        x, w, bits, block_m=bm, block_k=bk, block_n=bn, interpret=True)
    want = kref.sparce_gemm_ref(
        x, w, bits_lhs=bits, block_m=bm, block_k=bk, block_n=bn)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol)


def test_ops_wrapper_pads_ragged_dims():
    """ops.sparce_gemm handles dims not divisible by blocks."""
    M, K, N = 100, 300, 200
    plan = SkipPlan(gate="lhs", variant="gated",
                    block_m=64, block_k=128, block_n=128)
    x = sprf.random_sparse(jax.random.PRNGKey(8), (M, K), 0.6, cluster=(50, 100))
    w = jax.random.normal(jax.random.PRNGKey(9), (K, N))
    bmp = sprf.compute_bitmap(x, (64, 128))
    got = kops.sparce_gemm(x, w, plan, lhs_bitmap=bmp)
    want = jnp.dot(x, w)  # honest bitmap => exact dense semantics
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **F32_TOL)
    assert got.shape == (M, N)


@pytest.mark.parametrize("shape,block", [
    ((128, 256), (8, 128)), ((64, 512), (16, 128)), ((256, 128), (64, 128)),
])
def test_relu_bitmap_kernel(shape, block):
    x = jax.random.normal(jax.random.PRNGKey(10), shape)
    y, bits = rbk.relu_bitmap(x, block_r=block[0], block_c=block[1],
                              interpret=True)
    y2, bits2 = kref.relu_bitmap_ref(x, block)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(bits2))


def test_relu_bwd_bitmap_kernel():
    x = jax.random.normal(jax.random.PRNGKey(11), (128, 256))
    g = jax.random.normal(jax.random.PRNGKey(12), (128, 256))
    gx, bits = rbk.relu_bwd_bitmap(x, g, block_r=8, block_c=128,
                                   interpret=True)
    gx2, bits2 = kref.relu_bwd_bitmap_ref(x, g, (8, 128))
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx2))
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(bits2))


# ----------------------------------------------- paged decode attention
# The contiguous-cache decode-attention prototype was retired in favour
# of the paged-pool kernel (kernels/paged_decode_attn.py); its kernel
# parity / fetch-elision coverage lives in tests/test_paged_attn.py and
# the occupancy benchmark baseline in benchmarks/baselines/
# attn_baseline.json.
