"""Paper Fig. 18 / Section 6.3: impact of operand ordering.

The paper: mapping the sparse features as the shared-SIMD operand gives
1.86x better benefit than mapping dense weights there (12% vs 6.5% for
AlexNet). TPU analogue: gate the tile-skipping on the operand with the
higher BLOCK-wise sparsity. We run both orderings through the actual
gated kernel and compare modeled savings; also the Deep-Compression
case where both operands are sparse (OR-condition gating).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timed
from repro.core import cost_model as cm
from repro.core import sasa, sprf
from repro.kernels import sparce_gemm as sgk
from repro.kernels.ops import interpret_mode

M, K, N = 256, 3456, 384


def run() -> None:
    key = jax.random.PRNGKey(0)
    feats = sprf.random_sparse(key, (M, K), 0.62, cluster=(8, 128))
    dense_w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)

    bm, bk, bn = 8, 128, 128
    fb = sprf.compute_bitmap(feats, (bm, bk))

    # ordering A (correct): gate on sparse features (lhs)
    _, us_a = timed(
        lambda: jax.block_until_ready(sgk.sparce_gemm_gated(
            feats, dense_w, fb.bits, block_m=bm, block_k=bk, block_n=bn,
            interpret=interpret_mode())), warmup=1, iters=2)
    skip_a = float(fb.sparsity())
    sv_a = cm.tpu_gemm_time(M, K, N, tile_skip_frac=skip_a, dtype_bytes=4)

    # ordering B (wrong): gate on the dense weights (rhs) -> no skips
    wb = sprf.compute_bitmap(dense_w, (bk, bn))
    _, us_b = timed(
        lambda: jax.block_until_ready(sgk.sparce_gemm_gated(
            feats, dense_w, wb.bits, gate="rhs",
            block_m=bm, block_k=bk, block_n=bn, interpret=interpret_mode())),
        warmup=1, iters=2)
    skip_b = float(wb.sparsity())
    sv_b = cm.tpu_gemm_time(M, K, N, tile_skip_frac=skip_b, dtype_bytes=4)

    red_a = 1 - sv_a.sparce_s / sv_a.base_s
    red_b = 1 - sv_b.sparce_s / sv_b.base_s
    ratio = red_a / max(red_b, 1e-9)
    emit("fig18/features_gated", us_a,
         f"tile_skip={skip_a:.3f};time_red={red_a:.3f}")
    emit("fig18/weights_gated", us_b,
         f"tile_skip={skip_b:.3f};time_red={red_b:.3f}")
    emit("fig18/ordering_ratio", 0.0,
         f"ratio={min(ratio, 99):.2f};paper=1.86x_for_simd4")

    # Deep-Compression case: both operands sparse -> OR condition
    pruned = sprf.prune_weights(dense_w, 0.8, block=(bk, bn))
    pb = sprf.compute_bitmap(pruned, (bk, bn))
    y, us_both = timed(
        lambda: jax.block_until_ready(sgk.sparce_gemm_gated_both(
            feats, pruned, fb.bits, pb.bits,
            block_m=bm, block_k=bk, block_n=bn, interpret=interpret_mode())),
        warmup=1, iters=2)
    or_skip = float(jnp.mean(jnp.maximum(
        fb.bits[:, :, None], pb.bits[None, :, :]).astype(jnp.float32)))
    emit("fig18/both_sparse_or", us_both,
         f"or_tile_skip={or_skip:.3f};"
         f"feat={float(fb.sparsity()):.2f};weight={float(pb.sparsity()):.2f}")
