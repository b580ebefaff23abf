"""Paper Fig. 17: SparCE performance scaling with sparsity.

The paper's exact setup: B(169x3456) @ A(3456x384), B-matrix sparsity
swept with zero locations chosen at random. We report:

  * GPP model: execution time + fraction-of-instructions-executed
    (scalar and SIMD4), vs the paper's observed strong scaling.
  * TPU kernels, ACTUALLY RUN (interpret mode): executed-tile fraction
    from the bitmap, modeled v5e time from the tile model, and the
    gated vs compacted variant comparison. Two sparsity geometries:
    iid-word zeros (the paper's setup -- tile harvest collapses, which
    IS the SIMD-coarsening lesson at MXU scale) and block-clustered
    zeros (where tile skipping recovers the paper's curve).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed
from repro.core import cost_model as cm
from repro.core import sasa, sprf
from repro.kernels import sparce_gemm as sgk

M, K, N = 169, 3456, 384  # the paper's Fig. 17 matrices


def run() -> None:
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)

    for s in (0.1, 0.3, 0.5, 0.7, 0.9):
        # --- GPP model (paper-faithful)
        for gpp, label in ((cm.SCALAR_GPP, "scalar"), (cm.SIMD4_GPP, "simd4")):
            g = cm.gpp_gemm_time(M, K, N, sparsity=s, cfg=gpp)
            emit(f"fig17/gpp_{label}/s{int(s*100)}", 0.0,
                 f"speedup={g['speedup']:.3f};"
                 f"instr_frac={g['instr_frac_executed']:.3f};ideal={1-s:.2f}")

        # --- TPU kernel, actually executed (interpret) per geometry
        from repro.kernels import ops as kops
        for cluster, geo in (((8, 128), "clustered"), (None, "iid")):
            plan = sasa.plan_matmul(
                M, K, N, lhs_sparsity=s,
                lhs_cluster=1 if cluster is None else cluster[0] * cluster[1])
            bm, bk = plan.block_m, plan.block_k
            x = sprf.random_sparse(key, (M, K), s, cluster=cluster)
            bmp = sprf.compute_bitmap(x, (bm, bk))
            tile_skip = float(bmp.sparsity())

            run_plan = plan if plan.gate != "none" else sasa.SkipPlan(
                gate="lhs", variant="gated",
                block_m=bm, block_k=bk, block_n=plan.block_n)
            out, us = timed(
                lambda: jax.block_until_ready(kops.sparce_gemm(
                    x, w, run_plan, lhs_bitmap=bmp)),
                warmup=1, iters=2)
            sv = cm.tpu_gemm_time(M, K, N, tile_skip_frac=tile_skip,
                                  dtype_bytes=4)
            emit(f"fig17/tpu_{geo}/s{int(s*100)}", us,
                 f"word={s:.2f};tile_skip={tile_skip:.3f};"
                 f"blocks={bm}x{bk};variant={plan.variant};"
                 f"modeled_speedup={sv.speedup:.3f}")
