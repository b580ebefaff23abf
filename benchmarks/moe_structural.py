"""Beyond-paper: MoE routing as SparCE structural sparsity.

Top-k routing makes (num_experts - k)/num_experts of expert-weight tiles
redundant per token -- exactly the paper's dynamic sparsity, made
structural. The dispatch buffer's slot-occupancy mask IS a tile bitmap;
we measure it on the reduced MoE configs and run the gated kernel over
the padded expert GEMM, reporting the skip fraction a SparCE-style
expert GEMM harvests over a dense (compute-every-slot) baseline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timed
from repro.configs import get_config
from repro.core import cost_model as cm
from repro.core import sprf
from repro.kernels import sparce_gemm as sgk
from repro.kernels.ops import interpret_mode
from repro.models import moe as moe_lib
from repro.models import model as model_lib


def run() -> None:
    for arch in ("deepseek-v3-671b", "qwen2-moe-a2.7b"):
        cfg = get_config(arch)
        m = cfg.moe
        # structural bound: fraction of expert compute skippable
        bound = 1.0 - m.top_k / m.num_experts
        emit(f"moe/{arch}/structural_bound", 0.0,
             f"skippable={bound:.4f};experts={m.num_experts};topk={m.top_k}")

        # measured slot occupancy on the reduced config
        rcfg = get_config(arch).reduced()
        params = model_lib.init_params(rcfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, rcfg.d_model))
        moe_params = jax.tree_util.tree_map(
            lambda a: a[0], params["stack"])["moe"]
        (y, aux, slot_sparsity), us = timed(
            lambda: jax.block_until_ready(
                moe_lib.moe_forward(moe_params, x, rcfg)))
        emit(f"moe/{arch}/slot_sparsity_reduced", us,
             f"unused_slot_frac={float(slot_sparsity):.3f};"
             f"cap_factor={m.capacity_factor}")

        # gated kernel over a padded expert GEMM (one expert's slots)
        C, d, ff = 128, 128, 256
        occupied = 40  # tokens actually routed here
        buf = jnp.zeros((C, d)).at[:occupied].set(
            jax.random.normal(jax.random.PRNGKey(2), (occupied, d)))
        wexp = jax.random.normal(jax.random.PRNGKey(3), (d, ff))
        bmp = sprf.compute_bitmap(buf, (8, 128))
        _, us_k = timed(
            lambda: jax.block_until_ready(sgk.sparce_gemm_gated(
                buf, wexp, bmp.bits, block_m=8, block_k=128, block_n=128,
                interpret=interpret_mode())), warmup=1, iters=2)
        skip = float(bmp.sparsity())
        sv = cm.tpu_gemm_time(C, d, ff, tile_skip_frac=skip, dtype_bytes=2)
        emit(f"moe/{arch}/gated_expert_gemm", us_k,
             f"tile_skip={skip:.3f};modeled_speedup={sv.speedup:.2f}")
