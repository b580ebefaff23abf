"""Quickstart: the paper's technique in one file.

1. A sparse-activation GEMM skipped tile-by-tile (SpRF bitmap + SASA
   plan + gated Pallas kernel) vs its dense baseline.
2. A tiny ReLU LM trained with SparCE-gated MLPs (exact same loss
   trajectory as dense -- the transform is lossless).

Run: PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model, sasa, sprf
from repro.kernels import ops as kops

# ---------------------------------------------------------------- 1. GEMM
print("== SparCE gated GEMM ==")
M, K, N = 512, 2048, 512
key = jax.random.PRNGKey(0)

# Features out of a ReLU layer: ~60% zeros, clustered in rows.
x = sprf.random_sparse(key, (M, K), 0.6, cluster=(8, 128))
w = jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 0.02

# SASA-style static analysis chooses gating operand + tile shapes.
plan = sasa.plan_matmul(M, K, N, lhs_sparsity=0.6, lhs_cluster=8 * 128)
print(f"plan: gate={plan.gate} variant={plan.variant} "
      f"blocks={plan.block_m}x{plan.block_k}x{plan.block_n}")

# SpRF-style bitmap (produced fused into the ReLU in the full stack).
bitmap = sprf.compute_bitmap(x, plan.block_lhs)
print(f"tile-level sparsity: {float(bitmap.sparsity()):.1%}")

y_sparce = kops.sparce_gemm(x, w, plan, lhs_bitmap=bitmap)
y_dense = jnp.dot(x, w)
err = float(jnp.max(jnp.abs(y_sparce - y_dense)))
print(f"max |sparce - dense| = {err:.2e}  (bit-exact transform)")

sv = cost_model.tpu_gemm_time(
    M, K, N, tile_skip_frac=float(bitmap.sparsity()), dtype_bytes=4)
print(f"modeled v5e speedup at this sparsity: {sv.speedup:.2f}x\n")

# ------------------------------------------------------------------ 2. LM
print("== tiny ReLU LM with SparCE-gated MLPs ==")
import dataclasses

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.sparse_ops import SparsityConfig
from repro.data.pipeline import DataConfig, make_batch_iterator
from repro.optim.adamw import AdamW
from repro.runtime.trainer import TrainConfig, Trainer

cfg = dataclasses.replace(
    get_config("smollm-135m").reduced(),
    mlp_act="relu",  # the paper's sparsity source
    sparsity=SparsityConfig(enabled=True, mode="reference"),
)
shape = ShapeConfig("tiny", seq_len=32, global_batch=8, kind="train")
trainer = Trainer(cfg, shape, AdamW(lr=3e-3, weight_decay=0.0),
                  TrainConfig(steps=30, log_every=10))
out = trainer.run(make_batch_iterator(cfg, shape, DataConfig(noise=0.05)))
losses = [h["loss"] for h in out["history"] if "loss" in h]
print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f} over {len(losses)} steps")
assert losses[-1] < losses[0]
print("quickstart OK")
