"""SparCE kernel anatomy demo: gated vs compacted vs dense, with the
skip accounting the paper reports (instructions skipped -> tiles
skipped; D-cache accesses -> HBM tile fetches).

Run: PYTHONPATH=src python examples/sparse_gemm_demo.py [sparsity]
"""
import sys

import jax
import jax.numpy as jnp

from repro.core import cost_model, sprf
from repro.kernels import sparce_gemm as sgk
from repro.kernels.ops import interpret_mode

s = float(sys.argv[1]) if len(sys.argv) > 1 else 0.7
M, K, N = 256, 3456, 384  # paper Fig. 17 inner dims (padded M)
bm, bk, bn = 8, 128, 128

x = sprf.random_sparse(jax.random.PRNGKey(0), (M, K), s, cluster=(bm, bk))
w = jax.random.normal(jax.random.PRNGKey(1), (K, N))
w = jnp.pad(w, ((0, 0), (0, 128 * ((N + 127) // 128) - N)))
bmp = sprf.compute_bitmap(x, (bm, bk))
nm, nk = bmp.grid
total_tiles = nm * nk
skipped = int(bmp.num_skipped())

print(f"word sparsity {s:.0%} -> {skipped}/{total_tiles} tiles skippable "
      f"({skipped / total_tiles:.1%})")

y_g = sgk.sparce_gemm_gated(
    x, w, bmp.bits, block_m=bm, block_k=bk, block_n=128,
    interpret=interpret_mode())
y_c = sgk.sparce_gemm_compacted(
    x, w, bmp.bits, block_m=bm, block_k=bk, block_n=128,
    interpret=interpret_mode())
y_d = jnp.dot(x, w)
print(f"gated     max err vs dense: {float(jnp.abs(y_g - y_d).max()):.2e}")
print(f"compacted max err vs dense: {float(jnp.abs(y_c - y_d).max()):.2e}")

# Savings accounting (the paper's Fig. 16 metrics, TPU units)
frac = skipped / total_tiles
sv = cost_model.tpu_gemm_time(M, K, N, tile_skip_frac=frac, dtype_bytes=4)
print(f"MXU steps skipped:   {frac:.1%}  (instructions, in paper terms)")
print(f"HBM fetch skipped:   {sv.bytes_skipped_frac:.1%}  (D-cache, in paper terms)")
print(f"modeled v5e speedup: {sv.speedup:.2f}x")
