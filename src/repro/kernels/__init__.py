"""Pallas TPU kernels for SparCE (compiled on a TPU, interpreted on CPU).

Modules: sparce_gemm (gated/compacted GEMM), sparce_mlp (fused MLP
megakernel), paged_decode_attn (fetch-skipping decode attention over the
paged KV pool), relu_bitmap (fused SVC), ops (padded jit wrappers), ref
(pure-jnp oracles).
"""
from repro.kernels import (  # noqa: F401
    ops, paged_decode_attn, ref, relu_bitmap, sparce_gemm, sparce_mlp,
)
