"""Fused ReLU + tile-bitmap Pallas kernel: the SVC-at-writeback analogue.

SparCE's Sparse Value Checker rides on the writeback stage: the zero check
costs no extra pass because it happens while the value is being written.
The TPU analogue: the producer kernel that writes the activation tile also
reduces it to its ``isSparse`` bit in the same VMEM pass, so bitmap
production is fused with the ReLU that creates the zeros -- no extra HBM
read. Also provided: the ReLU backward + error-bitmap fusion (error
sparsity for the BP/WG steps, Section 2.2.2 of the paper).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _relu_bitmap_kernel(x_ref, y_ref, bits_ref, *, nc: int):
    y = jnp.maximum(x_ref[...], 0)
    y_ref[...] = y.astype(y_ref.dtype)
    # Writeback-fused SVC: reduce the tile we just produced to one bit
    # (reduced in f32: the chip moves only 32-bit values to scalars).
    bits_ref[pl.program_id(0) * nc + pl.program_id(1)] = jnp.where(
        jnp.max(y.astype(jnp.float32)) == 0, jnp.int32(1), jnp.int32(0)
    )


def _relu_bwd_bitmap_kernel(x_ref, g_ref, gx_ref, bits_ref, *, nc: int):
    gx = jnp.where(x_ref[...] > 0, g_ref[...], jnp.zeros_like(g_ref[...]))
    gx_ref[...] = gx.astype(gx_ref.dtype)
    bits_ref[pl.program_id(0) * nc + pl.program_id(1)] = jnp.where(
        jnp.max(jnp.abs(gx).astype(jnp.float32)) == 0, jnp.int32(1), jnp.int32(0)
    )


def _tiled_call(kernel, operands, out_dtype, block_r, block_c, interpret):
    """Elementwise tile pass + one bit per tile.

    Rows go through an (nr, block_r, c) view with the tile axis squeezed,
    so any block_r compiles; the bitmap is one flat SMEM array written at
    i * nc + j (block_c must be a multiple of 128 when compiled)."""
    r, c = operands[0].shape
    if r % block_r or c % block_c:
        raise ValueError(
            f"padded dims required: {(r, c)} % ({block_r},{block_c})")
    nr, nc = r // block_r, c // block_c
    tile = pl.BlockSpec((None, block_r, block_c), lambda i, j: (i, 0, j))
    y, bits = pl.pallas_call(
        functools.partial(kernel, nc=nc),
        grid=(nr, nc),
        in_specs=[tile] * len(operands),
        out_specs=[tile, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((nr, block_r, c), out_dtype),
            jax.ShapeDtypeStruct((nr * nc,), jnp.int32),
        ],
        interpret=interpret,
    )(*(o.reshape(nr, block_r, c) for o in operands))
    return y.reshape(r, c), bits.reshape(nr, nc)


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_c", "interpret")
)
def relu_bitmap(
    x: jax.Array, *, block_r: int, block_c: int, interpret: bool = False
):
    """Returns (relu(x), bits) with bits int32[r/block_r, c/block_c]."""
    return _tiled_call(
        _relu_bitmap_kernel, (x,), x.dtype, block_r, block_c, interpret)


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_c", "interpret")
)
def relu_bwd_bitmap(
    x: jax.Array, g: jax.Array, *, block_r: int, block_c: int,
    interpret: bool = False,
):
    """Returns (g * (x > 0), error-bits) -- fused error-sparsity writeback."""
    assert g.shape == x.shape
    return _tiled_call(
        _relu_bwd_bitmap_kernel, (x, g), g.dtype, block_r, block_c,
        interpret)
