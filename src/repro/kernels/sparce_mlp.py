"""Fused SparCE MLP megakernel: up-proj, activation, bitmap, down-proj.

The paper's core loop is a *chain*: the producer writes a zero, the SpRF
is updated at writeback, and the consumer's fetch is skipped. The
two-kernel path (``relu_bitmap`` then ``sparce_gemm``) breaks that chain
on TPU: the up-projection materializes to HBM, the bitmap pass re-reads
it, and the gated down-projection re-reads it again -- three HBM round
trips where the paper does zero. This kernel restores the chain:

  1. **SpRF update at writeback** -- each (block_m, block_f) tile of the
     up-projection is activated and reduced to its ``isSparse`` bit in
     the same VMEM pass that produces it (SparseNN's observation that
     output sparsity is cheapest to detect at the producer's writeback).
  2. **VMEM-resident intermediate** -- the activated tile never leaves
     VMEM scratch; the down-projection consumes it immediately (SCNN's
     compounding win: the compacted operand stays in local memory).
  3. **Fetch skip before the fetch** -- the matching ``w_out`` f-stripe
     lives in HBM (``memory_space=ANY``) and is DMA'd manually; a zero
     tile's stripe DMA is *never issued*. This is the PSRU analogue:
     the skip decision precedes the operand fetch, not just the MXU op.
  4. **Double-buffered overlap** -- stripe DMAs land in a 2-slot VMEM
     buffer with a one-step skew: while stripe ``f`` is in flight, the
     MXU runs the down-projection for stripe ``f-1`` and the
     up-projection for tile ``f+1``.

Grid: ``(nm, nf)``, f innermost. Per row-tile the accumulator holds the
full (block_m, N) output row stripe in f32 VMEM scratch and flushes once.

K (d_model in) and N (d_model out) are unblocked: one x row-tile and one
w_out f-stripe must fit VMEM, which holds for MLP shapes (K, N = d_model,
the small dimension). The wrapper in ``kernels/ops.py`` pads ragged dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ACTS = ("relu", "relu2")


def _fused_mlp_kernel(
    x_ref, win_ref, wout_hbm, y_ref, bits_ref,
    a_sc, wbuf, acc_ref, bit_sc, sems,
    *, nf: int, block_f: int, act: str,
):
    """One grid step: up-proj tile f of row-tile i, bit, gated down-proj."""
    i = pl.program_id(0)
    f = pl.program_id(1)
    slot = jax.lax.rem(f, 2)
    prev = jax.lax.rem(f + 1, 2)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # -- up-projection tile + activation; the SVC bit rides on writeback --
    h = jnp.dot(x_ref[...], win_ref[...], preferred_element_type=jnp.float32)
    a = jnp.maximum(h, 0.0)
    if act == "relu2":
        a = a * a
    # Round to the input dtype exactly as the two-kernel path's HBM
    # writeback would -- keeps the fused kernel bit-compatible with the
    # reference contract in low precision (the tile still lives in VMEM).
    a = a.astype(x_ref.dtype).astype(jnp.float32)
    bit = jnp.where(jnp.max(a) == 0.0, jnp.int32(1), jnp.int32(0))
    bits_ref[i * nf + f] = bit
    a_sc[slot] = a
    bit_sc[slot] = bit

    def stripe_dma(s, ff):
        return pltpu.make_async_copy(
            wout_hbm.at[pl.ds(ff * block_f, block_f), :],
            wbuf.at[s],
            sems.at[s],
        )

    # -- fetch skip: a zero tile's w_out stripe DMA is never issued --
    @pl.when(bit == 0)
    def _start_fetch():
        stripe_dma(slot, f).start()

    # -- consume the PREVIOUS stripe: its DMA overlapped the dots above --
    @pl.when(jnp.logical_and(f > 0, bit_sc[prev] == 0))
    def _consume_prev():
        stripe_dma(prev, f - 1).wait()
        acc_ref[...] += jnp.dot(
            a_sc[prev], wbuf[prev].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    @pl.when(f == nf - 1)
    def _drain_and_flush():
        @pl.when(bit == 0)
        def _consume_last():
            stripe_dma(slot, f).wait()
            acc_ref[...] += jnp.dot(
                a_sc[slot], wbuf[slot].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )

        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_f", "act", "out_dtype", "interpret"),
)
def sparce_mlp_fused(
    x: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    *,
    block_m: int,
    block_f: int,
    act: str = "relu",
    out_dtype=None,
    interpret: bool = False,
):
    """(act(x @ w_in)) @ w_out in one kernel; returns (y, bits).

    x: (M, K); w_in: (K, F); w_out: (F, N). M % block_m == 0,
    F % block_f == 0 and, compiled, N % 128 == 0 are required (the w_out
    stripe DMA must be lane-aligned; ops.sparce_mlp_fused pads).
    bits: int32[M/block_m, F/block_f], 1 == activated tile all-zero --
    identical semantics to ``relu_bitmap`` over the intermediate, so the
    aux skip accounting matches the two-kernel path exactly.
    """
    if act not in _ACTS:
        raise ValueError(f"act must be one of {_ACTS}, got {act!r}")
    m, k = x.shape
    k2, fdim = w_in.shape
    f2, n = w_out.shape
    assert k == k2 and fdim == f2, (x.shape, w_in.shape, w_out.shape)
    if m % block_m or fdim % block_f:
        raise ValueError(
            f"padded dims required: M={m} % {block_m}, F={fdim} % {block_f}"
        )
    nm, nf = m // block_m, fdim // block_f
    out_dtype = out_dtype or x.dtype

    kernel = functools.partial(
        _fused_mlp_kernel, nf=nf, block_f=block_f, act=act
    )
    # Row tiles via an (nm, block_m, K) view with the tile axis squeezed
    # (any block_m compiles); the bitmap is one flat SMEM array.
    y, bits = pl.pallas_call(
        kernel,
        grid=(nm, nf),
        in_specs=[
            pl.BlockSpec((None, block_m, k), lambda i, f: (i, 0, 0)),
            pl.BlockSpec((k, block_f), lambda i, f: (0, f)),
            # w_out stays in HBM; the kernel DMAs only the live stripes.
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((None, block_m, n), lambda i, f: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nm, block_m, n), out_dtype),
            jax.ShapeDtypeStruct((nm * nf,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, block_m, block_f), jnp.float32),  # a tiles
            pltpu.VMEM((2, block_f, n), w_out.dtype),  # w_out stripes
            pltpu.VMEM((block_m, n), jnp.float32),  # output accumulator
            pltpu.SMEM((2,), jnp.int32),  # per-slot isSparse bits
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(x.reshape(nm, block_m, k), w_in, w_out)
    return y.reshape(m, n), bits.reshape(nm, nf)
