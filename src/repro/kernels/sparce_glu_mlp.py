"""Gated-GLU SparCE megakernel: predict-then-skip for silu/gelu MLPs.

The plain-MLP megakernel (``sparce_mlp.py``) skips *after* the zeros
exist: the activation writes them, the bitmap rides the writeback, and
only the down-projection's fetches are elided. A GLU
``y = (act(x @ w_gate) * (x @ w_in)) @ w_out`` admits something
stronger -- SparseNN's predicted-OUTPUT-sparsity gating (PAPERS.md,
arxiv 1711.01263): the gate projection is cheap relative to the pair of
GEMMs it controls, and wherever ``|act(g)|`` is near zero the whole
intermediate tile is (near) zero *before it is computed*. So the kernel
computes the gate FIRST per (row-tile, f-stripe) step and emits the
SpRF bit at the gate's writeback:

  ``bit = all(|act(g_tile)| <= tau)``  (dead tile)

exact at ``tau=0`` for a relu-family gate (the bit fires only on true
zeros), value-approximate for silu/gelu at a calibrated ``tau`` (the
dropped tiles contribute at most ``tau * |h|`` each -- the serving
tests pin token parity at the default config).

The bit then gates TWO-SIDED, the paper's skip-before-fetch (PSRU)
applied on both ends of the dead tile's dataflow:

  * the ``w_in`` f-stripe is DMA'd from HBM and the up-projection tile
    dot is computed ONLY for live stripes -- the dead intermediate is
    never computed and its up-projection weights are never fetched;
  * the matching ``w_out`` f-stripe DMA is never issued either (the
    plain megakernel's one-sided skip).

Double buffering gives the overlap a one-step skew makes free: at step
``f`` the kernel computes the gate for stripe ``f`` and launches stripe
``f``'s (live) DMAs, while the MXU consumes stripe ``f-1`` from the
other slot. x and w_gate stream through the automatic Pallas pipeline
-- the gate is the predictor, so its weights are always read.

Grid ``(nm, nf)``, f innermost; K and N unblocked (same VMEM residency
contract as the plain megakernel; ``kernels/ops.py`` pads ragged dims).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_GLU_ACTS = ("silu", "gelu", "relu", "relu2")


def _gate_act_f32(g: jax.Array, act: str) -> jax.Array:
    """The canonical f32 gate activation (the moe.py upcast convention)."""
    if act == "silu":
        return jax.nn.silu(g)
    if act == "gelu":
        return jax.nn.gelu(g)
    if act == "relu":
        return jnp.maximum(g, 0.0)
    if act == "relu2":
        r = jnp.maximum(g, 0.0)
        return r * r
    raise ValueError(act)


def _glu_mlp_kernel(
    x_ref, wgate_ref, win_hbm, wout_hbm, y_ref, bits_ref,
    ga_sc, winbuf, woutbuf, acc_ref, bit_sc, sems,
    *, nf: int, block_f: int, act: str, tau: float,
):
    """One grid step: gate tile f of row-tile i, bit, gated up+down proj."""
    i = pl.program_id(0)
    f = pl.program_id(1)
    slot = jax.lax.rem(f, 2)
    prev = jax.lax.rem(f + 1, 2)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # -- gate projection first: the predictor runs before the work it
    # may cancel. Round g and act(g) through the input dtype exactly as
    # the unfused path's writebacks would, so the bit (and the values)
    # stay bit-compatible with the reference contract in low precision.
    g = jnp.dot(
        x_ref[...], wgate_ref[...], preferred_element_type=jnp.float32
    ).astype(x_ref.dtype).astype(jnp.float32)
    ga = _gate_act_f32(g, act).astype(x_ref.dtype).astype(jnp.float32)
    # -- SpRF bit at the gate's writeback: near-zero gate => dead tile.
    # `<=` makes tau=0 the exact all-zero test (relu-gated exactness).
    bit = jnp.where(jnp.max(jnp.abs(ga)) <= tau, jnp.int32(1), jnp.int32(0))
    bits_ref[i * nf + f] = bit
    ga_sc[slot] = ga
    bit_sc[slot] = bit

    def win_dma(s, ff):
        return pltpu.make_async_copy(
            win_hbm.at[:, pl.ds(ff * block_f, block_f)],
            winbuf.at[s],
            sems.at[s, 0],
        )

    def wout_dma(s, ff):
        return pltpu.make_async_copy(
            wout_hbm.at[pl.ds(ff * block_f, block_f), :],
            woutbuf.at[s],
            sems.at[s, 1],
        )

    # -- two-sided fetch skip: a dead tile's w_in AND w_out stripe DMAs
    # are never issued.
    @pl.when(bit == 0)
    def _start_fetch():
        win_dma(slot, f).start()
        wout_dma(slot, f).start()

    def _consume(s, ff):
        win_dma(s, ff).wait()
        wout_dma(s, ff).wait()
        # Up-projection tile dot only exists for live stripes: the dead
        # intermediate is never computed, not computed-and-discarded.
        h = jnp.dot(
            x_ref[...], winbuf[s].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ).astype(x_ref.dtype).astype(jnp.float32)
        a = (ga_sc[s] * h).astype(x_ref.dtype).astype(jnp.float32)
        acc_ref[...] += jnp.dot(
            a, woutbuf[s].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    # -- consume the PREVIOUS stripe: its DMAs overlapped the gate dot --
    @pl.when(jnp.logical_and(f > 0, bit_sc[prev] == 0))
    def _consume_prev():
        _consume(prev, f - 1)

    @pl.when(f == nf - 1)
    def _drain_and_flush():
        @pl.when(bit == 0)
        def _consume_last():
            _consume(slot, f)

        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m", "block_f", "act", "tau", "out_dtype", "interpret",
    ),
)
def sparce_glu_mlp_fused(
    x: jax.Array,
    w_gate: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    *,
    block_m: int,
    block_f: int,
    act: str = "silu",
    tau: float = 0.0,
    out_dtype=None,
    interpret: bool = False,
):
    """(act(x @ w_gate) * (x @ w_in)) @ w_out in one kernel.

    x: (M, K); w_gate, w_in: (K, F); w_out: (F, N). M % block_m == 0,
    F % block_f == 0 and, compiled, N % 128 == 0 are required (the w_out
    stripe DMA must be lane-aligned; ops.sparce_glu_mlp_fused pads).
    Returns (y, bits); bits: int32[M/block_m, F/block_f], 1 == every
    ``|act(g)|`` in the tile is <= tau -- identical semantics to the
    unfused gate-thresholding path, so skip accounting matches exactly.
    """
    if act not in _GLU_ACTS:
        raise ValueError(f"act must be one of {_GLU_ACTS}, got {act!r}")
    if tau < 0.0:
        raise ValueError(f"gate threshold must be >= 0, got {tau}")
    m, k = x.shape
    kg, fg = w_gate.shape
    k2, fdim = w_in.shape
    f2, n = w_out.shape
    assert k == kg == k2 and fdim == fg == f2, (
        x.shape, w_gate.shape, w_in.shape, w_out.shape)
    if m % block_m or fdim % block_f:
        raise ValueError(
            f"padded dims required: M={m} % {block_m}, F={fdim} % {block_f}"
        )
    nm, nf = m // block_m, fdim // block_f
    out_dtype = out_dtype or x.dtype

    kernel = functools.partial(
        _glu_mlp_kernel, nf=nf, block_f=block_f, act=act, tau=float(tau)
    )
    # Row tiles are addressed through an (nm, block_m, K) view with the
    # tile axis squeezed: a block's trailing dims then equal the array's,
    # which the TPU compiler accepts for any block_m (one decode slot per
    # tile included). The bitmap is one flat SMEM array, written at
    # i * nf + f and copied back once.
    y, bits = pl.pallas_call(
        kernel,
        grid=(nm, nf),
        in_specs=[
            pl.BlockSpec((None, block_m, k), lambda i, f: (i, 0, 0)),
            # The gate weights always stream: they are the predictor.
            pl.BlockSpec((k, block_f), lambda i, f: (0, f)),
            # w_in and w_out stay in HBM; the kernel DMAs live stripes only.
            pl.BlockSpec(memory_space=pl.ANY),
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
            pltpu.VMEM((2, block_m, block_f), jnp.float32),  # act(g) tiles
            pltpu.VMEM((2, k, block_f), w_in.dtype),  # w_in stripes
            pltpu.VMEM((2, block_f, n), w_out.dtype),  # w_out stripes
            pltpu.VMEM((block_m, n), jnp.float32),  # output accumulator
            pltpu.SMEM((2,), jnp.int32),  # per-slot isSparse bits
            pltpu.SemaphoreType.DMA((2, 2)),  # (slot, win/wout)
        ],
        interpret=interpret,
    )(x.reshape(nm, block_m, k), w_gate, w_in, w_out)
    return y.reshape(m, n), bits.reshape(nm, nf)
