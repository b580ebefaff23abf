"""Public jit'd wrappers over the SparCE Pallas kernels.

Handles padding to block multiples, variant/gate dispatch from a SkipPlan,
and the transpose trick that reuses the lhs-compacted kernel for
rhs-gated compaction.

Every wrapper here is deliberately mesh-OBLIVIOUS: under SPMD serving
the engine calls them inside a ``shard_map`` body with per-shard
operands (a 1/tp head or FFN slice, a data shard's sub-pool and LOCAL
block table), and the fetch-skipping machinery -- null-block table
entries, index-map clamps, dead-stripe bitmaps -- works shard-locally
without modification because it never assumes anything about the global
array. tests/test_spmd_serving.py NaN-poison-proves this per shard.

Whether a kernel is compiled or interpreted is decided here and nowhere
else: :func:`interpret_mode` reads the backend, so a TPU always runs the
compiled kernels and only the CPU backend interprets them.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.sasa import SkipPlan
from repro.core.sprf import TileBitmap
from repro.kernels import paged_decode_attn as _pda
from repro.kernels import sparce_gemm as _sg
from repro.kernels import relu_bitmap as _rb
from repro.kernels import sparce_glu_mlp as _sgm
from repro.kernels import sparce_mlp as _sm


# TPU vector registers are 128 lanes wide: a DMA'd weight stripe must
# span whole lane tiles.
_LANES = 128


def interpret_mode() -> bool:
    """Pallas kernels run compiled on a TPU and interpreted on the CPU
    backend; this is the one place that makes that decision."""
    return jax.default_backend() == "cpu"


def _ceil_to(v: int, q: int) -> int:
    return -(-v // q) * q


def _pad2(x: jax.Array, r: int, c: int) -> jax.Array:
    if x.shape == (r, c):
        return x
    return jnp.pad(x, ((0, r - x.shape[0]), (0, c - x.shape[1])))


def sparce_gemm(
    x: jax.Array,
    w: jax.Array,
    plan: SkipPlan,
    *,
    lhs_bitmap: Optional[TileBitmap] = None,
    rhs_bitmap: Optional[TileBitmap] = None,
    out_dtype=None,
) -> jax.Array:
    """y[M,N] = x[M,K] @ w[K,N] under ``plan``, dropping gated tiles."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype
    bm, bk, bn = plan.block_m, plan.block_k, plan.block_n
    pm, pk, pn = _ceil_to(m, bm), _ceil_to(k, bk), _ceil_to(n, bn)
    xp, wp = _pad2(x, pm, pk), _pad2(w, pk, pn)

    def fit_bits(bmp: TileBitmap, grid):
        assert bmp.block in ((bm, bk), (bk, bn)), (bmp.block, plan)
        bits = bmp.bits
        if bits.shape != grid:
            # Padding tiles are all-zero => skippable => bit 1.
            bits = jnp.pad(
                bits,
                ((0, grid[0] - bits.shape[0]), (0, grid[1] - bits.shape[1])),
                constant_values=1,
            )
        return bits

    interpret = interpret_mode()
    gate = plan.gate
    if gate == "none" or plan.variant == "dense":
        y = jnp.dot(
            xp.astype(jnp.float32), wp.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ).astype(out_dtype)
        return y[:m, :n]

    if gate == "lhs":
        assert lhs_bitmap is not None
        bits = fit_bits(lhs_bitmap, (pm // bm, pk // bk))
        fn = (
            _sg.sparce_gemm_compacted
            if plan.variant == "compacted"
            else _sg.sparce_gemm_gated
        )
        y = fn(
            xp, wp, bits, block_m=bm, block_k=bk, block_n=bn,
            out_dtype=out_dtype, interpret=interpret,
        )
    elif gate == "rhs":
        assert rhs_bitmap is not None
        bits = fit_bits(rhs_bitmap, (pk // bk, pn // bn))
        if plan.variant == "compacted":
            # y = (w^T @ x^T)^T with lhs-gating on w^T's (n, k) tiles.
            yt = _sg.sparce_gemm_compacted(
                wp.T, xp.T, bits.T, block_m=bn, block_k=bk, block_n=bm,
                out_dtype=out_dtype, interpret=interpret,
            )
            y = yt.T
        else:
            y = _sg.sparce_gemm_gated(
                xp, wp, bits, gate="rhs", block_m=bm, block_k=bk,
                block_n=bn, out_dtype=out_dtype, interpret=interpret,
            )
    elif gate == "both":
        assert lhs_bitmap is not None and rhs_bitmap is not None
        lb = fit_bits(lhs_bitmap, (pm // bm, pk // bk))
        rb = fit_bits(rhs_bitmap, (pk // bk, pn // bn))
        y = _sg.sparce_gemm_gated_both(
            xp, wp, lb, rb, block_m=bm, block_k=bk, block_n=bn,
            out_dtype=out_dtype, interpret=interpret,
        )
    else:
        raise ValueError(gate)
    return y[:m, :n]


def sparce_mlp_fused(
    x: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    *,
    block_m: int,
    block_f: int,
    act: str = "relu",
    out_dtype=None,
) -> tuple[jax.Array, TileBitmap]:
    """Padded wrapper over the fused MLP megakernel.

    Returns (y[M, N], bitmap) where the bitmap covers the activated
    intermediate act(x @ w_in) at (block_m, block_f) granularity -- the
    same TileBitmap the two-kernel path would produce, so skip
    accounting is identical. Padding rows/stripes are all-zero after the
    activation, so their bits are 1 and their w_out stripes never fetch.
    N pads to whole lanes (zero output columns, sliced off).
    """
    m, k = x.shape
    k2, fdim = w_in.shape
    f2, n = w_out.shape
    assert k == k2 and fdim == f2, (x.shape, w_in.shape, w_out.shape)
    pm, pf = _ceil_to(m, block_m), _ceil_to(fdim, block_f)
    xp = _pad2(x, pm, k)
    winp = _pad2(w_in, k, pf)
    woutp = _pad2(w_out, pf, _ceil_to(n, _LANES))
    y, bits = _sm.sparce_mlp_fused(
        xp, winp, woutp, block_m=block_m, block_f=block_f, act=act,
        out_dtype=out_dtype, interpret=interpret_mode(),
    )
    return y[:m, :n], TileBitmap(
        bits=bits, block=(block_m, block_f), shape=(m, fdim)
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
) -> tuple[jax.Array, TileBitmap]:
    """Padded wrapper over the gated-GLU megakernel.

    Returns (y[M, N], bitmap) where the bitmap covers the activated gate
    act(x @ w_gate) at (block_m, block_f) granularity -- the same grid
    the unfused gate-thresholding path produces, so skip accounting is
    identical. Padding stripes see zero gate weights, act(0) == 0 and
    ``|0| <= tau``, so their bits are 1 and their w_in/w_out stripes are
    never fetched; padding rows can only vote "dead" and never flip a
    real tile live. N pads to whole lanes (zero output columns, sliced
    off).
    """
    m, k = x.shape
    kg, fg = w_gate.shape
    k2, fdim = w_in.shape
    f2, n = w_out.shape
    assert k == kg == k2 and fdim == fg == f2, (
        x.shape, w_gate.shape, w_in.shape, w_out.shape)
    pm, pf = _ceil_to(m, block_m), _ceil_to(fdim, block_f)
    xp = _pad2(x, pm, k)
    wgatep = _pad2(w_gate, k, pf)
    winp = _pad2(w_in, k, pf)
    woutp = _pad2(w_out, pf, _ceil_to(n, _LANES))
    y, bits = _sgm.sparce_glu_mlp_fused(
        xp, wgatep, winp, woutp, block_m=block_m, block_f=block_f,
        act=act, tau=tau, out_dtype=out_dtype, interpret=interpret_mode(),
    )
    return y[:m, :n], TileBitmap(
        bits=bits, block=(block_m, block_f), shape=(m, fdim)
    )


def _pad_last(x: jax.Array, q: int) -> jax.Array:
    """Zero-pad the last axis up to a multiple of ``q``."""
    d = x.shape[-1]
    pd = _ceil_to(d, q)
    if pd == d:
        return x
    pads = [(0, 0)] * (x.ndim - 1) + [(0, pd - d)]
    return jnp.pad(x, pads)


def paged_decode_attn(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    feat_align: int = 1,
) -> jax.Array:
    """Ragged-shape wrapper over the paged GQA decode kernel.

    Unlike the retired contiguous prototype (which hard-errored on
    ``L % block_l != 0``), the sequence dimension needs no tile
    alignment at all: the kernel walks each slot's live table prefix
    (``max_blocks`` is any positive int -- the pool is sized by
    ``ceil(max_rows / block_size)``, never rounded up) and copies no
    entry at/past each live length, so there is nothing to pad there.
    ``feat_align > 1`` additionally pads ragged head dims up to that
    many lanes (zero features move no scores; the
    padded output columns are sliced off) -- an OPT-IN for compiled TPU
    mode with a non-lane-aligned head dim, because padding the pool
    here copies it every call; production pools should be ALLOCATED
    lane-aligned instead (head dims 64/128 already are), and interpret
    mode needs no alignment.

    q: (B, KV, g, D); pools: (nb, bs, KV, D); block_tables:
    int32 (B, max_blocks); lengths: int32 (B,) live rows (0 = inactive).
    """
    D = q.shape[-1]
    scale = scale if scale is not None else D**-0.5
    out = _pda.paged_gqa_decode_attn(
        _pad_last(q, feat_align),
        _pad_last(k_pool, feat_align),
        _pad_last(v_pool, feat_align),
        block_tables,
        jnp.minimum(lengths, block_tables.shape[1] * k_pool.shape[1]),
        scale=scale, interpret=interpret_mode(),
    )
    return out[..., :D]


def paged_mla_decode_attn(
    q_lat: jax.Array,
    q_rope: jax.Array,
    ckv_pool: jax.Array,
    kr_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: float,
    feat_align: int = 1,
) -> jax.Array:
    """Ragged-shape wrapper over the paged MLA absorbed-decode kernel.

    ``feat_align > 1`` pads the latent (r) and rope dims up to that
    many lanes (see :func:`paged_decode_attn` for when to opt in).
    Returns (B, h, r) latent-space context.
    """
    r = q_lat.shape[-1]
    out = _pda.paged_mla_decode_attn(
        _pad_last(q_lat, feat_align),
        _pad_last(q_rope, feat_align),
        _pad_last(ckv_pool, feat_align),
        _pad_last(kr_pool, feat_align),
        block_tables,
        jnp.minimum(lengths, block_tables.shape[1] * ckv_pool.shape[1]),
        scale=scale, interpret=interpret_mode(),
    )
    return out[..., :r]


def relu_with_bitmap(x: jax.Array, block) -> tuple[jax.Array, TileBitmap]:
    """Fused relu + SVC bitmap over a 2-D activation."""
    r, c = x.shape
    br, bc = block
    pr, pc = _ceil_to(r, br), _ceil_to(c, bc)
    xp = _pad2(x, pr, pc)
    y, bits = _rb.relu_bitmap(
        xp, block_r=br, block_c=bc, interpret=interpret_mode())
    return y[:r, :c], TileBitmap(bits=bits, block=(br, bc), shape=(r, c))

