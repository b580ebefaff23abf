"""High-level SparCE ops used by the model layers.

``sparce_matmul`` is the first-class integration point of the paper's
technique: a matmul whose forward pass drops all-zero tiles of the sparse
operand (features / pruned weights) and whose *backward* pass gates the
BP and WG GEMMs on error sparsity -- the paper's training-time story
(Section 2.2.2: error sparsity from ReLU-backward; Section 6.1: BP gains
exceed FP gains because errors are sparser than features).

Modes:
  * 'kernel'    -- Pallas kernels (compiled on a TPU, interpreted on the
                   CPU backend; ``kernels.ops.interpret_mode`` decides).
  * 'reference' -- masked-dense jnp ops with identical semantics. This is
                   what the distributed model stacks use so that pjit/XLA
                   sees plain einsums (and the dry-run lowers collectives
                   cleanly); tile-skip *accounting* still happens.
  * 'off'       -- plain dense matmul (the baseline the paper compares to).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import sasa, sprf
from repro.kernels import ops as kops
from repro.kernels import ref as kref


@dataclasses.dataclass(frozen=True)
class SparsityConfig:
    """First-class framework config for the paper's technique."""

    enabled: bool = False
    mode: str = "reference"  # 'fused' | 'kernel' | 'reference' | 'off'
    block_m: int = 64
    block_k: int = 128
    block_n: int = 128
    gate_activations: bool = True  # dynamic feature sparsity (FP)
    gate_errors: bool = True  # dynamic error sparsity (BP/WG)
    gate_weights: bool = False  # static pruned-weight sparsity
    weight_sparsity: float = 0.0  # pruning level applied at init when >0
    relufication: bool = False  # swap smooth MLP act for relu^2
    # Planner-v2 inputs (mode='fused'): the measured block-sparsity
    # estimate the MLP plan is built from (bucketed; a changed value
    # means a retrace, so the serving engine only updates it when the
    # EMA crosses a bucket edge), and whether the engine may do so.
    expected_sparsity: float = 0.0
    autotune: bool = False
    # Gated-GLU (silu/gelu) near-zero threshold: a gate tile with every
    # |act(g)| <= gate_threshold is dead -- its up-projection is never
    # computed and its w_in/w_out stripes are never fetched. 0.0 is the
    # exact all-zero test (lossless; dead serving slots still skip);
    # calibrated small values trade bounded output error for skips on
    # smooth activations. Ignored by relu-family (2-matrix) MLPs.
    gate_threshold: float = 0.0

    def __post_init__(self):
        if self.gate_threshold < 0.0:
            raise ValueError(
                f"gate_threshold must be >= 0, got {self.gate_threshold}"
            )
        # Snap expected_sparsity to the SparsityEMA bucket grid at
        # validation time: the serving engine's replan check compares the
        # EMA's 1/8-bucketed measurement against this field, so an
        # off-grid config value (e.g. 0.3) could never compare equal and
        # always forced one needless re-jit on startup.
        v = min(max(float(self.expected_sparsity), 0.0), 1.0)
        snapped = round(v * sasa.SparsityEMA.BUCKETS) / sasa.SparsityEMA.BUCKETS
        object.__setattr__(self, "expected_sparsity", snapped)

    def block(self) -> Tuple[int, int]:
        return (self.block_m, self.block_k)


def _run_matmul(
    x, w, lbits, rbits, plan: sasa.SkipPlan, mode: str, out_dtype,
):
    if mode == "off" or plan.gate == "none":
        return jnp.dot(
            x.astype(jnp.float32), w.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ).astype(out_dtype)
    if mode == "kernel":
        lb = sprf.TileBitmap(lbits, plan.block_lhs, x.shape) if lbits is not None else None
        rb = sprf.TileBitmap(rbits, plan.block_rhs, w.shape) if rbits is not None else None
        return kops.sparce_gemm(
            x, w, plan, lhs_bitmap=lb, rhs_bitmap=rb,
            out_dtype=out_dtype,
        )
    # reference: masked dense (bit-exact with the kernel contract)
    return kref.sparce_gemm_ref(
        x, w,
        bits_lhs=lbits if plan.gate in ("lhs", "both") else None,
        bits_rhs=rbits if plan.gate in ("rhs", "both") else None,
        block_m=plan.block_m, block_k=plan.block_k, block_n=plan.block_n,
        out_dtype=out_dtype,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _sparce_matmul(x, w, lbits, rbits, plan, mode):
    return _run_matmul(x, w, lbits, rbits, plan, mode, x.dtype)


def _fwd(x, w, lbits, rbits, plan, mode):
    y = _run_matmul(x, w, lbits, rbits, plan, mode, x.dtype)
    return y, (x, w, lbits, rbits)


def _bwd(plan, mode, res, g):
    x, w, lbits, rbits = res
    m, k = x.shape
    _, n = w.shape
    # --- BP: dx = g @ w^T, gated on ERROR sparsity (bitmap of g). ---
    # The paper: errors are sparser than features => BP gains exceed FP.
    gbits = None
    bwd_gate = "none"
    if mode != "off" and plan.gate in ("lhs", "both"):
        gbits = sprf.compute_bitmap(g, (plan.block_m, plan.block_n)).bits
        bwd_gate = "lhs"
    dx_plan = sasa.SkipPlan(
        gate=bwd_gate, variant="gated" if bwd_gate != "none" else "dense",
        block_m=plan.block_m, block_k=plan.block_n, block_n=plan.block_k,
    )
    dx = _run_matmul(
        g, w.T, gbits, None, dx_plan, mode, x.dtype
    )
    # --- WG: dw = x^T @ g, gated on the FEATURE bitmap (transposed). ---
    wg_gate = "none"
    xtbits = None
    if mode != "off" and plan.gate in ("lhs", "both") and lbits is not None:
        xtbits = lbits.T
        wg_gate = "lhs"
    dw_plan = sasa.SkipPlan(
        gate=wg_gate, variant="gated" if wg_gate != "none" else "dense",
        block_m=plan.block_k, block_k=plan.block_m, block_n=plan.block_n,
    )
    dw = _run_matmul(
        x.T, g, xtbits, None, dw_plan, mode, w.dtype
    )
    return dx, dw, None, None


_sparce_matmul.defvjp(_fwd, _bwd)


def sparce_matmul(
    x: jax.Array,
    w: jax.Array,
    cfg: SparsityConfig,
    plan: Optional[sasa.SkipPlan] = None,
    *,
    lhs_bitmap: Optional[sprf.TileBitmap] = None,
    rhs_bitmap: Optional[sprf.TileBitmap] = None,
) -> jax.Array:
    """y = x @ w with SparCE tile skipping per ``cfg``/``plan``.

    x: (M, K) activations (M = flattened batch*seq), w: (K, N) weights.
    """
    if not cfg.enabled or cfg.mode == "off":
        return jnp.dot(x, w)
    if plan is None:
        gate = "lhs" if lhs_bitmap is not None else (
            "rhs" if rhs_bitmap is not None else "none"
        )
        if lhs_bitmap is not None and rhs_bitmap is not None:
            gate = "both"
        if gate == "lhs":
            # Hot path (serving MLP): memoised process-level plan.
            plan = sasa.bitmap_gated_plan(
                x.shape[0], x.shape[1], w.shape[1],
                block_m=cfg.block_m, block_k=cfg.block_k, block_n=cfg.block_n,
            )
        else:
            plan = sasa.SkipPlan(
                gate=gate, variant="gated",
                block_m=cfg.block_m, block_k=cfg.block_k, block_n=cfg.block_n,
            )
    lbits = lhs_bitmap.bits if lhs_bitmap is not None else None
    rbits = rhs_bitmap.bits if rhs_bitmap is not None else None
    return _sparce_matmul(x, w, lbits, rbits, plan, cfg.mode)


# ------------------------------------------------------------- fused MLP
# The megakernel path (SparsityConfig.mode='fused'): one Pallas kernel
# computes act(x @ w_in) @ w_out with the bitmap emitted at the
# activation's writeback, the intermediate VMEM-resident, and zero
# tiles' w_out stripe fetches never issued. Backward runs the reference
# semantics (recompute-from-x), so the op stays trainable.

def _fused_mlp_run(x, w_in, w_out, plan, act):
    y, bmp = kops.sparce_mlp_fused(
        x, w_in, w_out, block_m=plan.block_m, block_f=plan.block_f,
        act=act,
    )
    return y, bmp.bits


def two_kernel_mlp(x, w_in, w_out, plan, act="relu"):
    """The pre-fused pipeline the planner falls back to: dense up-proj,
    producer-fused relu+bitmap kernel, bitmap-gated down-proj kernel.
    Three HBM round trips of the intermediate -- what the fused variant
    eliminates -- but no VMEM residency requirement on K and N. The
    single implementation is shared by the fused-mode fallback, the
    measuring autotuner, and the benchmarks so all three time/serve the
    same pipeline. Returns (y, bits)."""
    h = jnp.dot(x, w_in)
    a, bmp = kops.relu_with_bitmap(h, (plan.block_m, plan.block_f))
    if act == "relu2":
        a = a * a  # same zero pattern: the bitmap stays valid
    gplan = sasa.bitmap_gated_plan(
        x.shape[0], w_in.shape[1], w_out.shape[1],
        block_m=plan.block_m, block_k=plan.block_f, block_n=plan.block_n,
    )
    y = kops.sparce_gemm(a, w_out, gplan, lhs_bitmap=bmp, out_dtype=x.dtype)
    return y, bmp.bits


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _sparce_mlp(x, w_in, w_out, plan, act):
    if plan.variant == "fused":
        return _fused_mlp_run(x, w_in, w_out, plan, act)
    if plan.variant == "two_kernel":
        return two_kernel_mlp(x, w_in, w_out, plan, act)
    h = jnp.dot(x, w_in)
    a = jnp.maximum(h, 0.0)
    if act == "relu2":
        a = a * a
    bits = sprf.compute_bitmap(a, (plan.block_m, plan.block_f)).bits
    return jnp.dot(a, w_out), bits


def _mlp_fwd_vjp(x, w_in, w_out, plan, act):
    out = _sparce_mlp(x, w_in, w_out, plan, act)
    return out, (x, w_in, w_out)


def _mlp_bwd_vjp(plan, act, res, cts):
    g, _ = cts  # no cotangent flows into the int32 bitmap
    x, w_in, w_out = res
    h = jnp.dot(
        x.astype(jnp.float32), w_in.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    r = jnp.maximum(h, 0.0)
    a = r * r if act == "relu2" else r
    gf = g.astype(jnp.float32)
    da = jnp.dot(gf, w_out.astype(jnp.float32).T)
    dw_out = jnp.dot(a.T, gf).astype(w_out.dtype)
    # d(act)/dh: relu -> 1[h>0]; relu2 -> 2*relu(h) (already 0 for h<=0).
    dh = da * ((2.0 * r) if act == "relu2" else (h > 0).astype(jnp.float32))
    dx = jnp.dot(dh, w_in.astype(jnp.float32).T).astype(x.dtype)
    dw_in = jnp.dot(x.astype(jnp.float32).T, dh).astype(w_in.dtype)
    return dx, dw_in, dw_out


_sparce_mlp.defvjp(_mlp_fwd_vjp, _mlp_bwd_vjp)


def sparce_mlp(
    x: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    act: str,
    cfg: SparsityConfig,
) -> Tuple[jax.Array, jax.Array, "sasa.MlpPlan"]:
    """Fused MLP forward under the planner-v2 MlpPlan.

    Returns (y, bits, plan) -- the plan rides along so callers can
    report honest skip accounting: the 'dense' fallback variant computes
    every tile, so its bits must not be counted as realized skips.

    x: (M, K); the plan is pulled from the process-level SASA cache keyed
    on shapes + the bucketed measured sparsity (cfg.expected_sparsity).
    cfg.block_* pin the tile geometry so skip accounting stays exactly
    comparable with the reference path; the planner still chooses the
    VARIANT (fused vs two-kernel fallback) from modeled HBM bytes.
    """
    m, k = x.shape
    _, f = w_in.shape
    _, n = w_out.shape
    plan = sasa.plan_mlp_cached(
        m, k, f, n,
        measured_block_sparsity=cfg.expected_sparsity,
        dtype=str(x.dtype),
        block_m=cfg.block_m, block_f=cfg.block_k, block_n=cfg.block_n,
    )
    y, bits = _sparce_mlp(x, w_in, w_out, plan, act)
    return y, bits, plan


# --------------------------------------------------------- gated-GLU MLP
# The GLU megakernel path: one Pallas kernel computes
# (act(x @ w_gate) * (x @ w_in)) @ w_out with the dead-tile bitmap
# emitted at the GATE's writeback (SparseNN's predicted-output-sparsity
# gating), so a dead tile's up-projection is never computed and its
# w_in/w_out stripe fetches are never issued -- two-sided skipping.
# Backward runs the exact (undropped) reference GLU gradient, so the op
# stays trainable at any threshold.

def unfused_glu_mlp(x, w_gate, w_in, w_out, plan, act, tau, mode="kernel"):
    """The pre-fused GLU pipeline the planner falls back to: dense gate
    + up GEMMs, threshold bitmap at the gate's writeback, bitmap-gated
    down-projection (compute skip only; six HBM round trips of the
    intermediate -- what the fused variant eliminates). Shared by the
    fused-mode fallback and the benchmarks. Returns (y, bits)."""
    g = jnp.dot(x, w_gate)
    ga = kref.glu_act_ref(g, act)
    bits = kref.gate_bitmap_ref(ga, (plan.block_m, plan.block_f), tau)
    h = jnp.dot(x, w_in)
    a = (ga.astype(jnp.float32) * h.astype(jnp.float32)).astype(x.dtype)
    bmp = sprf.TileBitmap(
        bits=bits, block=(plan.block_m, plan.block_f), shape=a.shape
    )
    gplan = sasa.bitmap_gated_plan(
        x.shape[0], w_in.shape[1], w_out.shape[1],
        block_m=plan.block_m, block_k=plan.block_f, block_n=plan.block_n,
    )
    if mode == "kernel":
        y = kops.sparce_gemm(
            a, w_out, gplan, lhs_bitmap=bmp, out_dtype=x.dtype,
        )
    else:
        y = kref.sparce_gemm_ref(
            a, w_out, bits_lhs=bits, block_m=plan.block_m,
            block_k=plan.block_f, block_n=plan.block_n, out_dtype=x.dtype,
        )
    return y, bits


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _sparce_glu_mlp(x, w_gate, w_in, w_out, plan, act, tau):
    if plan.variant == "fused":
        y, bmp = kops.sparce_glu_mlp_fused(
            x, w_gate, w_in, w_out, block_m=plan.block_m,
            block_f=plan.block_f, act=act, tau=tau,
        )
        return y, bmp.bits
    if plan.variant == "unfused":
        return unfused_glu_mlp(x, w_gate, w_in, w_out, plan, act, tau)
    # dense fallback: plain GLU; the bitmap still rides along (report
    # only -- the caller must not count it as realized skips).
    g = jnp.dot(x, w_gate)
    ga = kref.glu_act_ref(g, act)
    bits = kref.gate_bitmap_ref(ga, (plan.block_m, plan.block_f), tau)
    h = jnp.dot(x, w_in)
    a = (ga.astype(jnp.float32) * h.astype(jnp.float32)).astype(x.dtype)
    return jnp.dot(a, w_out), bits


def _glu_mlp_fwd_vjp(x, w_gate, w_in, w_out, plan, act, tau):
    out = _sparce_glu_mlp(x, w_gate, w_in, w_out, plan, act, tau)
    return out, (x, w_gate, w_in, w_out)


def _glu_mlp_bwd_vjp(plan, act, tau, res, cts):
    gy, _ = cts  # no cotangent flows into the int32 bitmap
    x, w_gate, w_in, w_out = res
    xf = x.astype(jnp.float32)
    g = jnp.dot(xf, w_gate.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    h = jnp.dot(xf, w_in.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    # Reference backward: the exact GLU gradient, ignoring the forward's
    # threshold drop (at tau=0 the dropped tiles are exactly zero so the
    # gradients agree; at tau>0 this is the standard straight-through
    # treatment of the approximation).
    ga, act_vjp = jax.vjp(lambda t: kref.glu_act_ref(t, act), g)
    gyf = gy.astype(jnp.float32)
    da = jnp.dot(gyf, w_out.astype(jnp.float32).T)
    dw_out = jnp.dot((ga * h).T, gyf).astype(w_out.dtype)
    dh = da * ga
    dg = act_vjp(da * h)[0]
    dx = (jnp.dot(dh, w_in.astype(jnp.float32).T)
          + jnp.dot(dg, w_gate.astype(jnp.float32).T)).astype(x.dtype)
    dw_in = jnp.dot(xf.T, dh).astype(w_in.dtype)
    dw_gate = jnp.dot(xf.T, dg).astype(w_gate.dtype)
    return dx, dw_gate, dw_in, dw_out


_sparce_glu_mlp.defvjp(_glu_mlp_fwd_vjp, _glu_mlp_bwd_vjp)


def sparce_glu_mlp(
    x: jax.Array,
    w_gate: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    act: str,
    cfg: SparsityConfig,
) -> Tuple[jax.Array, jax.Array, "sasa.MlpPlan"]:
    """Gated-GLU MLP forward under the planner-v2 GLU plan.

    Returns (y, bits, plan); as with :func:`sparce_mlp` the plan rides
    along so callers report honest skip accounting -- the 'dense'
    variant computes every tile. cfg.block_m/block_k pin the gate-tile
    geometry (block_k doubles as block_f over the intermediate, exactly
    like the relu path), cfg.gate_threshold is the dead-tile test.
    """
    m, k = x.shape
    _, f = w_in.shape
    _, n = w_out.shape
    plan = sasa.plan_glu_mlp_cached(
        m, k, f, n,
        measured_block_sparsity=cfg.expected_sparsity,
        dtype=str(x.dtype),
        block_m=cfg.block_m, block_f=cfg.block_k, block_n=cfg.block_n,
    )
    y, bits = _sparce_glu_mlp(
        x, w_gate, w_in, w_out, plan, act, float(cfg.gate_threshold),
    )
    return y, bits, plan


def glu_act_with_bitmap(
    g: jax.Array, act: str, cfg: SparsityConfig
) -> Tuple[jax.Array, Optional[sprf.TileBitmap]]:
    """Gate activation (f32-upcast convention) + dead-tile bitmap.

    The GLU analogue of :func:`relu_with_bitmap`: the bitmap is emitted
    at the gate's writeback from ``|act(g)| <= cfg.gate_threshold``, on
    the flattened-2D view the consuming matmul sees. Bit semantics are
    identical to the fused megakernel's, so skip accounting matches
    exactly across paths.
    """
    shape = g.shape
    g2 = g.reshape(-1, shape[-1])
    ga2 = kref.glu_act_ref(g2, act)
    if not cfg.enabled or cfg.mode == "off" or not cfg.gate_activations:
        return ga2.reshape(shape), None
    bits = kref.gate_bitmap_ref(
        ga2, (cfg.block_m, cfg.block_k), float(cfg.gate_threshold)
    )
    return ga2.reshape(shape), sprf.TileBitmap(
        bits=bits, block=(cfg.block_m, cfg.block_k), shape=g2.shape
    )


def gemm_skip_stats(
    bitmap: Optional[sprf.TileBitmap], n: int, block_n: int
) -> jax.Array:
    """[skipped_tile_dots, total_tile_dots] for an lhs-gated y = x @ w.

    Each lhs tile bit gates ``grid_n`` MXU tile-dots (one per output
    column tile); the pair is the SASA-style accounting the paper reports
    (redundant-MAC fraction, Fig. 4) at tile granularity, and is what the
    serving engine surfaces as ``mlp_skip_fraction``.
    """
    if bitmap is None:
        return jnp.zeros((2,), jnp.float32)
    grid_n = -(-n // block_n)
    total = bitmap.bits.size * grid_n
    skipped = jnp.sum(bitmap.bits).astype(jnp.float32) * grid_n
    return jnp.stack([skipped, jnp.asarray(total, jnp.float32)])


def relu_with_bitmap(
    x: jax.Array, cfg: SparsityConfig
) -> Tuple[jax.Array, Optional[sprf.TileBitmap]]:
    """Producer-fused SVC: relu + tile bitmap in one pass.

    Accepts (..., features); bitmap is over the flattened-2D view, which is
    exactly the layout the consuming matmul sees.
    """
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if not cfg.enabled or cfg.mode == "off":
        return jnp.maximum(x, 0), None
    if cfg.mode == "kernel":
        y2, bmp = kops.relu_with_bitmap(x2, (cfg.block_m, cfg.block_k))
        return y2.reshape(shape), bmp
    y2 = jnp.maximum(x2, 0)
    return y2.reshape(shape), sprf.compute_bitmap(y2, (cfg.block_m, cfg.block_k))


def relu2_with_bitmap(
    x: jax.Array, cfg: SparsityConfig
) -> Tuple[jax.Array, Optional[sprf.TileBitmap]]:
    """Squared ReLU ('relufication' option): same zero pattern as ReLU."""
    y, bmp = relu_with_bitmap(x, cfg)
    return y * y, bmp
