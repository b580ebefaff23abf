"""SASA-table analogue: trace-time static analysis producing SkipPlans.

In SparCE, software performs a static dependency analysis of the
instruction stream, finds regions rendered redundant by a zero register,
and loads ``{precedingPC, SpRFCondition, instsToSkip}`` entries into the
SASA table via the SASA-LD instruction. The PSRU then consults the table
at fetch.

On TPU the "instruction stream" is the tiled GEMM schedule. The static
analysis moves to trace time: for each matmul we decide

  * which operand gates skipping (the paper's operand-ordering rule,
    Section 4.1 / 6.3: gate on the operand with the highest *block-wise*
    sparsity; on SIMD that operand is mapped as the shared one),
  * the tile shapes (MXU/VMEM-aligned -- the SIMD-lane coarsening),
  * the kernel variant (gated grid vs. compacted grid vs. dense).

The resulting :class:`SkipPlan` plus the runtime bitmap are the "SASA
entry": the bitmap is scalar-prefetched into SMEM so the skip condition is
evaluated *before* the tile's DMA is issued -- the analogue of skipping
instructions before they are fetched.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

_MXU_LANE = 128  # MXU/VPU lane width: last-dim tiles must be multiples.
_SUBLANE = {  # second-to-last dim granularity per dtype
    "float32": 8,
    "bfloat16": 16,
    "int8": 32,
}
# Per-core VMEM budget we allow a single GEMM's working set to claim.
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class SkipPlan:
    """Static skip schedule for one matmul y[M,N] = x[M,K] @ w[K,N]."""

    gate: str  # 'lhs' | 'rhs' | 'both' | 'none'
    variant: str  # 'gated' | 'compacted' | 'dense'
    block_m: int
    block_k: int
    block_n: int
    # Planner book-keeping (reported like the paper's SASA-entry counts):
    expected_block_sparsity: float = 0.0
    table_entries: int = 0  # grid positions carrying a skip condition

    @property
    def block_lhs(self) -> Tuple[int, int]:
        return (self.block_m, self.block_k)

    @property
    def block_rhs(self) -> Tuple[int, int]:
        return (self.block_k, self.block_n)


def expected_block_sparsity(
    word_sparsity: float, block_elems: int, cluster_elems: int = 1
) -> float:
    """Probability a whole tile is zero given word-level sparsity.

    Under i.i.d. zeros P(block zero) = p^(block/cluster_size_effective);
    clustering (paper 6.3: pruned-weight zeros are 'typically clustered')
    raises it. ``cluster_elems`` is the typical contiguous zero-run size.
    """
    if word_sparsity <= 0.0:
        return 0.0
    if word_sparsity >= 1.0:
        return 1.0
    eff = max(1, block_elems // max(1, cluster_elems))
    return float(word_sparsity**eff)


def _round_block(dim: int, target: int, quantum: int) -> int:
    """Largest multiple of ``quantum`` <= target that is sensible for dim."""
    if dim <= quantum:
        return quantum
    b = min(target, dim)
    b = max(quantum, (b // quantum) * quantum)
    return b


def plan_matmul(
    m: int,
    k: int,
    n: int,
    *,
    lhs_sparsity: float = 0.0,
    rhs_sparsity: float = 0.0,
    lhs_cluster: int = 1,
    rhs_cluster: int = 1,
    dtype: str = "float32",
    block_m: Optional[int] = None,
    block_k: Optional[int] = None,
    block_n: Optional[int] = None,
    min_expected_block_sparsity: float = 0.02,
) -> SkipPlan:
    """Static analysis for one GEMM: operand ordering + tiling + variant.

    Mirrors the paper's software design steps (Section 4.1):
      1. identify the sparse data structure(s),
      2. choose the gating operand = highest block-wise sparsity
         (the shared-SIMD-operand rule),
      3. emit the skip conditions (here: tile grid + bitmap association).
    """
    sub = _SUBLANE.get(dtype, 8)
    itemsize = 2 if dtype == "bfloat16" else 4

    def ws(bm_, bk_, bn_):
        return (bm_ * bk_ + bk_ * bn_ + bm_ * bn_) * itemsize

    if block_m and block_k and block_n:
        bm, bk, bn = block_m, block_k, block_n
    else:
        # Tile-size search: bigger tiles amortize grid/DMA overhead, but
        # tiles larger than the zero-cluster geometry destroy block
        # sparsity (the paper's SIMD-lane coarsening, taken to MXU scale).
        # Score = expected skip fraction + small bonus for larger tiles.
        bm_menu = [b for b in (sub, 2 * sub, 4 * sub, 8 * sub, 16 * sub, 256)
                   if b <= max(m, sub)]
        bk_menu = [b for b in (128, 256, 512) if b <= max(k, 128)]
        bn_menu = [b for b in (128, 256, 512) if b <= max(n, 128)]

        def pick(menu_a, menu_b, sparsity, cluster, fixed):
            best, best_score = None, -1.0
            for a in menu_a:
                for b in menu_b:
                    if ws(*fixed(a, b)) > _VMEM_BUDGET_BYTES:
                        continue
                    ebs = expected_block_sparsity(sparsity, a * b, cluster)
                    score = ebs + 0.02 * (1 + (a * b).bit_length() / 32.0)
                    if score > best_score:
                        best, best_score = (a, b), score
            return best or (menu_a[0], menu_b[0])

        if lhs_sparsity >= rhs_sparsity:
            bn = block_n or _round_block(n, 256, _MXU_LANE)
            bm, bk = pick(bm_menu, bk_menu, lhs_sparsity, lhs_cluster,
                          lambda a, b: (a, b, bn))
        else:
            bm = block_m or _round_block(m, 256, sub)
            bk, bn = pick(bk_menu, bn_menu, rhs_sparsity, rhs_cluster,
                          lambda a, b: (bm, a, b))
        bm, bk, bn = block_m or bm, block_k or bk, block_n or bn

    # Respect the VMEM working-set budget (x-tile + w-tile + out-tile).
    while ws(bm, bk, bn) > _VMEM_BUDGET_BYTES and bk > _MXU_LANE:
        bk //= 2
    while ws(bm, bk, bn) > _VMEM_BUDGET_BYTES and bn > _MXU_LANE:
        bn //= 2
    while ws(bm, bk, bn) > _VMEM_BUDGET_BYTES and bm > sub:
        bm //= 2

    lhs_bs = expected_block_sparsity(lhs_sparsity, bm * bk, lhs_cluster)
    rhs_bs = expected_block_sparsity(rhs_sparsity, bk * bn, rhs_cluster)

    if max(lhs_bs, rhs_bs) < min_expected_block_sparsity:
        gate, ebs = "none", 0.0
    elif lhs_bs >= min_expected_block_sparsity and rhs_bs >= min_expected_block_sparsity:
        gate, ebs = "both", 1.0 - (1.0 - lhs_bs) * (1.0 - rhs_bs)
    elif lhs_bs >= rhs_bs:
        gate, ebs = "lhs", lhs_bs
    else:
        gate, ebs = "rhs", rhs_bs

    if gate == "none":
        variant = "dense"
    elif ebs >= 0.5:
        # High block sparsity: compacting the grid (visit only nonzero
        # tiles) pays off -- the strict 'PC jumps over the region' mode.
        variant = "compacted"
    else:
        variant = "gated"

    grid_m = -(-m // bm)
    grid_k = -(-k // bk)
    grid_n = -(-n // bn)
    entries = grid_m * grid_k if gate in ("lhs", "both") else (
        grid_k * grid_n if gate == "rhs" else 0
    )
    return SkipPlan(
        gate=gate,
        variant=variant,
        block_m=bm,
        block_k=bk,
        block_n=bn,
        expected_block_sparsity=ebs,
        table_entries=entries,
    )


# ------------------------------------------------------- process-level cache
# The SASA table is loaded ONCE per static region and consulted many
# times (SASA-LD is hoisted out of the loop in the paper's Fig. 6). The
# serving analogue: one decode step traces thousands of times per second
# over the same (m, k, n) GEMM shapes, so plans are memoised process-wide
# keyed on (m, k, n, dtype, sparsity-bucket, tiling overrides).
_PLAN_CACHE: dict = {}
_PLAN_CACHE_STATS = {"hits": 0, "misses": 0}
_SPARSITY_BUCKETS = 64  # sparsity quantised to 1/64 for cache keying


def _bucket_sparsity(s: float) -> float:
    """Quantise a sparsity estimate so near-identical values share a plan."""
    s = min(max(float(s), 0.0), 1.0)
    return round(s * _SPARSITY_BUCKETS) / _SPARSITY_BUCKETS


def plan_matmul_cached(
    m: int,
    k: int,
    n: int,
    *,
    lhs_sparsity: float = 0.0,
    rhs_sparsity: float = 0.0,
    lhs_cluster: int = 1,
    rhs_cluster: int = 1,
    dtype: str = "float32",
    block_m: Optional[int] = None,
    block_k: Optional[int] = None,
    block_n: Optional[int] = None,
    min_expected_block_sparsity: float = 0.02,
) -> SkipPlan:
    """Memoised :func:`plan_matmul`.

    Sparsity estimates are bucketed to 1/64 before keying AND before
    planning, so a cached plan is always byte-identical to the uncached
    ``plan_matmul`` called with the bucketed sparsities.
    """
    ls, rs = _bucket_sparsity(lhs_sparsity), _bucket_sparsity(rhs_sparsity)
    key = ("plan", m, k, n, dtype, ls, rs, lhs_cluster, rhs_cluster,
           block_m, block_k, block_n, min_expected_block_sparsity)
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        _PLAN_CACHE_STATS["misses"] += 1
        plan = plan_matmul(
            m, k, n, lhs_sparsity=ls, rhs_sparsity=rs,
            lhs_cluster=lhs_cluster, rhs_cluster=rhs_cluster, dtype=dtype,
            block_m=block_m, block_k=block_k, block_n=block_n,
            min_expected_block_sparsity=min_expected_block_sparsity,
        )
        _PLAN_CACHE[key] = plan
    else:
        _PLAN_CACHE_STATS["hits"] += 1
    return plan


def bitmap_gated_plan(
    m: int, k: int, n: int, *, block_m: int, block_k: int, block_n: int,
) -> SkipPlan:
    """Cached gated-lhs plan for a GEMM whose lhs bitmap already exists.

    Used on the producer-fused path (ReLU writes the bitmap, the down
    projection consumes it): the gate side and tiling are dictated by the
    bitmap, so no operand-ordering search is needed -- only the memoised
    plan object, shared across every trace of the serving decode step.
    """
    key = ("gated-lhs", m, k, n, block_m, block_k, block_n)
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        _PLAN_CACHE_STATS["misses"] += 1
        plan = SkipPlan(
            gate="lhs", variant="gated",
            block_m=block_m, block_k=block_k, block_n=block_n,
            table_entries=-(-m // block_m) * -(-k // block_k),
        )
        _PLAN_CACHE[key] = plan
    else:
        _PLAN_CACHE_STATS["hits"] += 1
    return plan


def plan_cache_stats() -> dict:
    return dict(size=len(_PLAN_CACHE), **_PLAN_CACHE_STATS)


def plan_cache_clear() -> None:
    _PLAN_CACHE.clear()
    _PLAN_CACHE_STATS["hits"] = _PLAN_CACHE_STATS["misses"] = 0


# ------------------------------------------------------------- planner v2
# MLP-level planning: instead of planning the two GEMMs of an MLP
# independently, plan the pair as one unit and decide whether the fused
# megakernel (kernels/sparce_mlp.py) or the two-kernel path should serve
# it. The decision input is MEASURED per-layer block sparsity (EMA of the
# realized aux skip fractions), not an i.i.d. prior -- the serving engine
# feeds the tracker and replans when the bucketed estimate moves.

@dataclasses.dataclass(frozen=True)
class MlpPlan:
    """Skip schedule for one MLP y = act(x[M,K] @ w_in[K,F]) @ w_out[F,N]."""

    variant: str  # 'fused' | 'two_kernel' (GLU: 'unfused') | 'dense'
    block_m: int
    block_f: int  # bitmap granularity over the intermediate's F dim
    block_n: int  # down-projection n-tile (two-kernel path only)
    expected_block_sparsity: float = 0.0
    # Explainability: modeled HBM bytes per variant at the measured
    # sparsity, so `why this plan` is answerable from the plan itself.
    modeled_bytes: Tuple[Tuple[str, int], ...] = ()

    def modeled(self) -> dict:
        return dict(self.modeled_bytes)


def _fused_vmem_bytes(bm: int, bf: int, k: int, n: int, itemsize: int) -> int:
    """Working set of the fused kernel: x tile + w_in tile (x2 pipeline
    buffers), 2 a-tiles (f32), 2 w_out stripes, f32 accumulator, y tile."""
    return (
        2 * bm * k * itemsize
        + 2 * k * bf * itemsize
        + 2 * bm * bf * 4
        + 2 * bf * n * itemsize
        + bm * n * 4
        + bm * n * itemsize
    )


def plan_mlp(
    m: int,
    k: int,
    f: int,
    n: int,
    *,
    measured_block_sparsity: float = 0.0,
    dtype: str = "float32",
    block_m: Optional[int] = None,
    block_f: Optional[int] = None,
    block_n: Optional[int] = None,
    min_expected_block_sparsity: float = 0.02,
) -> MlpPlan:
    """Choose tiling + variant for one MLP from measured block sparsity.

    Search: block shapes from the MXU-aligned menu, constrained by the
    fused kernel's VMEM working set; variant = argmin of modeled HBM
    bytes (core.cost_model.mlp_hbm_bytes). The fused kernel needs K and N
    resident per row-tile, so very wide d_model falls back to the
    two-kernel path -- the plan records why via ``modeled_bytes``.
    """
    from repro.core import cost_model

    sub = _SUBLANE.get(dtype, 8)
    itemsize = 2 if dtype == "bfloat16" else 4
    s = min(max(float(measured_block_sparsity), 0.0), 1.0)

    bm_menu = [block_m] if block_m else [
        b for b in (sub, 2 * sub, 4 * sub, 8 * sub, 256) if b <= max(m, sub)
    ]
    bf_menu = [block_f] if block_f else [
        b for b in (128, 256, 512) if b <= max(f, 128)
    ]
    bn = block_n or _round_block(n, 256, _MXU_LANE)

    best = None  # (bytes, -tile_area, bm, bf) -> prefer bigger tiles on tie
    for bm in bm_menu:
        for bf in bf_menu:
            if _fused_vmem_bytes(bm, bf, k, n, itemsize) > _VMEM_BUDGET_BYTES:
                continue
            by = cost_model.mlp_hbm_bytes(
                m, k, f, n, block_sparsity=s, dtype_bytes=itemsize,
                block_m=bm,
            )["fused"]
            cand = (by, -(bm * bf), bm, bf)
            if best is None or cand < best:
                best = cand
    fused_ok = best is not None
    if fused_ok:
        _, _, bm, bf = best
    else:
        bm = block_m or _round_block(m, 64, sub)
        bf = block_f or 128

    by = cost_model.mlp_hbm_bytes(
        m, k, f, n, block_sparsity=s, dtype_bytes=itemsize, block_m=bm
    )
    if s < min_expected_block_sparsity:
        # No sparsity to exploit: the fused kernel still wins on HBM
        # round trips, but only when its working set fits.
        variant = "fused" if fused_ok else "dense"
    elif fused_ok and by["fused"] <= by["two_kernel"]:
        variant = "fused"
    else:
        variant = "two_kernel"
    return MlpPlan(
        variant=variant,
        block_m=bm,
        block_f=bf,
        block_n=bn,
        expected_block_sparsity=s,
        modeled_bytes=tuple(
            (kk, vv) for kk, vv in by.items() if isinstance(vv, int)
        ),
    )


def plan_mlp_cached(
    m: int,
    k: int,
    f: int,
    n: int,
    *,
    measured_block_sparsity: float = 0.0,
    dtype: str = "float32",
    block_m: Optional[int] = None,
    block_f: Optional[int] = None,
    block_n: Optional[int] = None,
    min_expected_block_sparsity: float = 0.02,
) -> MlpPlan:
    """Memoised :func:`plan_mlp`; sparsity bucketed as in plan_matmul_cached."""
    s = _bucket_sparsity(measured_block_sparsity)
    key = ("mlp", m, k, f, n, dtype, s, block_m, block_f, block_n,
           min_expected_block_sparsity)
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        _PLAN_CACHE_STATS["misses"] += 1
        plan = plan_mlp(
            m, k, f, n, measured_block_sparsity=s, dtype=dtype,
            block_m=block_m, block_f=block_f, block_n=block_n,
            min_expected_block_sparsity=min_expected_block_sparsity,
        )
        _PLAN_CACHE[key] = plan
    else:
        _PLAN_CACHE_STATS["hits"] += 1
    return plan


def _glu_fused_vmem_bytes(bm: int, bf: int, k: int, n: int,
                          itemsize: int) -> int:
    """Working set of the gated-GLU megakernel: x tile + w_gate tile (x2
    pipeline buffers each), 2 act(g) tiles (f32), 2 manually-DMA'd w_in
    stripes, 2 w_out stripes, f32 accumulator, y tile."""
    return (
        2 * bm * k * itemsize
        + 2 * k * bf * itemsize
        + 2 * bm * bf * 4
        + 2 * k * bf * itemsize
        + 2 * bf * n * itemsize
        + bm * n * 4
        + bm * n * itemsize
    )


def plan_glu_mlp(
    m: int,
    k: int,
    f: int,
    n: int,
    *,
    measured_block_sparsity: float = 0.0,
    dtype: str = "float32",
    block_m: Optional[int] = None,
    block_f: Optional[int] = None,
    block_n: Optional[int] = None,
    min_expected_block_sparsity: float = 0.02,
) -> MlpPlan:
    """Choose tiling + variant for one GLU MLP
    y = (act(x @ w_gate) * (x @ w_in)) @ w_out.

    Same shape as :func:`plan_mlp` but scored by the 3-matrix byte model
    (core.cost_model.glu_mlp_hbm_bytes) and constrained by the gated-GLU
    kernel's bigger VMEM working set (two weight-stripe buffers).
    Variants: 'fused' (megakernel, two-sided fetch skip), 'unfused'
    (gate-thresholded pipeline, compute skip only), 'dense'. Unlike the
    plain MLP, fused is NOT a free win at zero sparsity: its per-row-tile
    w_in stripe DMAs re-stream k*f bytes nm times, so at low measured
    sparsity and many row-tiles the planner honestly prefers the
    fallback -- ``modeled_bytes`` records why.
    """
    from repro.core import cost_model

    sub = _SUBLANE.get(dtype, 8)
    itemsize = 2 if dtype == "bfloat16" else 4
    s = min(max(float(measured_block_sparsity), 0.0), 1.0)

    bm_menu = [block_m] if block_m else [
        b for b in (sub, 2 * sub, 4 * sub, 8 * sub, 256) if b <= max(m, sub)
    ]
    bf_menu = [block_f] if block_f else [
        b for b in (128, 256, 512) if b <= max(f, 128)
    ]
    bn = block_n or _round_block(n, 256, _MXU_LANE)

    best = None  # (bytes, -tile_area, bm, bf) -> prefer bigger tiles on tie
    for bm in bm_menu:
        for bf in bf_menu:
            if _glu_fused_vmem_bytes(bm, bf, k, n, itemsize) > _VMEM_BUDGET_BYTES:
                continue
            by = cost_model.glu_mlp_hbm_bytes(
                m, k, f, n, block_sparsity=s, dtype_bytes=itemsize,
                block_m=bm,
            )["fused"]
            cand = (by, -(bm * bf), bm, bf)
            if best is None or cand < best:
                best = cand
    fused_ok = best is not None
    if fused_ok:
        _, _, bm, bf = best
    else:
        bm = block_m or _round_block(m, 64, sub)
        bf = block_f or 128

    by = cost_model.glu_mlp_hbm_bytes(
        m, k, f, n, block_sparsity=s, dtype_bytes=itemsize, block_m=bm
    )
    if fused_ok and by["fused"] <= by["unfused"]:
        variant = "fused"
    elif s >= min_expected_block_sparsity:
        variant = "unfused"
    else:
        # No sparsity to exploit and the megakernel doesn't fit/win:
        # the 6-round-trip unfused pipeline would be pure overhead.
        variant = "dense"
    return MlpPlan(
        variant=variant,
        block_m=bm,
        block_f=bf,
        block_n=bn,
        expected_block_sparsity=s,
        modeled_bytes=tuple(
            (kk, vv) for kk, vv in by.items() if isinstance(vv, int)
        ),
    )


def plan_glu_mlp_cached(
    m: int,
    k: int,
    f: int,
    n: int,
    *,
    measured_block_sparsity: float = 0.0,
    dtype: str = "float32",
    block_m: Optional[int] = None,
    block_f: Optional[int] = None,
    block_n: Optional[int] = None,
    min_expected_block_sparsity: float = 0.02,
) -> MlpPlan:
    """Memoised :func:`plan_glu_mlp`; bucketed like plan_mlp_cached."""
    s = _bucket_sparsity(measured_block_sparsity)
    key = ("glu_mlp", m, k, f, n, dtype, s, block_m, block_f, block_n,
           min_expected_block_sparsity)
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        _PLAN_CACHE_STATS["misses"] += 1
        plan = plan_glu_mlp(
            m, k, f, n, measured_block_sparsity=s, dtype=dtype,
            block_m=block_m, block_f=block_f, block_n=block_n,
            min_expected_block_sparsity=min_expected_block_sparsity,
        )
        _PLAN_CACHE[key] = plan
    else:
        _PLAN_CACHE_STATS["hits"] += 1
    return plan


def autotune_mlp_plan(
    m: int, k: int, f: int, n: int, *,
    measured_block_sparsity: float, dtype: str = "float32",
    sample_inputs=None, iters: int = 2,
) -> Tuple[MlpPlan, dict]:
    """Measuring autotuner: time the fused vs two-kernel candidates.

    The model-scored :func:`plan_mlp_cached` is the hot-path default (no
    arrays needed, pure trace-time); this entry point additionally RUNS
    both variants on ``sample_inputs`` (or synthetic ones at the measured
    sparsity) and returns the wall-clock winner plus the measurements, so
    deployments can validate the byte model against real timings. Results
    are cached process-wide like every other plan.
    """
    import timeit

    import jax
    import jax.numpy as jnp

    key = ("mlp-tuned", m, k, f, n, dtype, _bucket_sparsity(
        measured_block_sparsity))
    hit = _PLAN_CACHE.get(key)
    if hit is not None:
        _PLAN_CACHE_STATS["hits"] += 1
        return hit

    from repro.core import sparse_ops, sprf
    from repro.kernels import ops as kops

    plan = plan_mlp(
        m, k, f, n, measured_block_sparsity=measured_block_sparsity,
        dtype=dtype,
    )
    if sample_inputs is None:
        dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
        kx, k1, k2 = jax.random.split(jax.random.PRNGKey(0), 3)
        # Row-clustered zeros so the activated intermediate realizes the
        # measured block sparsity regardless of w_in.
        x = jnp.abs(sprf.random_sparse(
            kx, (m, k), measured_block_sparsity, dtype=dt,
            cluster=(plan.block_m, k)))
        w_in = jnp.abs(jax.random.normal(k1, (k, f), jnp.float32)).astype(dt)
        w_out = jax.random.normal(k2, (f, n), jnp.float32).astype(dt) * 0.05
    else:
        x, w_in, w_out = sample_inputs

    def run_fused():
        y, _ = kops.sparce_mlp_fused(
            x, w_in, w_out, block_m=plan.block_m, block_f=plan.block_f)
        return jax.block_until_ready(y)

    def run_two_kernel():
        # Same pipeline the fused-mode fallback serves (single impl).
        y, _ = sparse_ops.two_kernel_mlp(x, w_in, w_out, plan)
        return jax.block_until_ready(y)

    timings = {}
    for name, fn in (("fused", run_fused), ("two_kernel", run_two_kernel)):
        fn()  # compile / warm
        timings[name] = timeit.timeit(fn, number=iters) / iters
    winner = min(timings, key=timings.get)
    tuned = dataclasses.replace(plan, variant=winner)
    result = (tuned, timings)
    _PLAN_CACHE_STATS["misses"] += 1
    _PLAN_CACHE[key] = result
    return result


class SparsityEMA:
    """EMA tracker of measured per-layer block sparsity.

    The aux pytree's ``skip`` leaf ([skipped, total] tile-dots) is the
    measurement; the serving engine calls :meth:`update` with it after
    every decode tick and reads :meth:`bucketed` when (re)planning. The
    bucket is coarse (1/8) so a drifting estimate does not thrash the
    trace cache: a replan (and hence a retrace) happens only when the
    measured sparsity crosses a bucket edge.
    """

    BUCKETS = 8

    def __init__(self, alpha: float = 0.2):
        self.alpha = float(alpha)
        self.value: Optional[float] = None
        self.updates = 0

    def update(self, skipped: float, total: float) -> float:
        if total > 0:
            frac = min(max(skipped / total, 0.0), 1.0)
            self.value = (
                frac if self.value is None
                else self.alpha * frac + (1 - self.alpha) * self.value
            )
            self.updates += 1
        return self.value or 0.0

    def bucketed(self) -> float:
        v = self.value or 0.0
        return round(v * self.BUCKETS) / self.BUCKETS


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One GEMM-shaped layer for network-level analysis."""

    name: str
    m: int
    k: int
    n: int
    act_sparsity: float = 0.0  # dynamic (features / errors)
    weight_sparsity: float = 0.0  # static (pruned)
    flops: Optional[int] = None

    def gemm_flops(self) -> int:
        return self.flops if self.flops is not None else 2 * self.m * self.k * self.n


def analyze_network(
    layers: Sequence[LayerSpec], *, dtype: str = "float32",
    act_cluster: int = 8, weight_cluster: int = 64,
) -> dict:
    """Whole-network static analysis: one SkipPlan per layer + summary.

    The summary mirrors the paper's reporting: total SASA-style entries
    (it found 20 suffice because compute lives in a few BLAS kernels --
    here: a handful of distinct (M,K,N,block) plans), and the redundant-MAC
    fraction (Fig. 4 analogue, at word and at tile granularity).
    """
    plans = {}
    distinct = set()
    tot_flops = 0
    word_redundant = 0.0
    tile_redundant = 0.0
    for layer in layers:
        plan = plan_matmul(
            layer.m, layer.k, layer.n,
            lhs_sparsity=layer.act_sparsity,
            rhs_sparsity=layer.weight_sparsity,
            lhs_cluster=act_cluster,
            rhs_cluster=weight_cluster,
            dtype=dtype,
        )
        plans[layer.name] = plan
        distinct.add((plan.block_m, plan.block_k, plan.block_n, plan.gate))
        f = layer.gemm_flops()
        tot_flops += f
        word = 1.0 - (1.0 - layer.act_sparsity) * (1.0 - layer.weight_sparsity)
        word_redundant += f * word
        tile_redundant += f * plan.expected_block_sparsity
    return dict(
        plans=plans,
        distinct_plans=len(distinct),
        total_flops=tot_flops,
        word_redundant_frac=word_redundant / max(1, tot_flops),
        tile_redundant_frac=tile_redundant / max(1, tot_flops),
    )
