"""Mixture-of-Experts with sort-based (grouped-GEMM style) dispatch.

Dispatch: top-k routing -> stable sort by expert id -> scatter into a
static (E, C, d) buffer -> per-expert GEMMs -> weighted scatter-add back.
Expert weights shard on the 'model' mesh axis (EP); tokens shard on
'data', so GSPMD inserts the all-to-all at the buffer resharding point.

SparCE tie-in (DESIGN.md §Arch-applicability): the (E, C) buffer is the
paper's dynamic sparsity made structural -- every slot beyond an expert's
actual load is an all-zero row, and the dispatch mask IS the tile bitmap.
``slot_occupancy`` is returned so benchmarks can account the skippable
fraction, and the expert GEMM can run through the gated kernel
(benchmarks/fig_moe) exactly like a feature-sparse GEMM.

Semantics note: capacity-factor dropping makes outputs BATCH-DEPENDENT
(an assignment dropped in a 12-token pass survives a 1-token decode pass).
Decode==forward consistency holds exactly only in the drop-free regime --
see tests/test_server.py. The EP path's per-shard capacity differs from
the global path only under overflow, tested equivalently.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import modules as nn
from repro.parallel.sharding import constrain, current_mesh
from jax.sharding import PartitionSpec as P


def moe_init(key, cfg: ArchConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    de = m.d_expert or cfg.d_ff
    ks = nn.split_keys(key, 5)
    p = {
        "router": nn.dense_init(ks[0], d, m.num_experts, dtype, scale=0.02),
        "w_in": (
            jax.random.normal(ks[1], (m.num_experts, d, de), jnp.float32)
            * d**-0.5
        ).astype(dtype),
        "w_gate": (
            jax.random.normal(ks[2], (m.num_experts, d, de), jnp.float32)
            * d**-0.5
        ).astype(dtype),
        "w_out": (
            jax.random.normal(ks[3], (m.num_experts, de, d), jnp.float32)
            * de**-0.5
        ).astype(dtype),
    }
    if m.n_shared_experts:
        ff_sh = de * m.n_shared_experts
        kss = nn.split_keys(ks[4], 3)
        p["shared"] = {
            "w_in": nn.dense_init(kss[0], d, ff_sh, dtype),
            "w_gate": nn.dense_init(kss[1], d, ff_sh, dtype),
            "w_out": nn.dense_init(kss[2], ff_sh, d, dtype),
        }
    return p


def capacity(num_tokens: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    c = int(num_tokens * m.top_k * m.capacity_factor / m.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to sublane multiple


def moe_forward(
    params, x: jax.Array, cfg: ArchConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss, slot_sparsity).

    Dispatches to the shard_map expert-parallel path when an ambient mesh
    makes it legal (model axis divides num_experts, data axes divide the
    batch); otherwise the global-einsum path below (single device, tests,
    uneven configs like qwen2-moe's 60 experts).
    """
    mesh = current_mesh()
    if mesh is not None and "model" in mesh.shape:
        m_sz = mesh.shape["model"]
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        d_sz = 1
        for a in data_axes:
            d_sz *= mesh.shape[a]
        if (cfg.moe.num_experts % m_sz == 0 and x.shape[0] % d_sz == 0
                and m_sz > 1):
            return _moe_forward_ep(params, x, cfg, mesh, data_axes)
    return _moe_forward_global(params, x, cfg)


def _moe_forward_global(
    params, x: jax.Array, cfg: ArchConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Reference/global path: sort + scatter into an (E, C, d) buffer."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = m.num_experts, m.top_k
    C = capacity(T, cfg)
    xf = x.reshape(T, d)

    logits = jnp.dot(xf.astype(jnp.float32), params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gates, idx = jax.lax.top_k(probs, K)  # (T, K)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # Load-balancing aux loss (Switch/GShard form).
    me = jnp.mean(probs, axis=0)  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0
    )
    aux = E * jnp.sum(me * ce) * m.router_aux_weight

    # ---- sort-based dispatch ----
    flat_e = idx.reshape(T * K)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_g = gates.reshape(T * K)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    st = flat_t[order]
    sg = flat_g[order]
    # Position of each assignment within its expert segment.
    first = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(T * K, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = pos < C
    slot = jnp.where(keep, se * C + pos, E * C)  # OOB -> dropped by scatter

    buf = jnp.zeros((E * C, d), x.dtype).at[slot].set(
        xf[st], mode="drop"
    ).reshape(E, C, d)
    # EP: pin the dispatch buffer to the expert axis so the grouped GEMMs
    # run expert-parallel (GSPMD inserts ONE all-to-all at this reshard
    # instead of all-gathering the buffer and replicating expert compute:
    # measured 11.7x extra FLOPs + 57TB/device collectives without it --
    # see EXPERIMENTS.md §Perf iteration ds-1).
    buf = constrain(buf, P("model", None, None))

    # ---- expert GEMMs (grouped) ----
    h = jnp.einsum("ecd,edf->ecf", buf, params["w_in"])
    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    a = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    a = constrain(a, P("model", None, None))
    ye = jnp.einsum("ecf,efd->ecd", a, params["w_out"])
    ye = constrain(ye, P("model", None, None)).reshape(E * C, d)

    # ---- combine ----
    gathered = jnp.where(
        keep[:, None], ye[jnp.minimum(slot, E * C - 1)], 0.0
    ) * sg[:, None].astype(ye.dtype)
    y = jnp.zeros((T, d), x.dtype).at[st].add(gathered.astype(x.dtype))

    if m.n_shared_experts:
        sh = params["shared"]
        hs = jnp.dot(xf, sh["w_in"])
        gs = jax.nn.silu(jnp.dot(xf, sh["w_gate"]).astype(jnp.float32))
        y = y + jnp.dot(gs.astype(hs.dtype) * hs, sh["w_out"])

    # Structural-sparsity accounting: fraction of (E*C) slots unoccupied
    # == the tile-bitmap sparsity a SparCE-gated expert GEMM would skip.
    occupancy = jnp.sum(keep.astype(jnp.float32)) / (E * C)
    return y.reshape(B, S, d), aux, 1.0 - occupancy


# ------------------------------------------------- expert-parallel (EP)
def _moe_forward_ep(
    params, x: jax.Array, cfg: ArchConfig, mesh, data_axes
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """shard_map EP path (§Perf iteration ds-2).

    Key observation: under the standard activation layout the token shard
    is REPLICATED across the 'model' axis, so each (token-shard i, expert
    -shard j) device can route its own tokens to its own experts with NO
    dispatch communication at all. The only collective is ONE psum of the
    per-device partial outputs over 'model' (+ the tiny aux-loss means).
    The GSPMD global-scatter formulation instead all-reduces the full
    (E, C, d) dispatch buffer -- measured 57 TB/device/step on
    deepseek-v3 train_4k (see EXPERIMENTS.md).

    Capacity semantics: C is per (expert, token-shard) -- GShard 'local
    groups'. Per-shard overflow drops differ slightly from the global
    formulation; both are capacity-factor-bounded.
    """
    m = cfg.moe
    B, S, d = x.shape
    M = mesh.shape["model"]
    E, K = m.num_experts, m.top_k
    E_loc = E // M
    d_sz = 1
    for a in data_axes:
        d_sz *= mesh.shape[a]
    T_loc = (B // d_sz) * S
    C = capacity(T_loc, cfg)
    de = m.d_expert or cfg.d_ff
    shared_scale = 1.0  # set below when a replicated shared expert psums

    def body(router, w_in, w_gate, w_out, shared, xs):
        # xs: (B_loc, S, d); expert weights: (E_loc, d, de)
        xf = xs.reshape(T_loc, d)
        j = jax.lax.axis_index("model")
        e0 = j * E_loc

        logits = jnp.dot(xf.astype(jnp.float32),
                         router.astype(jnp.float32))  # (T_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, K)
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

        # aux loss over ALL tokens (psum-mean over the data axes)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(
            jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1),
            axis=0)
        for a in data_axes:
            me = jax.lax.pmean(me, a)
            ce = jax.lax.pmean(ce, a)
        aux = E * jnp.sum(me * ce) * m.router_aux_weight

        # local dispatch: keep only assignments to OUR expert shard
        flat_e = idx.reshape(T_loc * K) - e0
        flat_t = jnp.repeat(jnp.arange(T_loc, dtype=jnp.int32), K)
        flat_g = gates.reshape(T_loc * K)
        local = jnp.logical_and(flat_e >= 0, flat_e < E_loc)
        key_e = jnp.where(local, flat_e, E_loc)  # non-local sorts last
        order = jnp.argsort(key_e, stable=True)
        se, st, sg = key_e[order], flat_t[order], flat_g[order]
        first = jnp.searchsorted(se, se, side="left")
        pos = jnp.arange(T_loc * K, dtype=jnp.int32) - first.astype(jnp.int32)
        keep = jnp.logical_and(se < E_loc, pos < C)
        slot = jnp.where(keep, se * C + pos, E_loc * C)

        buf = jnp.zeros((E_loc * C, d), xs.dtype).at[slot].set(
            xf[st], mode="drop").reshape(E_loc, C, d)

        h = jnp.einsum("ecd,edf->ecf", buf, w_in)
        g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
        a_act = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
        ye = jnp.einsum("ecf,efd->ecd", a_act, w_out).reshape(E_loc * C, d)

        gathered = jnp.where(
            keep[:, None], ye[jnp.minimum(slot, E_loc * C - 1)], 0.0
        ) * sg[:, None].astype(ye.dtype)
        y = jnp.zeros((T_loc, d), xs.dtype).at[st].add(
            gathered.astype(xs.dtype))

        if m.n_shared_experts:
            # shared-expert FFN hidden dim sharded over 'model':
            # partial products fold into the same psum as the routed y.
            # (if replication was forced, scale so psum sums to one copy)
            hs = jnp.dot(xf, shared["w_in"])
            gs = jax.nn.silu(jnp.dot(xf, shared["w_gate"]).astype(jnp.float32))
            ys = jnp.dot(gs.astype(hs.dtype) * hs, shared["w_out"])
            y = y + ys * jnp.asarray(shared_scale, ys.dtype)

        y = jax.lax.psum(y, "model")

        occ = jnp.sum(keep.astype(jnp.float32)) / (E_loc * C)
        occ = jax.lax.pmean(occ, "model")
        for a in data_axes:
            occ = jax.lax.pmean(occ, a)
        return y.reshape(xs.shape), aux, 1.0 - occ

    shared = params.get("shared")
    if shared is not None:
        ff_sh = shared["w_in"].shape[1]
        sh_div = ff_sh % M == 0
        shared_spec = {
            "w_in": P(None, "model" if sh_div else None),
            "w_gate": P(None, "model" if sh_div else None),
            "w_out": P("model" if sh_div else None, None),
        }
        if not sh_div:
            # replicated shared expert: scale partials so the closing
            # psum over 'model' sums to exactly one copy.
            shared_scale = 1.0 / M
    else:
        shared = {"w_in": jnp.zeros((d, 8), x.dtype),
                  "w_gate": jnp.zeros((d, 8), x.dtype),
                  "w_out": jnp.zeros((8, d), x.dtype)}
        shared_spec = {"w_in": P(None, None), "w_gate": P(None, None),
                       "w_out": P(None, None)}

    in_specs = (
        P(None, None),  # router replicated
        P("model", None, None), P("model", None, None),
        P("model", None, None),
        shared_spec,
        P(data_axes, None, None),
    )
    out_specs = (P(data_axes, None, None), P(), P())
    y, aux, slot_sparsity = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(params["router"], params["w_in"], params["w_gate"], params["w_out"],
      shared, x)
    return y, aux, slot_sparsity
