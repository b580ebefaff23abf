"""Sharding planner: DP/TP/EP/SP rules for every arch family.

Mesh axes: ``pod`` (cross-pod, DCN), ``data`` (in-pod DP), ``model`` (TP/EP).
The planner is divisibility-aware per tensor: a dim is sharded on 'model'
only when divisible (GSPMD tolerates uneven shards via padding, but even
sharding keeps collective sizes honest); otherwise that dim stays
replicated and the rest of the network still shards (e.g. smollm's 9
heads replicate while its d_ff=1536 shards 16-way).

Param rules match on the parameter's path leaf name; leading stack axes
(layer scan, zamba groups) are never sharded.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig

DATA_AXES = ("pod", "data")  # batch shards over both by default

SERVE_AXES = ("data", "model")  # serving mesh: slot DP x tensor TP


def _mesh_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.shape else 1


def _div(dim: int, mesh: Mesh, axis: str) -> bool:
    return dim % max(1, _mesh_size(mesh, axis)) == 0


# (regex on path-leaf, which trailing dim gets 'model')
# dim index is relative to the LAST ndims of the tensor (negative index).
_PARAM_RULES: Tuple[Tuple[str, Optional[int]], ...] = (
    (r"embed$", -2),          # (V, d) or (K, V, d): shard vocab
    (r"heads$", -1),          # audio heads (K, d, V): shard vocab
    (r"head$", -1),           # (d, V)
    (r"wq$|wk$|wv$|wuq$|wuk$|wuv$|wkr$", -1),
    (r"bq$|bk$|bv$", -1),
    (r"wo$", -2),
    (r"w_in$|w_gate$", -1),   # (d, ff) / (E, d, ff)
    (r"w_out$", -2),          # (ff, d) / (E, ff, d)
    (r"router$", None),
    (r"in_proj$", -1),        # ssm (d, d_in_proj)
    (r"out_proj$", -2),       # ssm (d_in, d)
    (r"conv_w$|conv_b$", -1),
    (r"dt_bias$|A_log$|D$", -1),
    (r"scale$", None),        # norms
    (r"wdq$|wdkv$", -1),
)

_EXPERT_LEAF = re.compile(r"(w_in|w_gate|w_out)$")


def _leaf_name(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(p.name)
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_spec(path, leaf, mesh: Mesh) -> P:
    """PartitionSpec for one parameter."""
    name = _leaf_name(path)
    ndim = leaf.ndim
    spec = [None] * ndim
    # Expert tensors (path .../moe/w_*): (..., E, d, ff): shard E on model
    # (EP) when divisible, else fall through to TP on the trailing dim.
    # The 'moe/' requirement keeps layer-stacked dense MLPs (also ndim>=3)
    # on the TP rules.
    if (_EXPERT_LEAF.search(name) and "moe/" in name and "shared" not in name
            and ndim >= 3):
        e_dim = ndim - 3
        if _div(leaf.shape[e_dim], mesh, "model"):
            spec[e_dim] = "model"
            return P(*spec)
    for pat, dim in _PARAM_RULES:
        if re.search(pat, name):
            if dim is None:
                return P(*spec)
            d = ndim + dim
            if 0 <= d < ndim and _div(leaf.shape[d], mesh, "model"):
                spec[d] = "model"
            return P(*spec)
    return P(*spec)  # default: replicated


def param_specs(params: Any, mesh: Mesh, profile: str = "tp") -> Any:
    """profile='tp': the rule table above. profile='dp': replicate all
    params and give the batch every mesh axis -- right for models whose
    per-layer GEMMs are too small to shard (e.g. smollm on 256 chips,
    where TP tiles of a 576x1536 matmul underfill the MXU and the 9-head
    attention forces gathers; see EXPERIMENTS.md §Perf sm-2)."""
    if profile == "dp":
        return jax.tree_util.tree_map(
            lambda x: P(*([None] * x.ndim)), params
        )
    return jax.tree_util.tree_map_with_path(
        lambda p, x: param_spec(p, x, mesh), params
    )


def param_shardings(params: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(params, mesh)
    )


# ----------------------------------------------------------- batch / cache
def batch_spec(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh,
               batch: Any, profile: str = "tp") -> Any:
    """Specs for the input batch pytree: shard batch dim over (pod, data),
    or over EVERY mesh axis for profile='dp'."""
    batch_axes = DATA_AXES + (("model",) if profile == "dp" else ())
    dp = 1
    for a in batch_axes:
        dp *= _mesh_size(mesh, a)

    def one(path, leaf):
        b = leaf.shape[0]
        axes: Tuple = tuple(a for a in batch_axes if a in mesh.shape)
        if b % dp == 0 and b > 0:
            return P(axes, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(one, batch)


def cache_spec(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh,
               caches: Any) -> Any:
    """KV/SSM cache specs. Caches are stacked over layers (leading axis).

    Batch-shardable -> shard batch dim (axis 1). long_500k (batch 1) ->
    shard the sequence axis of attention caches on 'data' (SP) and the
    head axis of SSM states on 'model'.
    """
    dp = _mesh_size(mesh, "pod") * _mesh_size(mesh, "data")
    data_axes = tuple(a for a in DATA_AXES if a in mesh.shape)

    def one(path, leaf):
        name = _leaf_name(path)
        spec = [None] * leaf.ndim
        if leaf.ndim <= 1:  # legacy scalar lengths per layer
            return P(*spec)
        if name.endswith("length"):  # (layers, B) per-slot lengths
            if shape.global_batch % dp == 0 and leaf.shape[-1] == shape.global_batch:
                spec[-1] = data_axes
            return P(*spec)
        # leading dim(s) are layer stacks; find batch dim = first dim
        # whose size == global batch.
        b_dim = None
        for i, s in enumerate(leaf.shape):
            if s == shape.global_batch and i >= 1:
                b_dim = i
                break
        if b_dim is not None and shape.global_batch % dp == 0:
            spec[b_dim] = data_axes
            # also TP-shard kv-heads / ssm heads when present
            if "k" == name.split("/")[-1] or "v" == name.split("/")[-1]:
                if leaf.ndim >= b_dim + 3 and _div(
                    leaf.shape[b_dim + 2], mesh, "model"
                ):
                    spec[b_dim + 2] = "model"
            if (name.split("/")[-1] == "h" and leaf.ndim >= b_dim + 2
                    and _div(leaf.shape[b_dim + 1], mesh, "model")):
                spec[b_dim + 1] = "model"
            return P(*spec)
        # batch too small: SP on the sequence axis (attention caches) or
        # TP on heads (ssm states).
        if name.endswith("/k") or name.endswith("/v"):
            if leaf.ndim >= 3 and _div(leaf.shape[2], mesh, "data"):
                spec[2] = "data"
            if leaf.ndim >= 4 and _div(leaf.shape[3], mesh, "model"):
                spec[3] = "model"
            return P(*spec)
        if name.endswith("/h") and leaf.ndim >= 3:
            if _div(leaf.shape[2], mesh, "model"):
                spec[2] = "model"
            return P(*spec)
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, caches)


# ------------------------------------------------------------ SPMD serving
# The serving engine (runtime/server.py) runs its prefill/decode steps
# under shard_map on a (data, model) mesh: slots (and their per-slot
# ``length`` vectors + paged-pool blocks) shard over 'data', attention
# heads / FFN columns over 'model' (Megatron-style: column-shard the
# in-projections, row-shard the out-projections, ONE psum per residual
# write). The paper mapping: skip state is per-core (SpRF/SASA), so the
# null-block and index-map-clamp tricks run SHARD-LOCAL -- every shard
# keeps its own null block 0 and clamps against its own table slice --
# and the psum'd skip counters are the aggregated SpRF telemetry.

# Column-sharded over 'model' on the trailing dim: all Q/K/V in-projections
# (head-major column layout, so a 1/tp column slice IS a head slice), the
# MLA up-projections, and both GLU in-projections.
_SERVE_COL = re.compile(
    r"(wq|wk|wv|bq|bk|bv|wuq|wuk|wuv|w_in|w_gate)$"
)
# Row-sharded (dim -2): the out-projections whose contraction dim is the
# sharded one -- their dot yields a partial sum, closed by one psum.
_SERVE_ROW = re.compile(r"(wo|w_out)$")


def serve_mesh(shape: Tuple[int, int]) -> Mesh:
    """A (data, model) serving mesh over the first dp*tp local devices.

    CPU hosts fake the device count the way ``launch/dryrun.py`` does:
    set ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` BEFORE
    the first jax import (tests/conftest.py arranges this for the suite).
    """
    dp, tp = int(shape[0]), int(shape[1])
    if dp < 1 or tp < 1:
        raise ValueError(f"serve mesh axes must be >= 1, got {shape}")
    n = dp * tp
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"serve mesh {shape} needs {n} devices, found {len(devices)}"
            " -- on CPU set XLA_FLAGS=--xla_force_host_platform_device_"
            "count=<n> before the first jax import (see launch/dryrun.py)"
        )
    # Auto axes: the engine's plain GSPMD pool ops (insert/copy/prefix
    # gather) leave the partitioning to the compiler.
    return jax.make_mesh(
        (dp, tp), SERVE_AXES, devices=devices[:n],
        axis_types=(AxisType.Auto,) * 2,
    )


def serve_local_config(cfg: ArchConfig, tp: int) -> ArchConfig:
    """The per-shard view of ``cfg`` a shard_map body runs unchanged:
    1/tp of the heads, kv heads and FFN columns, with ``tp_axis`` set so
    attention/MLP close their row-sharded out-projections with a psum."""
    if cfg.num_heads % tp or cfg.num_kv_heads % tp or cfg.d_ff % tp:
        raise ValueError(
            f"tensor-parallel degree {tp} must divide num_heads="
            f"{cfg.num_heads}, num_kv_heads={cfg.num_kv_heads} and "
            f"d_ff={cfg.d_ff}"
        )
    return dataclasses.replace(
        cfg,
        num_heads=cfg.num_heads // tp,
        num_kv_heads=cfg.num_kv_heads // tp,
        d_ff=cfg.d_ff // tp,
        tp_axis="model",
    )


def serve_param_spec(path, leaf, mesh: Mesh) -> P:
    """Serving-TP spec for one parameter (strict: a matched dim that does
    not divide raises instead of silently replicating, because the
    shard_map body's local config assumes exact 1/tp slices)."""
    name = _leaf_name(path)
    ndim = leaf.ndim
    tp = _mesh_size(mesh, "model")
    for pat, dim in ((_SERVE_COL, -1), (_SERVE_ROW, -2)):
        if pat.search(name):
            d = ndim + dim
            if leaf.shape[d] % tp:
                raise ValueError(
                    f"serving TP: param {name!r} dim {dim} "
                    f"({leaf.shape[d]}) does not divide over "
                    f"{tp} model shards"
                )
            spec = [None] * ndim
            spec[d] = "model"
            return P(*spec)
    # Everything else (embed/head, norms, MLA down-projections wdq/wdkv/
    # wkr) is replicated: decode-time logits and the latent KV cache must
    # be whole on every shard.
    return P(*([None] * ndim))


def serve_param_specs(params: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda p, x: serve_param_spec(p, x, mesh), params
    )


def serve_param_shardings(params: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), serve_param_specs(params, mesh)
    )


def serve_paged_cache_specs(cfg: ArchConfig) -> Any:
    """Spec tree (pytree of PartitionSpecs matching the paged caches from
    ``model.init_paged_caches``) for the serving pool: blocks shard over
    'data' (each data shard owns its own sub-pool INCLUDING its own null
    block 0), KV heads over 'model' (GQA; the MLA latent/rope pools are
    'model'-replicated since wdkv/wkr are), and the per-slot ``length``
    vector over 'data' -- the existing ``cache_spec`` convention."""
    from repro.models.attention import PagedKVCache  # noqa: PLC0415

    if cfg.mla is not None:
        kv = P(None, "data", None, None)  # (L, blocks, bs, lat/rope)
    else:
        kv = P(None, "data", None, "model", None)  # (L, nb, bs, KV, hd)
    return {"stack": PagedKVCache(kv, kv, P(None, "data"))}


def serve_prefill_cache_specs(cfg: ArchConfig) -> Any:
    """Spec tree for the batch=1 contiguous scratch caches a prefill
    shard_map body returns: KV heads over 'model' (GQA), everything else
    replicated (the prefix rows themselves are identical per data shard
    -- prefill is replicated over 'data')."""
    from repro.models.attention import KVCache  # noqa: PLC0415

    if cfg.mla is not None:
        kv = P()
    else:
        kv = P(None, None, None, "model", None)  # (L, 1, S, KV, hd)
    return {"stack": KVCache(kv, kv, P())}


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """Sharding constraint under the ambient mesh; a no-op without one
    (single-device model code shares this call site)."""
    if current_mesh() is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def current_mesh() -> Optional[Mesh]:
    """The ambient `with mesh:` context mesh, or None.

    Model code (MoE expert parallelism) consults this at trace time to
    decide whether the shard_map fast path is available."""
    from jax._src import mesh as mesh_src  # noqa: PLC0415

    m = mesh_src.thread_resources.env.physical_mesh
    return None if m.empty else m
