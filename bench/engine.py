"""The system under test, built from a configuration file.

A configuration file (``bench/configs/<config>.json``) holds the
published config keys at its top level (``num_hidden_layers``,
``hidden_size``, ...), the architecture's name in ``repro.configs``
(``arch``), the serve options (``serve``: ``ServeConfig`` fields, the
SparCE settings and the mesh) and the limits of the comparison with the
reference (``correct``). This module maps it onto the program's own
types and checks that the program's architecture agrees with the
published keys before anything runs.
"""
from __future__ import annotations

import dataclasses

# Published key -> the program's ArchConfig field.
ARCH_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def arch_config(c: dict):
    """The program's ArchConfig for configuration ``c``: the named
    architecture with every size, the norm epsilon, the rotary base and
    the dtype taken from the file. Raises where the architecture computes
    something else than the file's Llama-style decoder."""
    from repro.configs import get_config

    base = get_config(c["arch"])
    kw = {f: c[k] for k, f in ARCH_KEYS.items()}
    kw["head_dim"] = int(c.get("head_dim") or 0)
    kw["dtype"] = c["torch_dtype"]
    cfg = dataclasses.replace(base, **kw)
    if (c["hidden_act"] != cfg.mlp_act or cfg.qkv_bias
            or cfg.family != "dense" or cfg.frontend is not None):
        raise ValueError(
            f"repro config {c['arch']!r} is not a dense Llama-style "
            f"decoder with {c['hidden_act']} MLPs: family {cfg.family}, "
            f"act {cfg.mlp_act}, qkv_bias {cfg.qkv_bias}")
    return cfg


def serve_config(c: dict):
    """The ServeConfig of configuration ``c``."""
    from repro.core.sparse_ops import SparsityConfig
    from repro.runtime.server import ServeConfig

    s = dict(c["serve"])
    sp = s.pop("sparsity", None)
    mesh = s.pop("mesh", None)
    return ServeConfig(
        **s, mesh=tuple(mesh) if mesh else None,
        sparsity=SparsityConfig(enabled=True, **sp) if sp else None)


def param_shardings(c: dict, abstract_params):
    """Where each weight lives: sharded as the serving mesh keeps it, or
    None on one chip."""
    mesh = c["serve"].get("mesh")
    if not mesh:
        return None
    from repro.parallel import sharding as shard_lib

    return shard_lib.serve_param_shardings(
        abstract_params, shard_lib.serve_mesh(tuple(mesh)))


def check_layout(cfg, abstract_params) -> None:
    """The weight tree has the program's own layout, shapes and types."""
    import jax

    from repro.models import model as model_lib

    want = jax.eval_shape(
        lambda k: model_lib.init_params(cfg, k), jax.random.PRNGKey(0))
    got_s = jax.tree_util.tree_structure(abstract_params)
    want_s = jax.tree_util.tree_structure(want)
    if got_s != want_s:
        raise ValueError(f"weight tree {got_s} != program's {want_s}")
    for g, w in zip(jax.tree_util.tree_leaves(abstract_params),
                    jax.tree_util.tree_leaves(want)):
        if (g.shape, g.dtype) != (w.shape, w.dtype):
            raise ValueError(f"weight {g.shape} {g.dtype} != program's "
                             f"{w.shape} {w.dtype}")
