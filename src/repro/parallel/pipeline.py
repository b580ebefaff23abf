"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

Rationale (DESIGN.md §3): inter-pod links are DCN, ~10x slower than ICI.
Default multi-pod mode treats ``pod`` as extra DP, which all-reduces
O(params) bytes over DCN every step. Pipeline mode instead maps pods to
stages: cross-pod traffic becomes O(microbatch activations) via
``ppermute``, the right trade for large models on slow inter-pod links.

Implementation: shard_map over 'pod'; the stacked layer params carry a
leading stage axis sharded on 'pod'; micro-batches flow through a
ppermute ring with the canonical (n_micro + n_stages - 1)-step schedule.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "pod",
    n_micro: int | None = None,
    extra_specs: P | None = None,
):
    """Run ``x`` through n_stages pipeline stages.

    stage_params: pytree with leading stage axis (== mesh.shape[axis]).
    x: (batch, ...) -- split into ``n_micro`` micro-batches on the batch dim.
    stage_fn(params_for_stage, micro) -> micro (same shape).
    Returns y with x's shape.
    """
    n_stages = mesh.shape[axis]
    n_micro = n_micro or n_stages
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro
    micros = x.reshape((n_micro, mb) + x.shape[1:])

    p_params = jax.tree_util.tree_map(lambda _: P(axis), stage_params)

    def spmd(params, micros):
        # params: local stage slice (leading axis 1); micros: full (replicated)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        steps = n_micro + n_stages - 1

        def body(carry, t):
            buf, outs = carry  # buf: (mb, ...) activation entering this stage
            # Stage 0 injects micro-batch t; others use the ring buffer.
            inject = jnp.where(t < n_micro, t, 0)
            new_in = jnp.where(
                stage == 0,
                micros[inject],
                buf,
            )
            h = stage_fn(params, new_in)
            # Emit: last stage stores finished micro t - (n_stages - 1).
            out_idx = t - (n_stages - 1)
            valid = jnp.logical_and(out_idx >= 0, stage == n_stages - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_slice(
                    o, h[None].astype(o.dtype), (jnp.maximum(out_idx, 0),)
                    + (0,) * h.ndim,
                ),
                lambda o: o,
                outs,
            )
            # Ring handoff: stage s -> s+1 (last stage's send is ignored).
            nxt = jax.lax.ppermute(
                h, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (nxt, outs), None

        buf0 = jnp.zeros_like(micros[0])
        outs0 = jnp.zeros_like(micros)
        (_, outs), _ = jax.lax.scan(
            body, (buf0, outs0), jnp.arange(steps)
        )
        # Broadcast results from the last stage to all pods (ppermute is
        # a bijection, so a one-to-many broadcast uses all_gather+index).
        if n_stages > 1:
            outs = jax.lax.all_gather(outs, axis)[n_stages - 1]
        return outs

    out = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(p_params, extra_specs or P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, micros)
    return out.reshape(x.shape)
