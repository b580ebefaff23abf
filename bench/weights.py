"""Random weights from a seed, made on the device in one jitted call.

The tree is laid out the way the serving engine takes its parameters
(``embed``, ``head`` unless tied, ``final_norm``, and a ``stack`` with
every layer's weights stacked on a leading axis); its values come from
here alone, so the plain reference can make the same weights again from
the same seed. Scales follow the usual fan-in initialisation; norm
weights are ones, as published models start.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Fixed index of every leaf: its random stream is fold_in(key, index).
_LEAVES = ("embed", "head", "wq", "wk", "wv", "wo", "w_gate", "w_in",
           "w_out")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (wider than 32 bits
    included)."""
    return jax.random.PRNGKey(
        int(np.random.SeedSequence(seed).generate_state(1)[0]))


def shapes(m: dict) -> dict:
    """Leaf shapes of the tree, from the published config keys."""
    L, d = m["num_hidden_layers"], m["hidden_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = int(m.get("head_dim") or d // h)
    f, v = m["intermediate_size"], m["vocab_size"]
    out = {"embed": (v, d), "wq": (L, d, h * hd), "wk": (L, d, kv * hd),
           "wv": (L, d, kv * hd), "wo": (L, h * hd, d),
           "w_gate": (L, d, f), "w_in": (L, d, f), "w_out": (L, f, d)}
    if not m.get("tie_word_embeddings"):
        out["head"] = (d, v)
    return out


def init(m: dict, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """The parameter tree (traceable; wrap in ``jax.jit``)."""
    L, d = m["num_hidden_layers"], m["hidden_size"]
    shp = shapes(m)

    def leaf(name):
        shape = shp[name]
        scale = 0.02 if name == "embed" else shape[-2] ** -0.5
        k = jax.random.fold_in(key, _LEAVES.index(name))
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    params = {
        "embed": leaf("embed"),
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "stack": {
            "attn_norm": {"scale": jnp.ones((L, d), jnp.float32)},
            "mlp_norm": {"scale": jnp.ones((L, d), jnp.float32)},
            "attn": {n: leaf(n) for n in ("wq", "wk", "wv", "wo")},
            "mlp": {n: leaf(n) for n in ("w_gate", "w_in", "w_out")},
        },
    }
    if "head" in shp:
        params["head"] = leaf("head")
    return params


def make(m: dict, seed: int, shardings=None) -> dict:
    """The tree on the device(s); ``shardings`` (a matching tree of
    shardings) places each leaf's shards where they are made."""
    fn = jax.jit(lambda k: init(m, k), out_shardings=shardings)
    return fn(seed_key(seed))


def abstract(m: dict) -> dict:
    return jax.eval_shape(lambda k: init(m, k), jax.random.PRNGKey(0))
