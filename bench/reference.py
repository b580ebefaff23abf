"""Plain reference forward of a Llama-style decoder, and its control.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision
(on a TPU a float32 matmul otherwise runs in bf16 passes): RMSNorm,
rotary embedding on both halves of each head (``rotate_half``),
grouped-query causal attention, SiLU-gated MLP, final norm, logits over
the whole vocabulary. No cache, no kernels, no batching: one sequence,
padded at the end (causal attention keeps real positions exact).

It reads the published config keys and the weight tree of
``bench/weights.py``; it uses nothing of the program under test.

``control=True`` also runs the same forward with every matmul operand
rounded to float8 (e4m3, one scale per row of the left operand and per
column of the right): the precision one step below the bfloat16 that
the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, low):
    """a @ b over the last axis of ``a`` and the first of ``b``."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if low:
        a, b = _fp8(a, -1), _fp8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, heads, hd); rotation by position on (x1, x2) halves."""
    s, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, m, low):
    s = x.shape[0]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = int(m.get("head_dim") or m["hidden_size"] // h)
    eps = m["rms_norm_eps"]
    a = _rmsnorm(x, p["attn_norm"]["scale"], eps)
    q = _rope(_mm(a, p["attn"]["wq"], low).reshape(s, h, hd), m["rope_theta"])
    k = _rope(_mm(a, p["attn"]["wk"], low).reshape(s, kv, hd), m["rope_theta"])
    v = _mm(a, p["attn"]["wv"], low).reshape(s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)  # query head i reads kv head i // g
    v = jnp.repeat(v, h // kv, axis=1)
    if low:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    if low:
        pr = _fp8(pr, -1)
    o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST)
    x = x + _mm(o.reshape(s, h * hd), p["attn"]["wo"], low)
    a = _rmsnorm(x, p["mlp_norm"]["scale"], eps)
    g = _mm(a, p["mlp"]["w_gate"], low)
    u = _mm(a, p["mlp"]["w_in"], low)
    return x + _mm(jax.nn.silu(g) * u, p["mlp"]["w_out"], low)


def _logits(params, m, tokens, rows, low):
    """Logits at positions ``rows`` of the sequence ``tokens``."""
    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(lambda c, p: (_layer(c, p, m, low), None),
                        x, params["stack"])
    x = _rmsnorm(x[rows], params["final_norm"]["scale"], m["rms_norm_eps"])
    head = (params["embed"].T if m.get("tie_word_embeddings")
            else params["head"])
    return _mm(x, head, low)


@functools.partial(jax.jit, static_argnames=("m_items", "control"))
def _gaps(params, tokens, rows, served, *, m_items, control):
    m = dict(m_items)
    ref = _logits(params, m, tokens, rows, False)
    best = jnp.max(ref, -1)
    gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    if not control:
        return gap, jnp.zeros_like(gap)
    top = jnp.argmax(_logits(params, m, tokens, rows, True), -1)
    return gap, best - jnp.take_along_axis(ref, top[:, None], -1)[:, 0]


def _frozen(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool, str))))


def logit_gaps(params, m: dict, prompt, served, *, pad_len: int,
               pad_out: int, control: bool = False):
    """For each served token: how far its reference logit lies below the
    reference's best at that position (0 where they agree). With
    ``control``, also the same gap for the token the float8 forward puts
    first. Returns two NumPy arrays of ``len(served)``."""
    import numpy as np

    prompt, served = np.asarray(prompt), np.asarray(served)
    n, s = len(served), len(prompt)
    if s + n - 1 > pad_len or n > pad_out:
        raise ValueError(
            f"sequence of {s} + {n} tokens does not fit the padded shapes "
            f"({pad_len}, {pad_out})")
    seq = np.zeros(pad_len, np.int32)
    seq[:s] = prompt
    seq[s:s + n - 1] = served[:-1]
    rows = np.full(pad_out, s - 1, np.int32)
    rows[:n] = s - 1 + np.arange(n)
    tok = np.zeros(pad_out, np.int32)
    tok[:n] = served
    gap, ctrl = _gaps(params, jnp.asarray(seq), jnp.asarray(rows),
                      jnp.asarray(tok), m_items=_frozen(m), control=control)
    return np.asarray(gap)[:n], np.asarray(ctrl)[:n]
