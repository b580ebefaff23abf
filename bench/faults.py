"""Faults planted under the timed path, to show that the comparison
deciding ``correct`` catches each fault a served cell can have. Neither
the benchmark's runs nor anything else of the program uses them; the
tests in ``tests/bench/`` and ``bench/calibrate.py check --fault`` do.

``plant(name, setattr_)`` patches the program with ``setattr_`` (the
built-in, or pytest's ``monkeypatch.setattr``) before the engine is
built:

- ``state_unchanged``: the decode step returns the KV cache it was given;
- ``half_batch``: the decode step sees every odd slot as inactive;
- ``token_altered``: every other slot's sampled token is replaced by the
  next id;
- ``no_model_allreduce``: the sum over the model axis of the mesh is
  left out.
"""
from __future__ import annotations

FAULTS = ("state_unchanged", "half_batch", "token_altered",
          "no_model_allreduce")


def _decode(setattr_, fn):
    from repro.models import model as model_lib

    real = model_lib.serving_decode_step

    def patched(params, cfg, toks, caches, active, *a, **k):
        return fn(real, params, cfg, toks, caches, active, *a, **k)

    setattr_(model_lib, "serving_decode_step", patched)


def plant(name: str, setattr_=setattr) -> None:
    if name == "state_unchanged":
        def keep_state(real, params, cfg, toks, caches, active, *a, **k):
            logits, _, skip = real(params, cfg, toks, caches, active, *a, **k)
            return logits, caches, skip

        _decode(setattr_, keep_state)
    elif name == "half_batch":
        import jax.numpy as jnp

        def half(real, params, cfg, toks, caches, active, *a, **k):
            keep = (jnp.arange(active.shape[0]) % 2 == 0).astype(active.dtype)
            return real(params, cfg, toks, caches, active * keep, *a, **k)

        _decode(setattr_, half)
    elif name == "token_altered":
        import numpy as np

        from repro.runtime.server import Server

        real = Server._sample

        def altered(self, logits):
            out = np.array(real(self, logits))
            if out.ndim == 1 and out.size > 1:
                out[::2] = (out[::2] + 1) % logits.shape[-1]
            return out

        setattr_(Server, "_sample", altered)
    elif name == "no_model_allreduce":
        import jax

        real = jax.lax.psum

        def no_model_psum(x, axis_name, **k):
            if axis_name == "model":
                return x
            return real(x, axis_name, **k)

        setattr_(jax.lax, "psum", no_model_psum)
    else:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
