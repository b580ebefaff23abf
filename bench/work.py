"""Operations and bytes a decoder step needs, counted from its shapes.

The model dict uses the published (Hugging Face ``config.json``) keys:
``num_hidden_layers``, ``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim`` (optional), ``intermediate_size``,
``vocab_size``. Counts are of the work the model requires, never of what
an implementation happens to issue: padded rows, dead batch slots and
blocks fetched but masked do not count.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path=PEAKS_FILE) -> dict:
    """The peak table row for ``device_kind``; an unknown kind raises."""
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path}; "
            f"known: {sorted(table)}")
    return table[device_kind]


def head_dim(m: dict) -> int:
    return int(m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"])


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one decoder layer:
    q/k/v/o projections and the gated MLP's three matrices."""
    d, f = m["hidden_size"], m["intermediate_size"]
    h, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def param_count(m: dict) -> int:
    """All parameters: layers, embedding and (untied) head; norms left out."""
    embed = m["vocab_size"] * m["hidden_size"]
    head = 0 if m.get("tie_word_embeddings") else head_params(m)
    return m["num_hidden_layers"] * layer_matmul_params(m) + embed + head


def attn_flops(m: dict, ctx: int) -> int:
    """One query attending over ``ctx`` rows in every layer: QK and PV."""
    return (m["num_hidden_layers"] * 4 * m["num_attention_heads"]
            * head_dim(m) * ctx)


def decode_token_flops(m: dict, ctx: int) -> int:
    """One decoded token whose query attends over ``ctx`` rows (its own
    row included), with the head's logits."""
    return (2 * m["num_hidden_layers"] * layer_matmul_params(m)
            + 2 * head_params(m) + attn_flops(m, ctx))


def prefill_flops(m: dict, prompt_len: int) -> int:
    """A causal prefill of ``prompt_len`` tokens; logits only for the
    last position, which is all that serving samples from."""
    s = prompt_len
    layers = 2 * m["num_hidden_layers"] * layer_matmul_params(m) * s
    return layers + 2 * head_params(m) + attn_flops(m, s * (s + 1) // 2)


def kv_row_bytes(m: dict, dtype_bytes: int = 2) -> int:
    """Key and value bytes of one token in one layer."""
    return 2 * m["num_key_value_heads"] * head_dim(m) * dtype_bytes


def decode_attn_bytes(m: dict, ctx: int, dtype_bytes: int = 2) -> int:
    """KV bytes one decoded token must read: ``ctx`` rows in every layer."""
    return ctx * kv_row_bytes(m, dtype_bytes) * m["num_hidden_layers"]
