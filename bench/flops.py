"""Operations and bytes of the served model, from its shapes.

Adapted from the program's analytic count (`benchmarks/roofline.py`,
`param_count` and `model_flops`), kept here so that no change to the
program can change the yardstick.  One multiply-add is two operations.
Attention counts every query head against every cached key, as the
served program computes it (a key shared by a group of heads is still
multiplied once per head).
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}: {sorted(table)}")
    return table[device_kind]


def layer_params(c: dict) -> int:
    d, f, H, KV = c["d_model"], c["d_ff"], c["n_heads"], c["n_kv"]
    hd = d // H
    attn = d * H * hd + d * 2 * KV * hd + H * hd * d
    mlp = d * 2 * f + f * d
    return attn + mlp


def head_params(c: dict) -> int:
    return c["d_model"] * -(-c["vocab"] // 128) * 128


def matmul_params(c: dict) -> int:
    """Parameters every token multiplies through: all layers and the
    head (the embedding is a lookup)."""
    return c["n_layers"] * layer_params(c) + head_params(c)


def token_flops(c: dict, context: float) -> float:
    """One decoded token attending to `context` cached positions."""
    hd = c["d_model"] // c["n_heads"]
    attn = 4.0 * c["n_layers"] * c["n_heads"] * hd * context
    return 2.0 * matmul_params(c) + attn


def prefill_flops(c: dict, n: int) -> float:
    """A prompt of n tokens: every layer over every position, causal
    attention over n(n+1)/2 pairs, and the head at the last position
    only (the served prefill produces one next token)."""
    hd = c["d_model"] // c["n_heads"]
    body = 2.0 * c["n_layers"] * layer_params(c) * n
    attn = 4.0 * c["n_layers"] * c["n_heads"] * hd * n * (n + 1) / 2
    return body + attn + 2.0 * head_params(c)


def kv_bytes_per_token(c: dict, dtype_bytes: int) -> int:
    hd = c["d_model"] // c["n_heads"]
    return 2 * c["n_layers"] * c["n_kv"] * hd * dtype_bytes


def decode_step_bytes(c: dict, dtype_bytes: int, live_tokens: float) -> float:
    """Least bytes one decode step of the whole batch must read: every
    weight it multiplies through once, and each slot's live KV once."""
    return matmul_params(c) * dtype_bytes \
        + live_tokens * kv_bytes_per_token(c, dtype_bytes)
