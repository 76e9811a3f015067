"""Random weights from the seed, made by the benchmark and not the program.

Every weight is a pure function of (seed, tensor name, layer, row,
column): an integer hash of those coordinates gives 23 random bits, which
become a uniform value of the tensor's standard deviation.  So the serving
store can be made whole on the device in one jitted call, and the float32
reference can remake any layer, any rows of the embedding or any block of
columns of the head, bit for bit, after the program's state is freed.
The values are rounded to the served dtype before either side sees them.

The dense tensors follow the serving program's parameter layout, which is
its interface: ``w_up`` holds the up projection in its first ``d_ff``
columns and the gate in the rest, ``wkv`` holds K then V, and each norm
multiplies by ``1 + scale``.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: the columns of the head are remade in blocks of this many
HEAD_BLOCK = 8192


def seed_words(seed: int) -> np.ndarray:
    """Any non-negative whole number -> two uint32 words (high, low)."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"--seed must lie in [0, 2**64): {seed}")
    return np.asarray([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _fmix(x):
    """murmur3's 32-bit finaliser: a bijection that mixes every bit."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _salt(name: str) -> int:
    return zlib.crc32(name.encode()) & 0xFFFFFFFF


def tensor_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], float, bool]]:
    """name -> (shape of one layer's tensor, std, stacked over layers)."""
    d, f, H, KV = cfg["d_model"], cfg["d_ff"], cfg["n_heads"], cfg["n_kv"]
    hd = d // H
    v = padded_vocab(cfg)
    return {
        "embed.tok": ((v, d), 1.0, False),
        "embed.head": ((d, v), 1 / math.sqrt(d), False),
        "final_ln": ((d,), 0.05, False),
        "attn.ln": ((d,), 0.05, True),
        "attn.wq": ((d, H * hd), 1 / math.sqrt(d), True),
        "attn.wkv": ((d, 2 * KV * hd), 1 / math.sqrt(d), True),
        "attn.wo": ((H * hd, d), 1 / math.sqrt(H * hd), True),
        "mlp.ln": ((d,), 0.05, True),
        "mlp.w_up": ((d, 2 * f), 1 / math.sqrt(d), True),
        "mlp.w_down": ((f, d), 1 / math.sqrt(f), True),
    }


def padded_vocab(cfg: dict) -> int:
    """The served embedding and head carry the vocabulary padded to a
    multiple of 128 rows (the program's layout); pad ids are ordinary
    random rows, so the reference scores them too."""
    return -(-cfg["vocab"] // 128) * 128


def values(words, name: str, std: float, layer, rows, cols, dtype):
    """Weights at the broadcast grid `layer` x `rows` x `cols` (uint32
    index arrays), rounded to `dtype`."""
    hi, lo = words[0], words[1]
    k = _fmix(lo ^ _fmix(hi + jnp.uint32(_salt(name))))
    k = _fmix(k + layer * jnp.uint32(0x9E3779B9))
    h = _fmix(_fmix(k ^ rows) + cols * jnp.uint32(0x7FEB352D))
    u = jax.lax.bitcast_convert_type((h >> 9) | jnp.uint32(0x3F800000),
                                     jnp.float32)        # [1, 2)
    return ((u - 1.5) * jnp.float32(2 * math.sqrt(3) * std)).astype(dtype)


def _grid(shape, layers: int):
    """uint32 (layer, row, col) index arrays broadcast to the stacked
    shape; a vector is one row."""
    full = ((layers,) if layers else ()) + tuple(shape)
    n = len(full)
    ax = lambda i: jax.lax.broadcasted_iota(jnp.uint32, full, i)  # noqa: E731
    layer = ax(0) if layers else jnp.uint32(0)
    if len(shape) == 1:
        return layer, jnp.uint32(0), ax(n - 1)
    return layer, ax(n - 2), ax(n - 1)


def program_params(cfg: dict, seed: int, dtype) -> dict:
    """The whole served parameter tree in `dtype`, made on the device by
    one jitted call from the seed (the seed is an argument, so one
    compiled program serves every seed)."""
    shapes = tensor_shapes(cfg)
    L = cfg["n_layers"]

    def make(words):
        out = {}
        for name, (shape, std, stacked) in shapes.items():
            layer, rows, cols = _grid(shape, L if stacked else 0)
            out[name] = values(words, name, std, layer, rows, cols, dtype)
        return {"embed": {"tok": out["embed.tok"], "head": out["embed.head"]},
                "final_ln": out["final_ln"],
                "layers": {"attn": {k: out["attn." + k]
                                    for k in ("ln", "wq", "wkv", "wo")},
                           "mlp": {k: out["mlp." + k]
                                   for k in ("ln", "w_up", "w_down")}}}

    return jax.jit(make)(jnp.asarray(seed_words(seed)))
