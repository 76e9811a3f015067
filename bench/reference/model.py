"""Plain float32 reference of the served dense decoder.

The architecture as the papers publish it (Llama-style decoder: RMSNorm
before attention and MLP, rotary positions on the first and second half
of each head, grouped-query attention with a causal mask, SwiGLU MLP,
untied head), written in `jax.numpy` with no kernel, cache or batching,
and every matrix product at ``precision="highest"``.  Its weights come
from `bench.weights` and nothing from the program under test; they are
remade a layer at a time, so the reference fits beside nothing on one
chip at the published widths.  Departures from the papers, all shared
with the served program's layout: the vocabulary is padded to a multiple
of 128 rows (`weights.padded_vocab`), and norms multiply by
``1 + scale``.

`hidden` runs teacher-forced: it takes whole sequences (prompt plus the
served tokens) and returns the final normed hidden state at every
position; `logits` applies the head at chosen positions.

`quant` gives the control: every linear layer's weights and inputs
rounded to float8 e4m3 with a per-tensor (weights) or per-row (inputs)
scale, the step below the served bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with an absmax scale over `axis` (None: the
    whole tensor)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None) / F8_MAX
    s = jnp.maximum(s, 1e-30)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant:
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1.0 + scale)


def _rope(x, theta):
    """x (S, heads, hd): rotate the first half of each head against the
    second by position * theta^(-2i/hd)."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _tensor(cfg, words, name, layer, dtype):
    shape, std, _ = W.tensor_shapes(cfg)[name]
    if len(shape) == 1:
        rows, cols = jnp.uint32(0), jnp.arange(shape[0], dtype=jnp.uint32)
    else:
        rows = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    return W.values(words, name, std, layer, rows, cols,
                    dtype).astype(jnp.float32)


def _attention(q, k, v, n_kv):
    """q (S, H, hd), k/v (S, KV, hd); causal, in query blocks."""
    S, H, hd = q.shape
    G = H // n_kv
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    outs = []
    for q0 in range(0, S, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) \
            / math.sqrt(hd)
        qpos = q0 + jnp.arange(qb.shape[0])[:, None]
        s = jnp.where(qpos >= jnp.arange(S)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _layer(cfg_items, words, layer, x, dtype, quant):
    cfg = dict(cfg_items)
    d, H, KV = cfg["d_model"], cfg["n_heads"], cfg["n_kv"]
    hd, f, eps = d // H, cfg["d_ff"], cfg["norm_eps"]
    t = lambda n: _tensor(cfg, words, n, layer, dtype)  # noqa: E731
    S = x.shape[0]
    h = _norm(x, t("attn.ln"), eps)
    q = _mm(h, t("attn.wq"), quant).reshape(S, H, hd)
    kv = _mm(h, t("attn.wkv"), quant)
    k = kv[:, :KV * hd].reshape(S, KV, hd)
    v = kv[:, KV * hd:].reshape(S, KV, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    o = _attention(q, k, v, KV).reshape(S, H * hd)
    x = x + _mm(o, t("attn.wo"), quant)
    h = _norm(x, t("mlp.ln"), eps)
    up = _mm(h, t("mlp.w_up"), quant)
    return x + _mm(up[:, :f] * jax.nn.silu(up[:, f:]), t("mlp.w_down"), quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _embed(cfg_items, words, tokens, dtype):
    cfg = dict(cfg_items)
    _, std, _ = W.tensor_shapes(cfg)["embed.tok"]
    cols = jnp.arange(cfg["d_model"], dtype=jnp.uint32)[None, :]
    rows = tokens.astype(jnp.uint32)[:, None]
    return W.values(words, "embed.tok", std, jnp.uint32(0), rows, cols,
                    dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _final(cfg_items, words, x, dtype):
    cfg = dict(cfg_items)
    return _norm(x, _tensor(cfg, words, "final_ln", jnp.uint32(0), dtype),
                 cfg["norm_eps"])


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _head_block(cfg_items, words, h, c0, dtype, quant):
    """Logits for head columns c0 .. c0 + HEAD_BLOCK (clipped)."""
    cfg = dict(cfg_items)
    _, std, _ = W.tensor_shapes(cfg)["embed.head"]
    rows = jax.lax.broadcasted_iota(jnp.uint32, (cfg["d_model"],
                                                 W.HEAD_BLOCK), 0)
    cols = c0 + jax.lax.broadcasted_iota(jnp.uint32, rows.shape, 1)
    w = W.values(words, "embed.head", std, jnp.uint32(0), rows, cols,
                 dtype).astype(jnp.float32)
    return _mm(h, w, quant)


def _items(cfg: dict):
    keys = ("n_layers", "d_model", "n_heads", "n_kv", "d_ff", "vocab",
            "norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def hidden(cfg: dict, seed: int, tokens: np.ndarray, dtype, *,
           quant: bool = False) -> jax.Array:
    """Final normed hidden states (S, d) of one teacher-forced sequence.
    `dtype` is the served weight dtype the weights are rounded to."""
    items, words = _items(cfg), jnp.asarray(W.seed_words(seed))
    x = _embed(items, words, jnp.asarray(tokens, jnp.int32), dtype)
    for layer in range(cfg["n_layers"]):
        x = _layer(items, words, jnp.uint32(layer), x, dtype, quant)
    return _final(items, words, x, dtype)


def logits(cfg: dict, seed: int, h: jax.Array, dtype, *,
           quant: bool = False) -> jax.Array:
    """Head over every padded vocabulary id: h (n, d) -> (n, V) float32."""
    items, words = _items(cfg), jnp.asarray(W.seed_words(seed))
    V = W.padded_vocab(cfg)
    blocks = [_head_block(items, words, h, jnp.uint32(c0), dtype, quant)
              for c0 in range(0, V, W.HEAD_BLOCK)]
    return jnp.concatenate(blocks, axis=1)[:, :V]
