"""Packed parameter arena: one contiguous uint32 buffer per pytree.

The reliability layer's throughput problem (DESIGN.md §9) is dispatch
granularity: protecting a model with N leaves as N independent buffers costs
N kernel launches per protect/scrub/refresh, and the small leaves (biases,
norm scales) dominate launch overhead rather than bandwidth.  The arena
flattens the whole pytree into ONE flat uint32 buffer:

    [ leaf0 words | pad | leaf1 words | pad | ... ]

Every leaf starts on a 32-word (ECC block) boundary, so a block never
straddles two leaves, pad words are identically zero (their parity
contribution is zero and a syndrome over padding is clean), and an
uncorrectable block is attributable to exactly one leaf.

All metadata (offsets, pad, dtype, shape) is host-side and static — packing
and unpacking are pure bitcast/concatenate/slice programs, so they trace
and fuse under jit, and protect/scrub/refresh over the arena become a single
fused kernel launch regardless of the number of leaves.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

__all__ = ["BLOCK", "LeafSpec", "ArenaSpec", "leaf_to_words", "words_to_leaf",
           "pack", "unpack", "arena_spec", "leaf_spec", "map_leaf_words",
           "words_for"]

BLOCK = 32  # words per ECC block == bits per word


def _n_elems(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


def words_for(shape, dtype) -> int:
    """Payload words `leaf_to_words` would produce for a leaf of this
    shape/dtype (bfloat16 packs two 16-bit halves per word) — the
    host-side sizing primitive for arena consumers that lay out
    fixed-granularity regions, e.g. the paged KV pool checking that one
    KV page spans a whole number of ECC blocks."""
    n = _n_elems(shape)
    if jnp.dtype(dtype) == jnp.bfloat16:
        return (n + 1) // 2
    return n


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Placement of one leaf inside the arena (all host-side constants)."""
    offset: int          # word offset of the leaf start (block-aligned)
    n_words: int         # payload words (bf16 halves packed two per word)
    pad_words: int       # zero words up to the next block boundary
    dtype: Any           # jnp dtype of the original leaf
    shape: Tuple[int, ...]

    @property
    def n_blocks(self) -> int:
        return (self.n_words + self.pad_words) // BLOCK


@dataclasses.dataclass(frozen=True)
class ArenaSpec:
    leaves: Tuple[LeafSpec, ...]
    treedef: Any
    n_words: int         # total arena length in words (multiple of BLOCK)

    @property
    def n_blocks(self) -> int:
        return self.n_words // BLOCK

    def leaf_of_block(self, block: int) -> int:
        """Index of the leaf that owns ECC block `block` (host-side)."""
        for i, l in enumerate(self.leaves):
            first = l.offset // BLOCK
            if first <= block < first + l.n_blocks:
                return i
        raise IndexError(block)


def _words_per_leaf(x: jax.Array) -> int:
    if x.dtype == jnp.bfloat16:
        return (_n_elems(x.shape) + 1) // 2
    return _n_elems(x.shape)


def leaf_to_words(x: jax.Array) -> jax.Array:
    """Bitcast one leaf to its flat uint32 payload (no block padding).

    bfloat16 leaves pack two 16-bit halves per word, LSB-half first; an
    odd-length leaf carries one zero half-word in its last word.
    """
    if x.dtype == jnp.bfloat16:
        u16 = jax.lax.bitcast_convert_type(x, jnp.uint16)
        if x.ndim and x.shape[-1] % 2 == 0:
            # pairs along the last axis are the flat view's pairs; a flat
            # stride-2 slice would make XLA pad a (n, 2) array to 128
            # lanes on a TPU
            u = u16.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
            u = u.astype(jnp.uint32)
            return (u[..., 0] | (u[..., 1] << 16)).reshape(-1)
        u16 = u16.reshape(-1)
        if u16.shape[0] % 2:
            u16 = jnp.pad(u16, (0, 1))
        return u16[0::2].astype(jnp.uint32) | (u16[1::2].astype(jnp.uint32) << 16)
    if x.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(x.reshape(-1), jnp.uint32)
    if x.dtype in (jnp.int32, jnp.uint32):
        return x.reshape(-1).astype(jnp.uint32)
    raise TypeError(f"arena: unsupported dtype {x.dtype}")


def words_to_leaf(words: jax.Array, spec: LeafSpec) -> jax.Array:
    """Inverse of `leaf_to_words` given the leaf's exact payload words."""
    n = _n_elems(spec.shape)
    if spec.dtype == jnp.bfloat16:
        shape = spec.shape
        paired = bool(shape) and shape[-1] % 2 == 0   # see leaf_to_words
        if paired:
            words = words.reshape(*shape[:-1], shape[-1] // 2)
        u16 = jnp.stack([(words & 0xFFFF).astype(jnp.uint16),
                         (words >> 16).astype(jnp.uint16)], -1)
        u16 = u16.reshape(shape) if paired else u16.reshape(-1)[:n]
        return jax.lax.bitcast_convert_type(u16, jnp.bfloat16).reshape(shape)
    if spec.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(words, jnp.float32).reshape(spec.shape)
    return words.astype(spec.dtype).reshape(spec.shape)


def leaf_spec(x: jax.Array, offset: int = 0) -> LeafSpec:
    """Placement of one leaf (abstract shapes suffice) at `offset`."""
    n_words = _words_per_leaf(x)
    return LeafSpec(offset=offset, n_words=n_words,
                    pad_words=(-n_words) % BLOCK, dtype=x.dtype,
                    shape=tuple(x.shape))


def arena_spec(params: Any) -> ArenaSpec:
    """Layout (without building the buffer): abstract shapes suffice."""
    leaves, treedef = jax.tree.flatten(params)
    specs, offset = [], 0
    for x in leaves:
        specs.append(leaf_spec(x, offset))
        offset += specs[-1].n_blocks * BLOCK
    return ArenaSpec(leaves=tuple(specs), treedef=treedef, n_words=offset)


def map_leaf_words(x: jax.Array, fn: Callable, slab_words: int = 1 << 24
                   ) -> Tuple[jax.Array, Any]:
    """Apply ``fn(words, offset) -> (words', aux)`` to the word view of
    one leaf and return the rebuilt leaf and the summed `aux`.

    A leaf whose axis-0 rows hold whole ECC blocks is walked in slabs of
    rows (about `slab_words` words each) by one `fori_loop` that rewrites
    the leaf in place, so no temporary ever spans the whole leaf; `offset`
    is the slab's first word within the leaf.  Any other leaf goes in one
    piece at offset 0.  Every slab starts on a block boundary of the leaf,
    so a block-local code sees exactly the blocks of the whole leaf."""
    spec = leaf_spec(x)
    row_words = words_for(x.shape[1:], x.dtype) if x.ndim > 1 else 0
    paired = x.dtype != jnp.bfloat16 or _n_elems(x.shape[1:]) % 2 == 0
    if (x.ndim < 2 or not paired or row_words % BLOCK
            or spec.n_words <= slab_words):
        y, aux = fn(leaf_to_words(x), jnp.int32(0))
        return words_to_leaf(y, spec), aux
    rows = x.shape[0]
    k = max([d for d in range(1, rows + 1)
             if rows % d == 0 and d * row_words <= slab_words] or [1])
    slab = leaf_spec(jax.ShapeDtypeStruct((k,) + x.shape[1:], x.dtype))
    aux0 = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda w: fn(w, jnp.int32(0))[1],
                       jax.ShapeDtypeStruct((slab.n_words,), jnp.uint32)))

    # the loop carries the leaf's bits as unsigned ints: a float carry may
    # have its NaN payloads canonicalized (XLA:CPU widens bfloat16), and
    # flipped bits make NaNs
    bits = jnp.dtype(f"uint{jnp.dtype(x.dtype).itemsize * 8}")

    def body(i, carry):
        xb, aux = carry
        part = jax.lax.dynamic_slice_in_dim(xb, i * k, k)
        y, a = fn(leaf_to_words(jax.lax.bitcast_convert_type(part, x.dtype)),
                  i * (k * row_words))
        xb = jax.lax.dynamic_update_slice_in_dim(
            xb, jax.lax.bitcast_convert_type(words_to_leaf(y, slab), bits),
            i * k, 0)
        return xb, jax.tree.map(jnp.add, aux, a)

    xb, aux = jax.lax.fori_loop(
        0, rows // k, body, (jax.lax.bitcast_convert_type(x, bits), aux0))
    return jax.lax.bitcast_convert_type(xb, x.dtype), aux


def pack(params: Any) -> Tuple[jax.Array, ArenaSpec]:
    """Flatten a pytree into (arena_u32, spec); one concatenate, jit-safe."""
    spec = arena_spec(params)
    leaves = jax.tree.leaves(params)
    parts = []
    for x, l in zip(leaves, spec.leaves):
        w = leaf_to_words(x)
        if l.pad_words:
            w = jnp.pad(w, (0, l.pad_words))
        parts.append(w)
    if not parts:
        return jnp.zeros((0,), jnp.uint32), spec
    return jnp.concatenate(parts), spec


def unpack(arena: jax.Array, spec: ArenaSpec) -> Any:
    """Rebuild the pytree from the arena (static slices; jit-safe)."""
    leaves = [words_to_leaf(arena[l.offset:l.offset + l.n_words], l)
              for l in spec.leaves]
    return spec.treedef.unflatten(leaves)
