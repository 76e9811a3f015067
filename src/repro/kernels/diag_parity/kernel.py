"""Diagonal-parity kernels (paper §IV on TPU words).

A block is 32 consecutive uint32 words; the slope-s parity word is
XOR_i rotl32(w_i, s*i) — the 32-bit rotate IS the paper's barrel shifter.
Both kernels take the arena in the lane-dense tiling of
kernels/arena_tiles.py: each tile is transposed in VMEM so a block's 32
words run down the sublanes and `rows` blocks sit side by side across the
lanes.  Each row is rotated by its own amount in one vector shift pair,
and the 32 rows are XOR-folded in a five-step halving tree.

`encode_parity_kernel` is the protect/refresh hot loop.  `scrub_kernel`
fuses the whole scrub pass — encode → syndrome → locate → correct for both
data and parity-word errors — into one launch over the packed arena
(DESIGN.md §9), emitting corrected words, corrected parity and the
(corrected, parity_fixed, uncorrectable) counters summed over the grid.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.bitops import popcount32
from .. import arena_tiles as tiles

BLOCK = tiles.BLOCK


def encode_body(w: jax.Array, slopes: Tuple[int, ...]) -> jax.Array:
    """w: (32, n) words of n blocks, word i in row i -> (F, n) parity.
    Row i is rotated left by s*i (a per-row shift, all rows at once), then
    the rows are XOR-folded."""
    word = jax.lax.broadcasted_iota(jnp.uint32, w.shape, 0)
    outs = []
    for s in slopes:
        amt = (word * jnp.uint32(s % BLOCK)) & (BLOCK - 1)
        rot = (w << amt) | (w >> ((BLOCK - amt) & (BLOCK - 1)))
        outs.append(tiles.xor_rows(rot))
    return jnp.concatenate(outs, axis=0)


def _encode_kernel(words_ref, out_ref, scratch, *, slopes: Tuple[int, ...]):
    out_ref[...] = tiles.join_parity(
        [encode_body(w, slopes) for w in tiles.tile_blocks(words_ref[...])],
        scratch)


@functools.partial(jax.jit, static_argnames=("slopes", "rows", "interpret"))
def encode_parity_kernel(words: jax.Array, slopes: Tuple[int, ...] = (1, 2, -1),
                         rows: int = 512, interpret: bool = False) -> jax.Array:
    """words: (n_blocks / 4, 128) uint32 (`arena_tiles.words_view`) ->
    parity (F, n_blocks) uint32."""
    n_rows = words.shape[0]
    assert n_rows % rows == 0, (n_rows, rows)
    F = len(slopes)
    return pl.pallas_call(
        functools.partial(_encode_kernel, slopes=slopes),
        grid=(n_rows // rows,),
        in_specs=[tiles.words_spec(rows)],
        out_specs=tiles.parity_spec(F, rows),
        out_shape=jax.ShapeDtypeStruct((F, n_rows * tiles.SEGS), jnp.uint32),
        scratch_shapes=[tiles.parity_scratch(rows)],
        interpret=interpret,
    )(words)


def _onehot_position(x: jax.Array) -> jax.Array:
    """Bit index of a one-hot uint32: popcount(x - 1).  Gated by callers on
    popcount(x) == 1, so the x == 0 wrap is never observed."""
    return popcount32(x - jnp.uint32(1))


def scrub_body(w: jax.Array, p: jax.Array, slopes: Tuple[int, ...]):
    """The fused encode→syndrome→locate→correct body, shared by the scrub
    kernel and the fault-campaign inject+scrub kernel (kernels/inject_scrub)
    so the classification logic has one home.

    w: (32, n) data words of n blocks (word i in row i), p: (F, n) parity
    words (both uint32, already in VMEM).  Returns (corrected w, corrected
    p, data_err, parity_err, uncorrectable) with the last three bool (1, n)
    block classifications.
    """
    F = len(slopes)
    syn = encode_body(w, slopes) ^ p        # (F, n)

    # classify: per-family popcount / one-hot position
    pop = popcount32(syn)                   # (F, n) int32
    nonzero = pop > 0
    onehot = pop == 1
    n_nonzero = nonzero.astype(jnp.int32).sum(axis=0, keepdims=True)
    n_onehot = onehot.astype(jnp.int32).sum(axis=0, keepdims=True)
    n_quiet = (onehot | ~nonzero).astype(jnp.int32).sum(axis=0, keepdims=True)
    hot = _onehot_position(syn)             # (F, n); valid where onehot

    # locate: slopes (1, 2) invert the diagonal system; the rest must agree
    ia, ib = slopes.index(1), slopes.index(2)
    i0 = (hot[ib:ib + 1] - hot[ia:ia + 1]) & (BLOCK - 1)
    j0 = (hot[ia:ia + 1] - i0) & (BLOCK - 1)
    consistent = jnp.ones(i0.shape, dtype=jnp.bool_)
    for f, s in enumerate(slopes):
        consistent &= hot[f:f + 1] == ((j0 + s * i0) & (BLOCK - 1))

    data_err = (n_nonzero == F) & (n_onehot == F) & consistent
    parity_err = (n_nonzero == 1) & (n_quiet == F)
    uncorrectable = (n_nonzero > 0) & ~data_err & ~parity_err

    # correct: flip bit j0 of word i0 in flagged blocks; heal parity words
    flip_word = jnp.where(data_err, jnp.uint32(1) << j0.astype(jnp.uint32),
                          jnp.uint32(0))
    row = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0) == i0
    out_w = w ^ (row.astype(jnp.uint32) * flip_word)
    out_p = p ^ jnp.where(parity_err & nonzero, syn, jnp.uint32(0))
    return out_w, out_p, data_err, parity_err, uncorrectable


def scrub_tile(x: jax.Array, p: jax.Array, slopes: Tuple[int, ...],
               scratch):
    """Scrub one (rows, 128) word tile against its (F, 4 * rows) parity
    tile.  Returns (words, parity, [corrected, parity_fixed,
    uncorrectable] int32 scalars)."""
    return tiles.scrub_planes(
        x, p, lambda w, pq: scrub_body(w, pq, slopes), scratch)


def _scrub_kernel(words_ref, parity_ref, out_w_ref, out_p_ref, stats_ref,
                  scratch, *, slopes: Tuple[int, ...]):
    out_w, out_p, counts = scrub_tile(words_ref[...], parity_ref[...], slopes,
                                      scratch)
    out_w_ref[...] = out_w
    out_p_ref[...] = out_p
    tiles.accumulate_stats(stats_ref, counts)


@functools.partial(jax.jit, static_argnames=("slopes", "rows", "interpret"))
def scrub_kernel(words: jax.Array, parity: jax.Array,
                 slopes: Tuple[int, ...] = (1, 2, -1),
                 rows: int = 512, interpret: bool = False):
    """Fused scrub: words (n_blocks / 4, 128) + parity (F, n_blocks) uint32
    in the `arena_tiles` layout -> (corrected words, corrected parity,
    stats tile (8, 128) int32).

    stats row 0, lanes 0..2: corrected, parity_fixed, uncorrectable, summed
    over the grid.  Requires slopes to contain the locating pair (1, 2).
    """
    assert 1 in slopes and 2 in slopes, slopes
    n_rows, F = words.shape[0], len(slopes)
    assert n_rows % rows == 0, (n_rows, rows)
    return pl.pallas_call(
        functools.partial(_scrub_kernel, slopes=slopes),
        grid=(n_rows // rows,),
        in_specs=[tiles.words_spec(rows), tiles.parity_spec(F, rows)],
        out_specs=[tiles.words_spec(rows), tiles.parity_spec(F, rows),
                   tiles.STATS_SPEC],
        out_shape=[jax.ShapeDtypeStruct(words.shape, jnp.uint32),
                   jax.ShapeDtypeStruct(parity.shape, jnp.uint32),
                   jax.ShapeDtypeStruct(tiles.STATS_SHAPE, jnp.int32)],
        scratch_shapes=[tiles.parity_scratch(rows)],
        compiler_params=tiles.SEQUENTIAL,
        interpret=interpret,
    )(words, parity)
