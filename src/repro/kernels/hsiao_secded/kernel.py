"""Hsiao SEC-DED kernels: fused encode → syndrome → classify → correct.

Same tiling as kernels/diag_parity (kernels/arena_tiles.py): each tile is
transposed in VMEM so a block's 32 words run down the sublanes and blocks
sit across the lanes, with (7, blocks) parity tiles.  The encode is 7
masked-popcount parities per word, packed over the 32 words of a block
into one uint32 per check bit; the scrub recomputes them, XORs against
the stored table, reassembles a per-word 7-bit syndrome and classifies it
against the 39 compile-time column constants — 32 data columns and 7 unit
vectors — with unrolled equality compares, so the whole decode is branch-
and gather-free on the VPU.  Bit packing sums disjoint powers of two in
int32 (the TPU has no unsigned reductions), which equals the OR of the
bits modulo 2^32.

Unlike the diagonal code (one correction per block), every word of a
block decodes independently: the stats count words, not blocks.  A
syndrome that is nonzero but matches no column is a detected double
error; the word is left untouched and reported uncorrectable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.bitops import popcount32
from .. import arena_tiles as tiles
from .code import CHECK_MASKS, DATA_COLUMNS, N_CHECKS

BLOCK = tiles.BLOCK


def _pack_rows(bits: jax.Array) -> jax.Array:
    """(32, n) uint32 holding disjoint bits down each column -> (1, n)
    uint32, their OR, reduced as an int32 sum."""
    total = jax.lax.bitcast_convert_type(bits, jnp.int32).sum(
        axis=0, keepdims=True)
    return jax.lax.bitcast_convert_type(total, jnp.uint32)


def _word_index(w: jax.Array) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.uint32, w.shape, 0)


def _encode_checks(w: jax.Array) -> list:
    """w (32, n) uint32 (word i in row i) -> 7 packed check words, each
    (1, n) uint32 with check bit j of word i at bit position i."""
    word = _word_index(w)
    out = []
    for m in CHECK_MASKS:
        bit = (popcount32(w & jnp.uint32(m)) & 1).astype(jnp.uint32)
        out.append(_pack_rows(bit << word))
    return out


def _encode_kernel(words_ref, out_ref, scratch):
    out_ref[...] = tiles.join_parity(
        [jnp.concatenate(_encode_checks(w), axis=0)
         for w in tiles.tile_blocks(words_ref[...])], scratch)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def encode_hsiao_kernel(words: jax.Array, rows: int = 512,
                        interpret: bool = False) -> jax.Array:
    """words: (n_blocks / 4, 128) uint32 (`arena_tiles.words_view`) ->
    parity (7, n_blocks) uint32."""
    n_rows = words.shape[0]
    assert n_rows % rows == 0, (n_rows, rows)
    return pl.pallas_call(
        _encode_kernel,
        grid=(n_rows // rows,),
        in_specs=[tiles.words_spec(rows)],
        out_specs=tiles.parity_spec(N_CHECKS, rows),
        out_shape=jax.ShapeDtypeStruct((N_CHECKS, n_rows * tiles.SEGS),
                                       jnp.uint32),
        scratch_shapes=[tiles.parity_scratch(rows)],
        interpret=interpret,
    )(words)


def hsiao_body(w: jax.Array, p: jax.Array):
    """The fused body: w (32, n) data words of n blocks (word i in row i),
    p (7, n) parity words.

    Returns (corrected w, corrected p, data_err, check_err, uncorrectable)
    with the last three bool (32, n) per-WORD classifications.
    """
    word = _word_index(w)
    enc = _encode_checks(w)

    # per-word syndrome: bit j of s[i, b] = bit i of (enc_j ^ p[j])
    s = jnp.zeros_like(w)
    for j in range(N_CHECKS):
        syn_j = enc[j] ^ p[j:j + 1]                  # (1, n) packed over i
        s = s | (((syn_j >> word) & jnp.uint32(1)) << jnp.uint32(j))

    # classify against the 39 compile-time columns (unrolled compares)
    data_err = jnp.zeros(w.shape, jnp.bool_)
    flip = jnp.zeros_like(w)
    for k, col in enumerate(DATA_COLUMNS):
        eq = s == jnp.uint32(col)
        data_err |= eq
        flip = flip | (eq.astype(jnp.uint32) << jnp.uint32(k))

    check_err = jnp.zeros(w.shape, jnp.bool_)
    out_p = []
    for j in range(N_CHECKS):
        eq = s == jnp.uint32(1 << j)                 # check bit j flipped
        check_err |= eq
        out_p.append(p[j:j + 1] ^ _pack_rows(eq.astype(jnp.uint32) << word))
    uncorrectable = (s != 0) & ~data_err & ~check_err

    return (w ^ flip, jnp.concatenate(out_p, axis=0),
            data_err, check_err, uncorrectable)


def _scrub_kernel(words_ref, parity_ref, out_w_ref, out_p_ref, stats_ref,
                  scratch):
    out_w, out_p, counts = tiles.scrub_planes(
        words_ref[...], parity_ref[...], hsiao_body, scratch)
    out_w_ref[...] = out_w
    out_p_ref[...] = out_p
    tiles.accumulate_stats(stats_ref, counts)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def scrub_hsiao_kernel(words: jax.Array, parity: jax.Array,
                       rows: int = 512, interpret: bool = False):
    """Fused scrub: words (n_blocks / 4, 128) + parity (7, n_blocks) uint32
    in the `arena_tiles` layout -> (corrected words, corrected parity,
    stats tile (8, 128) int32).

    stats row 0, lanes 0..2: corrected, parity_fixed, uncorrectable — per
    word, summed over the grid.
    """
    n_rows = words.shape[0]
    assert n_rows % rows == 0, (n_rows, rows)
    return pl.pallas_call(
        _scrub_kernel,
        grid=(n_rows // rows,),
        in_specs=[tiles.words_spec(rows), tiles.parity_spec(N_CHECKS, rows)],
        out_specs=[tiles.words_spec(rows), tiles.parity_spec(N_CHECKS, rows),
                   tiles.STATS_SPEC],
        out_shape=[jax.ShapeDtypeStruct(words.shape, jnp.uint32),
                   jax.ShapeDtypeStruct(parity.shape, jnp.uint32),
                   jax.ShapeDtypeStruct(tiles.STATS_SHAPE, jnp.int32)],
        scratch_shapes=[tiles.parity_scratch(rows)],
        compiler_params=tiles.SEQUENTIAL,
        interpret=interpret,
    )(words, parity)
