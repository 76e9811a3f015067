"""Fused fault-injection + scrub kernel (Monte-Carlo campaign hot loop).

One Pallas launch executes a whole trial interval over the packed arena:

    inject (XOR the fault mask) → encode → syndrome → locate → correct

The tile body is diag_parity's shared `scrub_tile` (DESIGN.md §9) with the
corruption folded in front of the XOR trees: the corrupted words exist only
in VMEM — they are never round-tripped through HBM between injection and
scrub, which is exactly the memory traffic a campaign of thousands of
trials cares about.  The fault mask is sampled *outside* the kernel by a
faults.models.FaultModel (threefry — deterministic and identical to the jnp
oracle), so the kernel stays bit-exact testable against ref.py.

The stats tile gains a 4th counter, `injected` (popcount of the mask), so a
campaign reads (injected, corrected, parity_fixed, uncorrectable) for the
batch from one launch.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.bitops import popcount32
from .. import arena_tiles as tiles
from ..diag_parity.kernel import scrub_tile


def _inject_scrub_kernel(words_ref, parity_ref, mask_ref,
                         out_w_ref, out_p_ref, stats_ref, scratch,
                         *, slopes: Tuple[int, ...]):
    mask = mask_ref[...]
    out_w, out_p, counts = scrub_tile(words_ref[...] ^ mask,  # the injection
                                      parity_ref[...], slopes, scratch)
    out_w_ref[...] = out_w
    out_p_ref[...] = out_p
    tiles.accumulate_stats(stats_ref, [popcount32(mask).sum()] + counts)


@functools.partial(jax.jit, static_argnames=("slopes", "rows", "interpret"))
def inject_scrub_kernel(words: jax.Array, parity: jax.Array, mask: jax.Array,
                        slopes: Tuple[int, ...] = (1, 2, -1),
                        rows: int = 512, interpret: bool = False):
    """Fused inject+scrub: words/mask (n_blocks / 4, 128) + parity
    (F, n_blocks) uint32 in the `arena_tiles` layout -> (corrected words,
    corrected parity, stats tile (8, 128) int32).

    stats row 0, lanes 0..3: injected, corrected, parity_fixed,
    uncorrectable, summed over the grid.  Requires slopes to contain the
    locating pair (1, 2).
    """
    assert 1 in slopes and 2 in slopes, slopes
    n_rows, F = words.shape[0], len(slopes)
    assert n_rows % rows == 0, (n_rows, rows)
    return pl.pallas_call(
        functools.partial(_inject_scrub_kernel, slopes=slopes),
        grid=(n_rows // rows,),
        in_specs=[tiles.words_spec(rows), tiles.parity_spec(F, rows),
                  tiles.words_spec(rows)],
        out_specs=[tiles.words_spec(rows), tiles.parity_spec(F, rows),
                   tiles.STATS_SPEC],
        out_shape=[jax.ShapeDtypeStruct(words.shape, jnp.uint32),
                   jax.ShapeDtypeStruct(parity.shape, jnp.uint32),
                   jax.ShapeDtypeStruct(tiles.STATS_SHAPE, jnp.int32)],
        scratch_shapes=[tiles.parity_scratch(rows)],
        compiler_params=tiles.SEQUENTIAL,
        interpret=interpret,
    )(words, parity, mask)
