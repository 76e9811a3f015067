"""Lane-dense tiling of the packed arena for the ECC kernels.

The arena (core/arena.py) is a flat uint32 buffer of 32-word blocks.  Seen
as rows of 128 words — a free reshape of the flat buffer — each row holds
four consecutive blocks.  A kernel tile of `rows` such rows is transposed
in VMEM to (128, rows); its 32-row slice q holds block 4r + q of the tile
in lane r.  Words then run down the sublanes and blocks across the lanes,
so every XOR tree, syndrome and correction is a full-width vector op, and
no operand is lane-padded in HBM (a (n_blocks, 32) view pads each row to
128 lanes, 4x the arena, and a (n_blocks, F) parity view 128/F x).

The parity table (n_blocks, F) reaches the kernels transposed, as
(F, n_blocks) — the layout XLA already stores it in.  Inside a kernel a
(F, 4 * rows) parity tile is split into the four planes' (F, rows) parity
by a transpose and stride-4 sublane reads of a small VMEM scratch, and
joined back the same way, so the table keeps its natural block order.

The per-launch counters live in one (8, 128) int32 tile that every grid
step maps to, so it stays resident in VMEM and accumulates: counter k is
row 0, lane k.
"""
from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 32                 # words per ECC block
LANES = 128                # words per row of the flat arena view
SEGS = LANES // BLOCK      # blocks per row
MIN_ROWS = 128             # transposed tiles need a lane-dense row count
PARITY_ROWS = 8            # parity families padded to one sublane tile
STATS_SHAPE = (8, 128)

#: the stats tile accumulates across grid steps, so the grid runs in order
SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
STATS_SPEC = pl.BlockSpec(STATS_SHAPE, lambda i: (0, 0))


def plan(n_blocks: int, max_rows: int = 512) -> Tuple[int, int]:
    """(rows per tile, padded block count) for an arena of `n_blocks`.
    Picks the largest power-of-two row count in [MIN_ROWS, max_rows] whose
    tile divides the arena, so large arenas are never copied to pad them;
    otherwise pads to whole tiles of the largest row count that the arena
    fills at least once."""
    rows = max_rows
    while rows >= MIN_ROWS:
        if n_blocks % (SEGS * rows) == 0:
            return rows, n_blocks
        rows //= 2
    rows = max_rows
    while rows > MIN_ROWS and n_blocks < SEGS * rows:
        rows //= 2
    tile = SEGS * rows
    return rows, -(-n_blocks // tile) * tile


def words_view(buf: jax.Array, n_blocks: int) -> jax.Array:
    """Flat (n * 32,) uint32 -> (n_blocks * 32 / 128, 128), zero-padded
    to `n_blocks` (zero blocks with zero parity are syndrome-clean)."""
    pad = n_blocks * BLOCK - buf.shape[0]
    if pad:
        buf = jnp.pad(buf, (0, pad))
    return buf.reshape(-1, LANES)


def flat(words: jax.Array, n: int) -> jax.Array:
    """Inverse of `words_view`: the first `n` blocks as a flat buffer."""
    return words.reshape(-1)[:n * BLOCK]


def parity_to_kernel(parity: jax.Array, n_blocks: int) -> jax.Array:
    """(n, F) parity table -> the kernels' (F, n_blocks) operand."""
    n = parity.shape[0]
    if n_blocks > n:
        parity = jnp.pad(parity, ((0, n_blocks - n), (0, 0)))
    return parity.T


def parity_from_kernel(pk: jax.Array, n: int) -> jax.Array:
    """Inverse of `parity_to_kernel`: the first `n` rows of the table."""
    return pk.T[:n]


def words_spec(rows: int) -> pl.BlockSpec:
    return pl.BlockSpec((rows, LANES), lambda i: (i, 0))


def parity_spec(f: int, rows: int) -> pl.BlockSpec:
    return pl.BlockSpec((f, SEGS * rows), lambda i: (0, i))


def tile_blocks(x: jax.Array) -> List[jax.Array]:
    """(rows, 128) words -> SEGS planes of (32, rows): plane q, sublane i,
    lane r is word i of tile block 4r + q."""
    xt = x.T
    return [xt[BLOCK * q:BLOCK * (q + 1)] for q in range(SEGS)]


def untile_blocks(planes: Sequence[jax.Array]) -> jax.Array:
    """Inverse of `tile_blocks`."""
    return jnp.concatenate(planes, axis=0).T


def parity_scratch(rows: int):
    """VMEM scratch for `parity_planes` / `join_parity`."""
    return pltpu.VMEM((SEGS * rows, PARITY_ROWS), jnp.uint32)


def _pad_rows(x: jax.Array) -> jax.Array:
    f = x.shape[0]
    assert f <= PARITY_ROWS, f
    if f == PARITY_ROWS:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((PARITY_ROWS - f, x.shape[1]), x.dtype)], axis=0)


def parity_planes(p: jax.Array, scratch) -> List[jax.Array]:
    """(F, SEGS * rows) parity tile in block order -> SEGS planes of
    (F, rows), aligned with `tile_blocks`."""
    f, rows = p.shape[0], p.shape[1] // SEGS
    scratch[...] = _pad_rows(p).T
    return [scratch[pl.ds(q, rows, stride=SEGS), :].T[:f]
            for q in range(SEGS)]


def join_parity(planes: Sequence[jax.Array], scratch) -> jax.Array:
    """Inverse of `parity_planes`."""
    f, rows = planes[0].shape
    for q, pq in enumerate(planes):
        scratch[pl.ds(q, rows, stride=SEGS), :] = _pad_rows(pq).T
    return scratch[...].T[:f]


def scrub_planes(x: jax.Array, p: jax.Array, body: Callable, scratch):
    """Run a code's fused scrub ``body(w, p) -> (w', p', *flags)`` over the
    SEGS planes of one (rows, 128) word tile and its (F, SEGS * rows)
    parity tile.  Returns (words, parity, [int32 count of each flag])."""
    out_w, out_p, counts = [], [], None
    for w, pq in zip(tile_blocks(x), parity_planes(p, scratch)):
        ow, op, *flags = body(w, pq)
        out_w.append(ow)
        out_p.append(op)
        c = [f.astype(jnp.int32).sum() for f in flags]
        counts = c if counts is None else [a + b for a, b in zip(counts, c)]
    return untile_blocks(out_w), join_parity(out_p, scratch), counts


def xor_rows(x: jax.Array) -> jax.Array:
    """XOR of the 32 rows of x (32, n) -> (1, n), as a halving tree."""
    n = x.shape[0]
    while n > 1:
        n //= 2
        x = x[:n] ^ x[n:2 * n]
    return x


def accumulate_stats(stats_ref, counts) -> None:
    """Add this grid step's int32 counters into the resident stats tile
    (zeroed at step 0): counter k goes to row 0, lane k."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        stats_ref[...] = jnp.zeros(STATS_SHAPE, jnp.int32)

    row = jax.lax.broadcasted_iota(jnp.int32, STATS_SHAPE, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, STATS_SHAPE, 1)
    tile = jnp.zeros(STATS_SHAPE, jnp.int32)
    for k, c in enumerate(counts):
        tile = jnp.where((row == 0) & (lane == k), c, tile)
    stats_ref[...] += tile
