"""Public ECC ops: pad, tile and dispatch the Pallas kernels.

`encode_parity` is the protect/refresh path; `scrub` is the fused
encode->syndrome->locate->correct pass.  Both take a flat uint32 buffer
(the packed arena of core/arena.py) so the whole parameter pytree is one
launch, laid out for the kernels by kernels/arena_tiles.py.  Padding
blocks are zero words with zero parity — their syndrome is identically
clean, so they never contribute to the stats.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import arena_tiles as tiles
from .. import use_interpret
from .kernel import BLOCK, encode_parity_kernel, scrub_kernel


def encode_parity(buf: jax.Array, slopes: Tuple[int, ...] = (1, 2, -1),
                  block_rows: int = 512,
                  interpret: bool | None = None) -> jax.Array:
    """buf: flat uint32 buffer (length multiple of 32) ->
    (n_blocks, len(slopes)) parity words."""
    assert buf.ndim == 1 and buf.shape[0] % BLOCK == 0
    n = buf.shape[0] // BLOCK
    if n == 0:
        return jnp.zeros((0, len(slopes)), jnp.uint32)
    rows, nb = tiles.plan(n, block_rows)
    out = encode_parity_kernel(
        tiles.words_view(buf, nb), slopes=tuple(slopes), rows=rows,
        interpret=use_interpret() if interpret is None else interpret)
    return tiles.parity_from_kernel(out, n)


def scrub(buf: jax.Array, parity: jax.Array,
          slopes: Tuple[int, ...] = (1, 2, -1), block_rows: int = 512,
          interpret: bool | None = None):
    """Fused scrub of a flat uint32 buffer against its parity table.

    buf: (n_blocks * 32,) uint32; parity: (n_blocks, len(slopes)) uint32.
    Returns (corrected buf, corrected parity, counts) with counts a (3,)
    int32 vector: corrected, parity_fixed, uncorrectable.
    """
    assert buf.ndim == 1 and buf.shape[0] % BLOCK == 0
    n = buf.shape[0] // BLOCK
    assert parity.shape == (n, len(slopes)), (parity.shape, n)
    if n == 0:
        return buf, parity, jnp.zeros((3,), jnp.int32)
    rows, nb = tiles.plan(n, block_rows)
    fixed, par2, stats = scrub_kernel(
        tiles.words_view(buf, nb), tiles.parity_to_kernel(parity, nb),
        slopes=tuple(slopes), rows=rows,
        interpret=use_interpret() if interpret is None else interpret)
    return (tiles.flat(fixed, n), tiles.parity_from_kernel(par2, n),
            stats[0, :3])


def scrub_sharded(buf: jax.Array, parity: jax.Array,
                  slopes: Tuple[int, ...] = (1, 2, -1), block_rows: int = 512,
                  interpret: bool | None = None, *, mesh=None,
                  axes: Sequence[str] = ("copy", "data", "model"),
                  local_scrub: Optional[Callable] = None):
    """`scrub` with the arena block axis shard_map'd across `mesh` and the
    (3,) counts psum-reduced (DESIGN.md §14).  Bit-exact vs `scrub` — the
    op is block-local, so per-shard launches compose exactly.  With
    mesh=None (or a 1-device mesh) this IS `scrub`.  `local_scrub`
    overrides the per-shard op (backend registry passes the jnp oracle)."""
    if local_scrub is None:
        def local_scrub(b, p):
            return scrub(b, p, slopes=tuple(slopes), block_rows=block_rows,
                         interpret=interpret)
    if mesh is None:
        return local_scrub(buf, parity)
    from ..sharded import shard_scrub
    return shard_scrub(local_scrub, mesh, axes, buf, parity)
