"""Public Hsiao SEC-DED ops: pad, tile and dispatch the Pallas kernels.

Same contract as kernels/diag_parity/ops.py over the packed arena —
flat uint32 buffers, (n_blocks, 7) parity tables, zero padding blocks
are syndrome-clean — so the scheme layer, sharding helper and backend
registry treat the two codes uniformly.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import arena_tiles as tiles
from .. import use_interpret
from .code import N_CHECKS
from .kernel import BLOCK, encode_hsiao_kernel, scrub_hsiao_kernel


def encode_hsiao(buf: jax.Array, block_rows: int = 512,
                 interpret: bool | None = None) -> jax.Array:
    """buf: flat uint32 buffer (length multiple of 32) ->
    (n_blocks, 7) parity words."""
    assert buf.ndim == 1 and buf.shape[0] % BLOCK == 0
    n = buf.shape[0] // BLOCK
    if n == 0:
        return jnp.zeros((0, N_CHECKS), jnp.uint32)
    rows, nb = tiles.plan(n, block_rows)
    out = encode_hsiao_kernel(
        tiles.words_view(buf, nb), rows=rows,
        interpret=use_interpret() if interpret is None else interpret)
    return tiles.parity_from_kernel(out, n)


def scrub(buf: jax.Array, parity: jax.Array, block_rows: int = 512,
          interpret: bool | None = None):
    """Fused scrub of a flat uint32 buffer against its Hsiao table.

    buf: (n_blocks * 32,) uint32; parity: (n_blocks, 7) uint32.
    Returns (corrected buf, corrected parity, counts) with counts a (3,)
    int32 vector: corrected, parity_fixed, uncorrectable — per word.
    """
    assert buf.ndim == 1 and buf.shape[0] % BLOCK == 0
    n = buf.shape[0] // BLOCK
    assert parity.shape == (n, N_CHECKS), (parity.shape, n)
    if n == 0:
        return buf, parity, jnp.zeros((3,), jnp.int32)
    rows, nb = tiles.plan(n, block_rows)
    fixed, par2, stats = scrub_hsiao_kernel(
        tiles.words_view(buf, nb), tiles.parity_to_kernel(parity, nb),
        rows=rows,
        interpret=use_interpret() if interpret is None else interpret)
    return (tiles.flat(fixed, n), tiles.parity_from_kernel(par2, n),
            stats[0, :3])


def scrub_sharded(buf: jax.Array, parity: jax.Array, block_rows: int = 512,
                  interpret: bool | None = None, *, mesh=None,
                  axes: Sequence[str] = ("copy", "data", "model"),
                  local_scrub: Optional[Callable] = None):
    """`scrub` with the arena block axis shard_map'd across `mesh` and the
    (3,) counts psum-reduced — the op is word-local, so per-shard launches
    compose exactly.  With mesh=None this IS `scrub`."""
    if local_scrub is None:
        def local_scrub(b, p):
            return scrub(b, p, block_rows=block_rows, interpret=interpret)
    if mesh is None:
        return local_scrub(buf, parity)
    from ..sharded import shard_scrub
    return shard_scrub(local_scrub, mesh, axes, buf, parity)
