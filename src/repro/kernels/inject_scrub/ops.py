"""Public fused inject+scrub op: pad, tile and dispatch the Pallas kernel.

Takes the flat uint32 arena (core/arena.py), its parity table and an XOR
fault mask of the same length (sampled by a faults.models.FaultModel), so a
whole trial interval — corrupt every block, then scrub every block — is ONE
launch.  Padding blocks carry zero words, zero parity and zero mask: their
syndrome is identically clean and they contribute nothing to the stats.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import arena_tiles as tiles
from .. import use_interpret
from .kernel import inject_scrub_kernel

BLOCK = tiles.BLOCK


def inject_scrub(buf: jax.Array, parity: jax.Array, mask: jax.Array,
                 slopes: Tuple[int, ...] = (1, 2, -1), block_rows: int = 512,
                 interpret: bool | None = None):
    """Fused corrupt+scrub of a flat uint32 buffer against its parity table.

    buf, mask: (n_blocks * 32,) uint32; parity: (n_blocks, len(slopes)).
    Returns (corrected buf, corrected parity, counts) with counts a (4,)
    int32 vector: injected, corrected, parity_fixed, uncorrectable.
    """
    assert buf.ndim == 1 and buf.shape[0] % BLOCK == 0
    assert mask.shape == buf.shape, (mask.shape, buf.shape)
    n = buf.shape[0] // BLOCK
    assert parity.shape == (n, len(slopes)), (parity.shape, n)
    if n == 0:
        return buf, parity, jnp.zeros((4,), jnp.int32)
    rows, nb = tiles.plan(n, block_rows)
    fixed, par2, stats = inject_scrub_kernel(
        tiles.words_view(buf, nb), tiles.parity_to_kernel(parity, nb),
        tiles.words_view(mask, nb), slopes=tuple(slopes), rows=rows,
        interpret=use_interpret() if interpret is None else interpret)
    return (tiles.flat(fixed, n), tiles.parity_from_kernel(par2, n),
            stats[0, :4])


def inject_scrub_sharded(buf: jax.Array, parity: jax.Array, mask: jax.Array,
                         slopes: Tuple[int, ...] = (1, 2, -1),
                         block_rows: int = 512,
                         interpret: bool | None = None,
                         *, mesh=None,
                         axes: Sequence[str] = ("copy", "data", "model"),
                         local_op: Optional[Callable] = None):
    """`inject_scrub` with the arena block axis shard_map'd across `mesh`
    and the (4,) counts psum-reduced (DESIGN.md §14).  The mask shards with
    the buffer, so each shard corrupts and repairs only the blocks it owns;
    bit-exact vs `inject_scrub`.  With mesh=None this IS `inject_scrub`."""
    if local_op is None:
        def local_op(b, p, m):
            return inject_scrub(b, p, m, slopes=tuple(slopes),
                                block_rows=block_rows, interpret=interpret)
    if mesh is None:
        return local_op(buf, parity, mask)
    from ..sharded import shard_scrub
    return shard_scrub(local_op, mesh, axes, buf, parity, mask)
