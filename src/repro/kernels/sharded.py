"""shard_map wrapper for the arena-wide scrub ops (DESIGN.md §14).

The packed arena is a flat uint32 buffer of 32-word ECC blocks, and every
scrub op is *block-local*: block i's syndrome depends only on block i's
words and parity row.  So sharding the block axis across the whole mesh and
running the single-device op per shard is exactly the single-device result
— no halo, no re-tiling — and the (3,)/(4,) int32 stat vectors sum exactly
under `psum`.  `check_vma=False` is required because pallas_call has no
replication rule; correctness is carried by the block-locality argument
above, not by shard_map's rep checker.
"""
from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .diag_parity.kernel import BLOCK

__all__ = ["shard_scrub", "scrub_axes"]


def scrub_axes(mesh: Mesh, axes: Sequence[str] = ("copy", "data", "model"),
               ) -> Tuple[str, ...]:
    """Mesh axes the arena block dim shards over: every axis the mesh
    actually has, so the scrub uses the whole machine.  The copy axis is
    included because scrubbing is state maintenance, not computation — the
    three TMR copies hold *different* corrupted state, each scrubbed where
    it lives."""
    return tuple(a for a in axes if a in mesh.axis_names)


def shard_scrub(local_fn: Callable, mesh: Mesh, axes: Sequence[str],
                buf: jax.Array, parity: jax.Array, *flat_extra: jax.Array):
    """Run a block-local scrub op shard-wise over the arena block axis.

    local_fn(buf_shard, parity_shard, *extra_shards) -> (fixed, parity',
    counts) with counts a 1-D int32 vector; `flat_extra` are flat buffers
    sharded like `buf` (e.g. the inject mask).  Blocks are zero-padded to a
    multiple of the shard count — zero words with zero parity are
    syndrome-clean, so padding never perturbs the stats.
    """
    axes = scrub_axes(mesh, axes)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    if n_shards <= 1:
        return local_fn(buf, parity, *flat_extra)
    nb = parity.shape[0]
    pad_b = (-nb) % n_shards
    if pad_b:
        buf = jnp.pad(buf, (0, pad_b * BLOCK))
        parity = jnp.pad(parity, ((0, pad_b), (0, 0)))
        flat_extra = tuple(jnp.pad(x, (0, pad_b * BLOCK)) for x in flat_extra)
    axspec = axes if len(axes) > 1 else axes[0]

    def local(b, p, *ex):
        fixed, par2, counts = local_fn(b, p, *ex)
        return fixed, par2, jax.lax.psum(counts, axes)

    fixed, par2, counts = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axspec), P(axspec)) + (P(axspec),) * len(flat_extra),
        out_specs=(P(axspec), P(axspec), P()),
        check_vma=False)(buf, parity, *flat_extra)
    return fixed[:nb * BLOCK], par2[:nb], counts
