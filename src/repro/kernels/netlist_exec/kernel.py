"""Levelized Min3 netlist executor — one launch, whole netlist, VMEM-resident.

The crossbar_nor kernel already bit-packs trials into uint32 lanes but still
walks the gate list one Min3 at a time (O(G) dynamic column loads).  This
kernel consumes the dense levelized schedule from core/scheduler.py instead:
a fori_loop over *levels* gathers each level's W input rows at once,
evaluates W Minority3 gates as three bitwise ops on a (W, tile_tw) tile,
applies the level's corruption masks, and commits the level with a single
contiguous dynamic_update_slice — the schedule renumbers wires so level l
owns rows [base + l*W, base + (l+1)*W) of the packed state.  O(depth) wide
steps instead of O(G) serial ones (HIPE-MAGIC's parallelism, DESIGN.md §11).

The packed wire state ((base + L*W) x tile_tw uint32) is the fori_loop
carry: it stays in VMEM/vector registers across ALL levels of a trial tile
and never round-trips through HBM between gates.  For the 32-bit MultPIM
multiplier that is ~41k rows x 8 words x 4B ~ 1.3 MB per tile — far under
the ~16 MB VMEM budget.  The grid tiles the packed-trial axis, so trial
tiles execute independently (the mMPU's row parallelism twice over: 32
trials per lane word, tile_tw words per grid step).

Fault injection is mask-based and sampled *outside* the kernel by the
faults.FaultModel packed-trial samplers (threefry, schedule-ordered by
core/scheduler.schedule_fault_masks): slot (l, s)'s fresh column corrupts
as (val & keep[l,s]) ^ flip[l,s], which keeps the kernel bit-exact against
the jnp levelized oracle and the lax.scan reference — fault streams
included.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# the level evaluator is shared with the jnp oracle — the kernel == level
# bit-identity rests on literally the same expression
from ...core.scheduler import min3_level as _min3_level


def _kernel(rows_ref, state_in_ref, state_out_ref, *,
            n_levels: int, base: int, width: int):
    def body(l, state):
        val = _min3_level(state, rows_ref[l])
        return jax.lax.dynamic_update_slice(
            state, val, (base + l * width, jnp.int32(0)))

    state_out_ref[...] = jax.lax.fori_loop(0, n_levels, body,
                                           state_in_ref[...])


def _xor_kernel(rows_ref, flip_ref, state_in_ref, state_out_ref, *,
                n_levels: int, base: int, width: int):
    def body(l, state):
        val = _min3_level(state, rows_ref[l]) ^ flip_ref[l]
        return jax.lax.dynamic_update_slice(
            state, val, (base + l * width, jnp.int32(0)))

    state_out_ref[...] = jax.lax.fori_loop(0, n_levels, body,
                                           state_in_ref[...])


def _inject_kernel(rows_ref, keep_ref, flip_ref, state_in_ref,
                   state_out_ref, *, n_levels: int, base: int, width: int):
    def body(l, state):
        val = (_min3_level(state, rows_ref[l]) & keep_ref[l]) ^ flip_ref[l]
        return jax.lax.dynamic_update_slice(
            state, val, (base + l * width, jnp.int32(0)))

    state_out_ref[...] = jax.lax.fori_loop(0, n_levels, body,
                                           state_in_ref[...])


@functools.partial(jax.jit, static_argnames=("base", "tile_tw", "interpret"))
def netlist_exec_kernel(rows_in: jax.Array, state: jax.Array,
                        keep: Optional[jax.Array] = None,
                        flip: Optional[jax.Array] = None, *, base: int,
                        tile_tw: int = 8, interpret: bool = False) -> jax.Array:
    """rows_in: (L, W, 3) int32 remapped input rows per level; state:
    (base + L*W, tw) uint32 trial-packed wire state (tw divisible by
    tile_tw); keep/flip: optional (L, W, tw) uint32 corruption masks
    (flip without keep = pure-XOR injection, e.g. single-fault planes).
    Returns the final state.
    """
    L, W, _ = rows_in.shape
    n_rows, tw = state.shape
    tile = min(tile_tw, tw)
    assert tw % tile == 0, (tw, tile)
    grid = tw // tile
    state_spec = pl.BlockSpec((n_rows, tile), lambda i: (0, i))
    rows_spec = pl.BlockSpec((L, W, 3), lambda i: (0, 0, 0))
    mask_spec = pl.BlockSpec((L, W, tile), lambda i: (0, 0, i))
    out_shape = jax.ShapeDtypeStruct((n_rows, tw), jnp.uint32)
    if flip is None:
        return pl.pallas_call(
            functools.partial(_kernel, n_levels=L, base=base, width=W),
            grid=(grid,),
            in_specs=[rows_spec, state_spec],
            out_specs=state_spec, out_shape=out_shape, interpret=interpret,
        )(rows_in, state)
    if keep is None:
        return pl.pallas_call(
            functools.partial(_xor_kernel, n_levels=L, base=base, width=W),
            grid=(grid,),
            in_specs=[rows_spec, mask_spec, state_spec],
            out_specs=state_spec, out_shape=out_shape, interpret=interpret,
        )(rows_in, flip, state)
    return pl.pallas_call(
        functools.partial(_inject_kernel, n_levels=L, base=base, width=W),
        grid=(grid,),
        in_specs=[rows_spec, mask_spec, mask_spec, state_spec],
        out_specs=state_spec, out_shape=out_shape, interpret=interpret,
    )(rows_in, keep, flip, state)
