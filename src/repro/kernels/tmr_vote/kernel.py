"""Per-bit 2-of-3 majority vote kernel (paper §V).

The Minority3 stateful gate voting, as bitwise ops on packed words:
out = (a & b) | (b & c) | (a & c) corrects any single corrupted copy per
bit.  Tiled (block_m, 128)-aligned for the VPU; one fused pass, three
streams in, one out — the kernel is purely memory-bound, which is exactly
the paper's point: voting at the full bandwidth of the substrate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, b_ref, c_ref, o_ref):
    a, b, c = a_ref[...], b_ref[...], c_ref[...]
    o_ref[...] = (a & b) | (b & c) | (a & c)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def vote_kernel(a: jax.Array, b: jax.Array, c: jax.Array,
                block_m: int = 256, block_n: int = 512,
                interpret: bool = False) -> jax.Array:
    """a/b/c: (M, N) uint32 -> per-bit majority (M, N)."""
    M, N = a.shape
    bm, bn = min(block_m, M), min(block_n, N)
    assert M % bm == 0 and N % bn == 0, (a.shape, bm, bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        _kernel,
        grid=(M // bm, N // bn),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        interpret=interpret,
    )(a, b, c)
