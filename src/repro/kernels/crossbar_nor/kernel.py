"""In-VMEM Min3 netlist interpreter — the mMPU stateful-logic hot loop.

The crossbar's row parallelism maps to *bit-packing*: 32 independent trials
(crossbar rows) live in the bit lanes of one uint32, and a tile of
`tw` packed words executes the same gate simultaneously — exactly the
"same gate, every row, one cycle" semantics of MAGIC/FELIX (paper §II-A).

The whole wire state (tw x n_wires uint32) stays resident in VMEM while a
fori_loop walks the gate list (dynamic column loads/stores); for a 32-bit
MultPIM multiplier that is 8 x ~14k x 4B ~ 0.5 MB — far under the ~16 MB
VMEM budget, so the interpreter never touches HBM between gates.  On real
TPU the gate list would be scalar-prefetched into SMEM; in this repo it is
a VMEM operand (works in both interpret and compiled modes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(gates_ref, state_in_ref, state_ref, *, n_gates: int):
    state_ref[...] = state_in_ref[...]

    def body(g, carry):
        row = gates_ref[g]                     # (4,) int32: in1, in2, in3, out
        a = state_ref[:, pl.ds(row[0], 1)]
        b = state_ref[:, pl.ds(row[1], 1)]
        c = state_ref[:, pl.ds(row[2], 1)]
        maj = (a & b) | (b & c) | (a & c)
        state_ref[:, pl.ds(row[3], 1)] = ~maj
        return carry

    jax.lax.fori_loop(0, n_gates, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def netlist_kernel(gates: jax.Array, state: jax.Array,
                   interpret: bool = False) -> jax.Array:
    """gates: (G, 4) int32 Min3 netlist; state: (tw, n_wires) uint32 packed
    trials.  Returns the final wire state."""
    G = gates.shape[0]
    tw, n_wires = state.shape
    return pl.pallas_call(
        functools.partial(_kernel, n_gates=G),
        grid=(1,),
        in_specs=[pl.BlockSpec((G, 4), lambda i: (0, 0)),
                  pl.BlockSpec((tw, n_wires), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((tw, n_wires), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((tw, n_wires), jnp.uint32),
        interpret=interpret,
    )(gates, state)
