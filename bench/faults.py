"""The fault regime of a cell: bit flips in the weights at prepare.

The program's `TransientBitFlips` draws one float32 uniform per bit, on a
2^-23 grid, so it cannot flip fewer than one bit in 2^23.  At that floor
a 7.6 GB store gets about 7,300 flips and, under Hsiao (39,32), about one
run in seventy leaves a word with two flips that the code detects but
cannot correct.  A cell whose outputs are checked cannot carry that, so
a bit here flips only where two independent uniforms both fall below
sqrt(p): the same i.i.d. process at a rate below the grid.
"""
from __future__ import annotations

import dataclasses
import math

import jax
from repro.faults.models import FaultModel, uniform


@dataclasses.dataclass(frozen=True)
class SparseBitFlips(FaultModel):
    """Each stored bit flips independently with probability `p_bit`."""

    p_bit: float = 0.0

    def bit_flips(self, key, shape, dt: float = 1.0, offset=None):
        q = math.sqrt(self.p_bit)
        k1, k2 = jax.random.split(key)
        return (uniform(k1, shape, offset) < q) & \
            (uniform(k2, shape, offset) < q)
