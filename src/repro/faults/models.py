"""Unified fault-model taxonomy (DESIGN.md §10).

The paper evaluates reliability along two axes: short-term soft errors
(transient gate/bit flips, §II-B) and long-term degradation of large-scale
applications (permanent defects and retention drift, §VI).  Every error
process the repo simulates is expressed here as a `FaultModel` — a frozen
dataclass whose samplers are *pure functions of (key, shape, dt)*, so a
fault stream is fully determined by its PRNG key: campaigns replay
deterministically, disjoint keys give independent streams, and the samplers
vmap over a batch of trial keys without host-side state.

Three corruption surfaces, one model object:

* boolean state (crossbar cells, netlist gate outputs):
  `bit_flips(key, shape, dt)` / `corrupt_bits(bits, key, dt)`;
* packed uint32 words (the ECC arena of core/arena.py):
  `word_mask(key, words, dt)` / `corrupt_words(words, key, dt)` — the XOR
  mask feeds the fused inject+scrub kernel (kernels/inject_scrub/);
* parameter pytrees: `corrupt(params, key, dt)` (the canonical home of the
  former `core.reliability.inject_bit_flips`).

`dt` is the length of the exposure interval in model time units; transient
and drift models scale their per-interval flip probability as
1 - (1 - p)^dt, permanent stuck-at masks are dt-invariant (the defect is a
property of the device, not of the interval).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.extend.random import threefry2x32_p

from ..core import arena
from ..core.bitops import PACK, pack_trials

__all__ = ["FaultModel", "TransientBitFlips", "TransientGateFaults",
           "StuckAtFaults", "RetentionDrift", "CompositeFault",
           "inject_bit_flips", "pack_flip_mask", "corrupt_leaf", "uniform"]

BLOCK = arena.BLOCK


def _p_interval(p: float, dt: float) -> float:
    """Per-interval flip probability for a per-unit-time rate p over dt."""
    if dt == 1.0 or p <= 0.0:
        return p
    if p >= 1.0:
        return 1.0
    return -math.expm1(dt * math.log1p(-p))


def uniform(key: jax.Array, shape: Tuple[int, ...],
            offset: Optional[jax.Array] = None) -> jax.Array:
    """`jax.random.uniform(key, shape)` — or, with `offset`, rows
    ``[offset, offset + shape[0])`` of ``jax.random.uniform(key, (n,) +
    shape[1:])`` for any n, bit for bit.

    Threefry's counter-based bits depend only on an element's flat index,
    so rows of one large draw can be made slab by slab: a big leaf is
    corrupted a slab at a time with exactly the draws of one whole-leaf
    call, and no whole-leaf temporary is ever built.  Rows must hold a
    power-of-two number of elements (a word mask's rows hold 32 bits)."""
    if offset is None:
        return jax.random.uniform(key, shape)
    assert jax.config.jax_threefry_partitionable, \
        "row-offset draws need the counter-based threefry bits"
    row = math.prod(shape[1:])
    shift = row.bit_length() - 1
    assert row == 1 << shift, shape
    if jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    r = jnp.asarray(offset, jnp.uint32) \
        + jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    within = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for d in range(len(shape) - 1, 0, -1):
        within = within + jax.lax.broadcasted_iota(jnp.uint32, shape, d) \
            * jnp.uint32(stride)
        stride *= shape[d]
    # 64-bit flat index r * row + within, as the (hi, lo) counter pair
    hi = r >> (32 - shift) if shift else jnp.zeros(shape, jnp.uint32)
    lo = (r << shift) | within
    b1, b2 = threefry2x32_p.bind(key[0], key[1], hi, lo)
    mant = ((b1 ^ b2) >> 9) | jnp.uint32(0x3F800000)     # as in uniform
    return jax.lax.bitcast_convert_type(mant, jnp.float32) - 1.0


def _bernoulli(key, p: float, shape, offset=None) -> jax.Array:
    if offset is None:
        return jax.random.bernoulli(key, p, shape)
    return uniform(key, shape, offset) < jnp.float32(p)


def pack_flip_mask(flips: jax.Array) -> jax.Array:
    """Pack a (..., 32) bool flip plane into a (...,) uint32 XOR mask."""
    shifts = jnp.arange(BLOCK, dtype=jnp.uint32)
    return (flips.astype(jnp.uint32) << shifts).sum(axis=-1, dtype=jnp.uint32)


class FaultModel:
    """Abstract error process.  Subclasses are frozen dataclasses (hashable,
    usable as static jit arguments); all sampling is keyed and pure."""

    @property
    def permanent(self) -> bool:
        """True when the model describes a fixed device property (defect
        maps) rather than an exposure process: consumers that corrupt
        repeatedly (e.g. once per training step) must then reuse a stable
        key instead of re-keying per interval, or the 'permanent' defects
        would relocate every draw."""
        return False

    # -- boolean-state surface ------------------------------------------------
    def bit_flips(self, key: jax.Array, shape: Tuple[int, ...],
                  dt: float = 1.0, offset=None) -> jax.Array:
        """Bool XOR plane: True where a stored bit flips during dt.  With
        `offset`, rows offset.. of a larger plane (see `uniform`)."""
        raise NotImplementedError(
            f"{type(self).__name__} is data-dependent; use corrupt_bits")

    def corrupt_bits(self, bits: jax.Array, key: jax.Array,
                     dt: float = 1.0) -> jax.Array:
        return jnp.logical_xor(bits, self.bit_flips(key, bits.shape, dt))

    # -- packed-word surface (ECC arena) --------------------------------------
    def word_mask(self, key: jax.Array, words: jax.Array,
                  dt: float = 1.0, offset=None) -> jax.Array:
        """uint32 XOR mask over `words` (may inspect the data for stuck-at).
        With `offset`, `words` are words offset.. of a longer buffer and
        the mask is that buffer's mask over them."""
        return pack_flip_mask(self.bit_flips(key, words.shape + (BLOCK,), dt,
                                             offset))

    def corrupt_words(self, words: jax.Array, key: jax.Array,
                      dt: float = 1.0, offset=None) -> jax.Array:
        return words ^ self.word_mask(key, words, dt, offset)

    # -- packed-trial surface (netlist execution engines) ----------------------
    def gate_lane_masks(self, key: jax.Array, trials: int,
                        dt: float = 1.0) -> Tuple[jax.Array, jax.Array]:
        """Per-gate corruption as lane masks over trial-packed words.

        Any single-bit boolean corruption is affine per lane, so one gate's
        output column packed 32-trials-per-word (core/bitops.pack_trials
        layout) corrupts as ``(val & keep) ^ flip``.  Returns
        (keep, flip) uint32 (ceil(trials/32),), bit-exact against
        ``corrupt_bits`` on the unpacked (trials,) plane under the same key
        — the levelized/kernel netlist engines stay stream-identical to the
        lax.scan reference.  Padding lanes are don't-care (their trials are
        discarded on unpack).
        """
        flip = pack_trials(self.bit_flips(key, (trials,), dt))
        return jnp.full_like(flip, jnp.uint32(0xFFFFFFFF)), flip

    # -- pytree surface -------------------------------------------------------
    def corrupt(self, params: Any, key: jax.Array, dt: float = 1.0) -> Any:
        """Corrupt every leaf's stored bits (via the arena word view)."""
        leaves, treedef = jax.tree.flatten(params)
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten([corrupt_leaf(x, k, self, dt)
                                  for x, k in zip(leaves, keys)])


@functools.partial(jax.jit, static_argnums=(2, 3))
def corrupt_leaf(x: jax.Array, key: jax.Array, model: FaultModel,
                 dt: float = 1.0) -> jax.Array:
    """One leaf's stored bits through its arena word view, as one compiled
    program that walks the leaf in slabs (`arena.map_leaf_words`): the
    (words, 32) flip plane is packed into the XOR mask slab by slab and
    never reaches device memory.  The draws are those of
    `model.corrupt_words` on the whole leaf's words."""
    def corrupt(words, offset):
        return model.corrupt_words(words, key, dt, offset=offset), ()
    return arena.map_leaf_words(x, corrupt)[0]


@dataclasses.dataclass(frozen=True)
class TransientBitFlips(FaultModel):
    """Indirect soft errors: each stored bit flips i.i.d. w.p. p_bit per
    interval (read disturb / access corruption, paper §II-B)."""

    p_bit: float = 0.0

    def bit_flips(self, key, shape, dt: float = 1.0, offset=None):
        return _bernoulli(key, _p_interval(self.p_bit, dt), shape, offset)


@dataclasses.dataclass(frozen=True)
class TransientGateFaults(FaultModel):
    """Direct soft errors: a stateful gate writes the wrong output w.p.
    p_gate per evaluation (independently per row/column, paper §II-B)."""

    p_gate: float = 0.0

    def bit_flips(self, key, shape, dt: float = 1.0, offset=None):
        return _bernoulli(key, _p_interval(self.p_gate, dt), shape, offset)


@dataclasses.dataclass(frozen=True)
class StuckAtFaults(FaultModel):
    """Permanent defects: each cell is stuck-at-0 w.p. p_stuck0 and
    stuck-at-1 w.p. p_stuck1 (disjoint events).  The defect map is a pure
    function of the key and ignores dt — the same key always yields the
    same mask, so repeated corruption is idempotent."""

    p_stuck0: float = 0.0
    p_stuck1: float = 0.0

    @property
    def permanent(self) -> bool:
        return True

    def stuck_masks(self, key: jax.Array, shape: Tuple[int, ...],
                    offset=None):
        """(sa0, sa1) bool defect maps; disjoint by construction."""
        u = uniform(key, shape, offset)
        sa0 = u < self.p_stuck0
        sa1 = (u >= self.p_stuck0) & (u < self.p_stuck0 + self.p_stuck1)
        return sa0, sa1

    def corrupt_bits(self, bits, key, dt: float = 1.0):
        sa0, sa1 = self.stuck_masks(key, bits.shape)
        return (bits & ~sa0) | sa1

    def word_mask(self, key, words, dt: float = 1.0, offset=None):
        sa0, sa1 = self.stuck_masks(key, words.shape + (BLOCK,), offset)
        sa0w, sa1w = pack_flip_mask(sa0), pack_flip_mask(sa1)
        return (words & sa0w) | (~words & sa1w)

    def gate_lane_masks(self, key, trials: int, dt: float = 1.0):
        # (v & ~sa0) | sa1 == (v & ~(sa0|sa1)) ^ sa1 — sa0/sa1 are disjoint
        sa0, sa1 = self.stuck_masks(key, (trials,))
        sa1w = pack_trials(sa1)
        return ~(pack_trials(sa0) | sa1w), sa1w


@dataclasses.dataclass(frozen=True)
class RetentionDrift(FaultModel):
    """Time-dependent conductance drift (the paper's long-term axis): a
    stored bit decays w.p. 1 - (1 - p_unit)^dt over an interval of length
    dt — the continuous-time process behind `Crossbar.drift`."""

    p_unit: float = 0.0

    def bit_flips(self, key, shape, dt: float = 1.0, offset=None):
        return _bernoulli(key, _p_interval(self.p_unit, dt), shape, offset)


@dataclasses.dataclass(frozen=True)
class CompositeFault(FaultModel):
    """Sequential composition: each member corrupts with an independent
    subkey (e.g. drift + stuck-at defects in one campaign scenario)."""

    models: Tuple[FaultModel, ...] = ()

    @property
    def permanent(self) -> bool:
        return bool(self.models) and all(m.permanent for m in self.models)

    def corrupt_bits(self, bits, key, dt: float = 1.0):
        for m, k in zip(self.models, jax.random.split(key, len(self.models))):
            bits = m.corrupt_bits(bits, k, dt)
        return bits

    def corrupt_words(self, words, key, dt: float = 1.0, offset=None):
        for m, k in zip(self.models, jax.random.split(key, len(self.models))):
            words = m.corrupt_words(words, k, dt, offset)
        return words

    def word_mask(self, key, words, dt: float = 1.0, offset=None):
        return self.corrupt_words(words, key, dt, offset) ^ words

    def gate_lane_masks(self, key, trials: int, dt: float = 1.0):
        # lanewise affine composition: f2(f1(v)) with f = (v & K) ^ F gives
        # K = K1 & K2, F = (F1 & K2) ^ F2 — same member order and key split
        # as corrupt_bits, so the packed stream matches the scan reference.
        keep = jnp.full((-(-trials // PACK),), 0xFFFFFFFF, jnp.uint32)
        flip = jnp.zeros_like(keep)
        for m, k in zip(self.models, jax.random.split(key, len(self.models))):
            k2, f2 = m.gate_lane_masks(k, trials, dt)
            keep = keep & k2
            flip = (flip & k2) ^ f2
        return keep, flip


def inject_bit_flips(params: Any, key: jax.Array, p_bit: float) -> Any:
    """Canonical transient injector: flip each stored bit w.p. p_bit.

    Draw-compatible with the historic `core.reliability.inject_bit_flips`
    (same per-leaf key split, same Bernoulli plane, same packing).
    """
    return TransientBitFlips(p_bit).corrupt(params, key)
