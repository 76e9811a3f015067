"""TPU adaptation of the paper's reliability mechanisms (DESIGN.md §3).

The diagonal-parity code is re-tiled for the TPU memory hierarchy: instead of
an m x m crossbar block of memristors, a block is **32 consecutive uint32
words** of an HBM-resident parameter buffer — a 32 x 32 bit matrix whose
rows are words and whose columns are bit lanes.  The key identity:

    parity word of slope s over block W[0..31]  =  XOR_i rotl32(W[i], s*i)

i.e. bit k of the parity word is XOR_i W[i][(k - s*i) mod 32] — exactly the
paper's wrap-around diagonal, with the 32-bit *rotate playing the role of the
barrel shifter*.  Both "row" updates (a whole word rewritten) and "column"
updates (one bit lane across words, e.g. a sign-bit flip pattern) update the
parity in O(1) vector ops, preserving the paper's central property.

Families: slopes (1, 2, -1): (1,2) locate a single flipped bit per block
(gcd(2-1,32)=1); (-1) is the paper's counter-diagonal, kept as an integrity
check (see DESIGN.md §8).  Storage overhead = 3/32 ~ 9.4%.

`ReliableStore` wraps a parameter pytree.  The pytree is flattened into the
packed arena of core/arena.py — one contiguous uint32 buffer with every leaf
block-aligned — so protect, scrub and refresh are each ONE fused Pallas
launch over the whole model (DESIGN.md §9) instead of a per-leaf Python
loop.  `scrub()` verifies and corrects bit flips between training steps (SDC
defense) and reports uncorrectable blocks so the runtime can trigger a
checkpoint restore — connecting the paper's mechanism to large-scale fault
tolerance.  The pure-jnp word functions (`encode_words`, `correct_words`)
are retained both as the kernels' bit-exact oracle and as the
`backend="jnp"` fallback.

NOTE (DESIGN.md §12): the public protection API is now
`repro.reliability` — `DiagParityEcc()` wraps this module's machinery
behind the composable `Scheme` protocol and the backend registry.
`ReliableStore` and `tmr_serve` remain as bit-exact building blocks /
deprecation shims.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import arena
from .bitops import bit_position, popcount32, rotl32

__all__ = ["WordEccConfig", "encode_words", "syndrome_words", "correct_words",
           "ReliableStore", "ScrubReport", "tmr_serve",
           "protect_leaves", "scrub_leaves"]

BLOCK = arena.BLOCK  # words per block == bits per word


@dataclasses.dataclass(frozen=True)
class WordEccConfig:
    slopes: Tuple[int, ...] = (1, 2, -1)

    @property
    def n_parity_words(self) -> int:
        return len(self.slopes)


def _as_blocks(words: jax.Array) -> jax.Array:
    assert words.ndim == 1 and words.shape[0] % BLOCK == 0
    return words.reshape(-1, BLOCK)


def encode_words(words: jax.Array, cfg: WordEccConfig = WordEccConfig()) -> jax.Array:
    """Parity words for a flat uint32 buffer: (n_blocks, n_families).

    parity[b, f] = XOR_i rotl32(words[b*32 + i], slopes[f] * i)
    """
    blocks = _as_blocks(words)                              # (B, 32)
    i = jnp.arange(BLOCK, dtype=jnp.int32)
    outs = []
    for s in cfg.slopes:
        rot = rotl32(blocks, (s * i) % BLOCK)               # (B, 32)
        acc = rot[:, 0]
        for t in range(1, BLOCK):
            acc = acc ^ rot[:, t]
        outs.append(acc)
    return jnp.stack(outs, axis=-1)                         # (B, F)


def syndrome_words(words: jax.Array, parity: jax.Array,
                   cfg: WordEccConfig = WordEccConfig()) -> jax.Array:
    return encode_words(words, cfg) ^ parity


class ScrubReport(NamedTuple):
    corrected: jax.Array        # int32: blocks with a single bit corrected
    parity_fixed: jax.Array     # int32: blocks where a check word was fixed
    uncorrectable: jax.Array    # int32: blocks with >= 2 errors


def correct_words(words: jax.Array, parity: jax.Array,
                  cfg: WordEccConfig = WordEccConfig()):
    """Locate and correct one flipped bit per 32-word block.

    For an error in data word i0, bit j0: family-s syndrome is one-hot with
    hot bit k_s = (j0 + s*i0) mod 32 (rotl by s*i moves bit j to j + s*i).
    With slopes (1,2): i0 = k_2 - k_1, j0 = k_1 - i0 (mod 32).
    """
    slopes = list(cfg.slopes)
    syn = syndrome_words(words, parity, cfg)                # (B, F)
    pop = popcount32(syn)                                   # (B, F)
    hot = jnp.stack([bit_position(syn[:, f]) for f in range(len(slopes))], -1)
    nonzero = pop > 0
    onehot = pop == 1
    n_nonzero = nonzero.astype(jnp.int32).sum(-1)

    ia, ib = slopes.index(1), slopes.index(2)
    i0 = (hot[:, ib] - hot[:, ia]) % BLOCK
    j0 = (hot[:, ia] - i0) % BLOCK
    consistent = jnp.ones(syn.shape[0], dtype=bool)
    for f, s in enumerate(slopes):
        consistent &= hot[:, f] == (j0 + s * i0) % BLOCK

    data_err = (n_nonzero == len(slopes)) & onehot.all(-1) & consistent
    parity_err = (n_nonzero == 1) & (onehot | ~nonzero).all(-1)
    uncorrectable = (n_nonzero > 0) & ~data_err & ~parity_err

    blocks = _as_blocks(words)
    flip_word = jnp.where(data_err,
                          jnp.uint32(1) << j0.astype(jnp.uint32),
                          jnp.uint32(0))
    onehot_row = (jnp.arange(BLOCK)[None, :] == i0[:, None])
    blocks = blocks ^ (onehot_row.astype(jnp.uint32) * flip_word[:, None])
    parity_fix = jnp.where((parity_err[:, None] & nonzero), syn, jnp.uint32(0))
    report = ScrubReport(
        corrected=data_err.astype(jnp.int32).sum(),
        parity_fixed=parity_err.astype(jnp.int32).sum(),
        uncorrectable=uncorrectable.astype(jnp.int32).sum(),
    )
    return blocks.reshape(-1), parity ^ parity_fix, report


# --------------------------------------------------------------------------
# parameter-store integration (arena-backed)
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class ReliableStore:
    """ECC-protected parameter pytree (the paper's §IV at datacenter scale).

    params are stored as-is (zero-copy for the forward pass); the parity
    table of the *packed arena* — one (n_blocks, n_families) uint32 array
    covering every leaf — is held alongside.  `scrub()` packs the pytree,
    runs the fused encode->syndrome->locate->correct Pallas kernel in a
    single launch, and unpacks the corrected arena, returning a ScrubReport.
    Call `refresh(params)` after an optimizer step rewrites the weights (the
    "function output ECC update" of §IV — whole buffers change, so re-encode
    with the one-launch encode kernel; incremental column/row updates are
    exercised in core/ecc.py).

    backend="kernel" (default) dispatches the Pallas kernels;
    backend="jnp" runs the pure-jnp oracle on the same arena (bit-exact,
    used for verification and on hosts without Pallas support).
    """

    def __init__(self, params: Any, parity: jax.Array,
                 cfg: WordEccConfig = WordEccConfig(),
                 backend: str = "kernel"):
        assert backend in ("kernel", "jnp"), backend
        self.params = params
        self.parity = parity
        self.cfg = cfg
        self.backend = backend
        # best-effort cache of (packed arena, spec) for params as stored.
        # protect/scrub fill it, so a scrub right after a refresh (the loop's
        # steady state) does not pack the same pytree twice.  Dropped by
        # tree_flatten — stores crossing a jit boundary just repack.
        self._packed: Optional[Tuple[jax.Array, arena.ArenaSpec]] = None

    # the single implementation of pack/encode/scrub lives in the scheme
    # layer (DESIGN.md §12); this class adapts it to the historic surface
    def _scheme(self):
        from ..reliability.scheme import DiagParityEcc
        return DiagParityEcc(slopes=self.cfg.slopes, impl=self.backend)

    @classmethod
    def _from_protected(cls, prot, cfg: WordEccConfig,
                        backend: str) -> "ReliableStore":
        store = cls(prot.payload, prot.redundancy, cfg, backend)
        store._packed = prot._packed
        return store

    @staticmethod
    def protect(params: Any, cfg: WordEccConfig = WordEccConfig(),
                backend: str = "kernel") -> "ReliableStore":
        from ..reliability.scheme import DiagParityEcc
        scheme = DiagParityEcc(slopes=cfg.slopes, impl=backend)
        return ReliableStore._from_protected(scheme.protect(params),
                                             cfg, backend)

    def refresh(self, new_params: Any) -> "ReliableStore":
        return ReliableStore.protect(new_params, self.cfg, self.backend)

    def scrub(self) -> Tuple["ReliableStore", ScrubReport]:
        scheme = self._scheme()
        prot = scheme.adopt(self.params, self.parity)
        prot._packed = self._packed
        fixed, report = scheme.scrub(prot)
        return self._from_protected(fixed, self.cfg, self.backend), report

    @property
    def n_blocks(self) -> int:
        return int(self.parity.shape[0])

    # pytree plumbing
    def tree_flatten(self):
        return (self.params, self.parity), (self.cfg, self.backend)

    @classmethod
    def tree_unflatten(cls, aux, children):
        cfg, backend = aux
        return cls(children[0], children[1], cfg, backend)


# --------------------------------------------------------------------------
# legacy per-leaf path — N dispatches, one per pytree leaf.  Kept only as
# the baseline that benchmarks/kernels_bench.py measures the arena against.
# --------------------------------------------------------------------------

def _pad_leaf_words(x: jax.Array) -> jax.Array:
    words = arena.leaf_to_words(x)
    pad = (-words.shape[0]) % BLOCK
    return jnp.pad(words, (0, pad)) if pad else words


def protect_leaves(params: Any, cfg: WordEccConfig = WordEccConfig()) -> Any:
    """Per-leaf parity tree (the pre-arena layout): one encode per leaf."""
    return jax.tree.map(lambda x: encode_words(_pad_leaf_words(x), cfg), params)


def scrub_leaves(params: Any, parity_tree: Any,
                 cfg: WordEccConfig = WordEccConfig()):
    """Per-leaf jnp scrub loop (the pre-arena hot path): one dispatch per
    leaf plus a Python-level reduction of the reports."""
    leaves, treedef = jax.tree.flatten(params)
    pleaves = treedef.flatten_up_to(parity_tree)
    out_p, out_c, reps = [], [], []
    for x, par in zip(leaves, pleaves):
        words = _pad_leaf_words(x)
        fixed, par2, rep = correct_words(words, par, cfg)
        spec = arena.leaf_spec(x)
        out_p.append(arena.words_to_leaf(fixed[:spec.n_words], spec))
        out_c.append(par2)
        reps.append(rep)
    total = ScrubReport(
        corrected=sum(r.corrected for r in reps),
        parity_fixed=sum(r.parity_fixed for r in reps),
        uncorrectable=sum(r.uncorrectable for r in reps),
    )
    return treedef.unflatten(out_p), treedef.unflatten(out_c), total


# Deprecated re-export (module attribute only — dropped from __all__): the
# canonical transient injector lives in repro.faults.models as part of the
# unified FaultModel taxonomy.  Kept one release so historic
# `from repro.core.reliability import inject_bit_flips` call sites keep
# working; new code must use repro.faults directly.
from ..faults.models import inject_bit_flips  # noqa: E402,F401


def tmr_serve(serve_fn, mode: str = "serial", use_kernel: bool = True):
    """DEPRECATED shim: TMR-voted serving via `repro.reliability.Tmr.wrap`.

    serve_fn(params, *inputs) -> pytree of arrays; the wrapper is called as
    wrapped(p1, p2, p3, *inputs) with per-copy parameter versions.  All
    three paper disciplines are accepted ('serial', 'parallel',
    'semi_parallel'); use_kernel=False selects the jnp voter.  New code
    should construct `Tmr(discipline=...).wrap(serve_fn)` directly
    (DESIGN.md §12) — this shim is bit-exact against it by construction.
    """
    from ..reliability.scheme import Tmr
    return Tmr(discipline=mode,
               impl=None if use_kernel else "jnp").wrap(serve_fn)
