"""Composable protection schemes — the single public API (DESIGN.md §12).

The paper's point (§V–§VI) is that diagonal-parity ECC and TMR are not
alternatives but a *design space*: ECC for short-term scrubbing, TMR
disciplines for long-term protection, and joint configurations evaluated
together on NN workloads.  This module expresses that space as one small
protocol so every consumer — train loop, serving, fault campaigns,
benchmarks — can sweep protection schemes instead of hard-coding one:

    scheme = parse_scheme("ecc+tmr-serial")
    prot   = scheme.protect(params)          # Protected pytree node
    prot, report = scheme.scrub(prot)        # verify/correct redundancy
    prot   = scheme.refresh(new_params)      # after a parameter rewrite
    params = scheme.read(prot)               # decode/vote the payload
    cost   = scheme.overhead()               # CostReport (paper §IV/§V)

Schemes: `Unprotected`, `DiagParityEcc` (the arena-backed §IV word code),
`Tmr` with all three paper disciplines (serial / parallel / semi-parallel),
and `Compose(ecc, tmr)` for the joint long-term configurations.  Each is a
frozen dataclass (hashable — usable as a static jit argument and as pytree
aux data) and every array op dispatches through the backend registry
(`reliability.backend`), so ``impl=`` / ``REPRO_IMPL`` select kernel vs jnp
paths uniformly.

`Protected` is a registered pytree node carrying payload + redundancy +
scheme metadata, so it flows through `jit`, `vmap` and the checkpointer
unchanged.  All schemes are bit-exact against the pre-redesign
`ReliableStore` / `core.tmr` paths (golden tests in tests/test_scheme.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import arena
from ..core.reliability import ScrubReport
from ..core.tmr import TMR_COSTS
from . import backend

__all__ = ["CostReport", "Protected", "Scheme", "Unprotected", "ArenaEcc",
           "DiagParityEcc", "HsiaoSecDed", "Tmr", "Compose", "parse_scheme",
           "SCHEME_CHOICES", "standard_grid", "register_scheme",
           "scheme_choices", "scheme_help"]


@dataclasses.dataclass(frozen=True)
class CostReport:
    """Protection overheads relative to the unprotected baseline.

    storage_x counts held redundancy (parity words, extra copies);
    latency/area/throughput follow the paper's §IV/§V accounting.
    """
    storage_x: float = 1.0
    latency_x: float = 1.0
    area_x: float = 1.0
    throughput_x: float = 1.0

    def describe(self) -> str:
        return (f"storage={self.storage_x:.3f}x latency={self.latency_x:.2f}x "
                f"area={self.area_x:.0f}x throughput={self.throughput_x:.2f}x")


@jax.tree_util.register_pytree_node_class
class Protected:
    """A protected parameter pytree: payload + scheme-specific redundancy.

    Registered pytree node — children are (payload, redundancy), aux data is
    the (hashable, frozen) scheme — so a Protected store crosses `jit`,
    `vmap` and `Checkpointer.save/restore` boundaries unchanged.  The
    `_packed` attribute is a best-effort (arena, spec) cache for the payload
    as stored; it is dropped by tree_flatten, so instances crossing a jit
    boundary simply repack.
    """

    def __init__(self, payload: Any, redundancy: Any, scheme: "Scheme"):
        self.payload = payload
        self.redundancy = redundancy
        self.scheme = scheme
        self._packed: Optional[Tuple[jax.Array, arena.ArenaSpec]] = None

    def read(self) -> Any:
        return self.scheme.read(self)

    def scrub(self, mesh=None) -> Tuple["Protected", ScrubReport]:
        return self.scheme.scrub(self, mesh=mesh)

    # pytree plumbing
    def tree_flatten(self):
        return (self.payload, self.redundancy), self.scheme

    @classmethod
    def tree_unflatten(cls, scheme, children):
        return cls(children[0], children[1], scheme)

    def __repr__(self) -> str:
        return f"Protected(scheme={self.scheme.name})"


def _zero_report() -> ScrubReport:
    z = jnp.zeros((), jnp.int32)
    return ScrubReport(corrected=z, parity_fixed=z, uncorrectable=z)


def _ns_tree(pspecs: Any, mesh) -> Any:
    """PartitionSpec tree -> NamedSharding tree (specs are leaves)."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))


def _vote_counts(a: Any, b: Any, c: Any) -> Tuple[jax.Array, jax.Array]:
    """(corrected, uncorrectable) word counts for a 3-copy vote, disjoint
    like the ECC convention: `corrected` counts words where a majority
    exists and the minority copy was repaired (each word once, however
    many copies diverged); `uncorrectable` counts words where all three
    copies pairwise differ — multiple independent corruptions landed on
    the same word, so the per-bit majority may itself be wrong there (the
    danger signal TMR can actually *detect*; a clean 2-of-3 double flip
    is inherently silent)."""
    corrected = jnp.zeros((), jnp.int32)
    conflicts = jnp.zeros((), jnp.int32)
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                       jax.tree.leaves(c)):
        xw, yw, zw = (arena.leaf_to_words(v) for v in (x, y, z))
        d01, d02, d12 = xw != yw, xw != zw, yw != zw
        conflict = d01 & d02 & d12
        corrected = corrected + ((d01 | d02 | d12)
                                 & ~conflict).sum(dtype=jnp.int32)
        conflicts = conflicts + conflict.sum(dtype=jnp.int32)
    return corrected, conflicts


class Scheme:
    """Protection-scheme protocol.  Subclasses are frozen dataclasses."""

    @property
    def name(self) -> str:
        raise NotImplementedError

    def protect(self, payload: Any) -> Protected:
        raise NotImplementedError

    def refresh(self, payload: Any) -> Protected:
        """Re-protect after the payload was rewritten (optimizer step)."""
        return self.protect(payload)

    def adopt(self, payload: Any, redundancy: Any) -> Protected:
        """Rebuild a Protected from externally stored payload+redundancy
        (checkpoint restore) without re-encoding."""
        return Protected(payload, redundancy, self)

    def scrub(self, prot: Protected,
              mesh=None) -> Tuple[Protected, ScrubReport]:
        """Verify/correct the redundancy.  With a mesh, arena-wide scrubs
        run as per-shard shard_map launches with psum'd counters
        (DESIGN.md §14) — bit-exact vs mesh=None."""
        raise NotImplementedError

    def vote_share(self, report: ScrubReport):
        """The copy-vote share of a scrub report — the scheme knows which
        of its counters are vote outcomes.  None for non-voting schemes;
        an on-device int32 otherwise (fetch with the rest)."""
        return None

    def scrub_into(self, prot: Protected, metrics, mesh=None,
                   registry=None) -> Tuple[Protected, dict]:
        """Scrub and fold the report into a metrics-registry accumulator
        dict (obs.MetricsRegistry schema names, device-side adds):

            metrics = DEFAULT_REGISTRY.zeros(["ecc_corrected", ...])
            prot, metrics = scheme.scrub_into(prot, metrics)
            ...
            stats = fetch_telemetry(metrics)     # ONE host sync at the end

        Counters never touch the host between scrubs — the accumulation is
        `registry.accumulate`, all jnp adds."""
        from ..obs import DEFAULT_REGISTRY
        registry = registry if registry is not None else DEFAULT_REGISTRY
        fixed, report = self.scrub(prot, mesh=mesh)
        updates = registry.from_report(report)
        vd = self.vote_share(report)
        if vd is not None:
            updates["tmr_final_disagreements"] = vd
        return fixed, registry.accumulate(metrics, updates)

    def read(self, prot: Protected) -> Any:
        """Decode/vote the protected payload back to a plain pytree."""
        return prot.payload

    def shardings(self, payload: Any, pspecs: Any, mesh,
                  rules=None) -> Protected:
        """NamedSharding tree shaped like ``protect(payload)`` — pass to
        `jax.device_put` to place a Protected store on `mesh`.

        `payload` may be abstract (ShapeDtypeStructs); `pspecs` is its
        PartitionSpec tree (e.g. from `models.params.partition_specs`).
        Redundancy placement is scheme-aware: parity tables shard their
        arena-block axis across the whole mesh, TMR copies shard exactly
        like the payload they mirror (each copy lands on its replica group
        when the engine later stacks them under a copy-axis spec).
        """
        return Protected(_ns_tree(pspecs, mesh),
                         self._redundancy_shardings(payload, pspecs, mesh,
                                                    rules), self)

    def _redundancy_shardings(self, payload, pspecs, mesh, rules):
        return None

    def corrupt_store(self, prot: Protected, model, key: jax.Array,
                      dt: float = 1.0) -> Protected:
        """Inject storage faults into every held *data* copy (payload and,
        for TMR-style schemes, the redundant copies — each under an
        independent subkey), leaving parity tables untouched, matching the
        paper's exposure model where check words are scrub-verified.
        Campaign trials drive one exposure interval per call."""
        return self.adopt(model.corrupt(prot.payload, key, dt),
                          prot.redundancy)

    def overhead(self) -> CostReport:
        raise NotImplementedError

    def cost_events(self, base, profile, spec):
        """mMPU cost-model hookup (costmodel.compile.lower_step): extend
        or transform a redundancy-free step event stream with this
        scheme's redundancy traffic.  `base` is a sequence of
        `costmodel.MmpuEvent`; `profile` a `costmodel.StepProfile`;
        `spec` a `costmodel.DeviceSpec`.  The analytical `overhead()`
        CostReport is the closed form these streams must agree with
        (tests/test_costmodel.py holds both to each other)."""
        return tuple(base)

    #: does the redundancy belong in a checkpoint?  True for compact parity
    #: tables; False when redundancy is full copies (rebuilt on restore).
    checkpoint_redundancy: bool = False


@dataclasses.dataclass(frozen=True)
class Unprotected(Scheme):
    """No redundancy — the baseline every CostReport is relative to."""

    @property
    def name(self) -> str:
        return "unprotected"

    def protect(self, payload: Any) -> Protected:
        return Protected(payload, None, self)

    def scrub(self, prot: Protected,
              mesh=None) -> Tuple[Protected, ScrubReport]:
        return prot, _zero_report()

    def overhead(self) -> CostReport:
        return CostReport()


class ArenaEcc(Scheme):
    """Shared machinery for packed-arena word codes (the code zoo,
    DESIGN.md §18): everything that depends only on the arena layout —
    pack/protect, fused scrub, copy concatenation, parity sharding,
    checkpointing — lives here; subclasses supply the code itself
    (`_encode` / `_scrub` / `n_parity_words` and the cost accounting).

    Subclasses are frozen dataclasses carrying at least ``impl``
    (backend override) and ``write_back`` (the correct-on-read serving
    discipline: `read_corrected` is meaningful for every ArenaEcc, but
    a True flag tells serving paths — the paged KV pool, the batcher —
    to correct-and-persist hot state on access instead of waiting for
    the periodic scrub).
    """

    # spec-string token of the code family ("ecc", "hsiao") — a plain
    # class attribute, deliberately unannotated so dataclass subclasses
    # do not inherit it as a field
    code_name = "ecc"

    @property
    def name(self) -> str:
        return self.code_name + ("-wb" if self.write_back else "")

    @property
    def n_parity_words(self) -> int:
        """Redundancy words per 32-word block (the parity-table width)."""
        raise NotImplementedError

    def _encode(self, buf: jax.Array) -> jax.Array:
        raise NotImplementedError

    def _scrub(self, buf: jax.Array, parity: jax.Array, mesh=None):
        raise NotImplementedError

    def _ecc_events(self, profile, spec, copies: int = 1):
        """This code's mMPU redundancy traffic (costmodel hookup)."""
        raise NotImplementedError

    def protect(self, payload: Any) -> Protected:
        buf, spec = arena.pack(payload)
        parity = self._encode(buf)
        prot = Protected(payload, parity, self)
        prot._packed = (buf, spec)
        return prot

    def scrub(self, prot: Protected,
              mesh=None) -> Tuple[Protected, ScrubReport]:
        buf, spec = prot._packed if prot._packed is not None \
            else arena.pack(prot.payload)
        fixed, par2, counts = self._scrub(buf, prot.redundancy, mesh=mesh)
        out = Protected(arena.unpack(fixed, spec), par2, self)
        out._packed = (fixed, spec)
        report = ScrubReport(corrected=counts[0], parity_fixed=counts[1],
                             uncorrectable=counts[2])
        return out, report

    def read_corrected(self, prot: Protected, mesh=None):
        """The write-back-on-read discipline at the scheme level: decode
        through a fused scrub so the caller gets *corrected* bits AND the
        corrected store persists.  Returns (payload, prot', report)."""
        fixed, report = self.scrub(prot, mesh=mesh)
        return fixed.payload, fixed, report

    def _redundancy_shardings(self, payload, pspecs, mesh, rules):
        from jax.sharding import NamedSharding
        from ..optim.sharding_rules import parity_pspec
        spec = arena.arena_spec(payload)
        return NamedSharding(mesh, parity_pspec(spec.n_blocks,
                                                self.n_parity_words, mesh,
                                                rules))

    def encode_arena(self, buf: jax.Array) -> jax.Array:
        """Parity table for a packed uint32 arena.

        The write-back discipline for *mutable* arena state (the paged KV
        pool, which rewrites pages every scheduler tick): re-encode after
        each legitimate write so a later scrub never "corrects" fresh data
        back toward a stale parity.  Device op; jit-safe."""
        return self._encode(buf)

    def scrub_arena(self, buf: jax.Array, parity: jax.Array,
                    mesh=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Fused scrub over a packed uint32 arena that is NOT wrapped in a
        `Protected` pytree — mutable arena-resident state such as the
        paged KV pool.  Returns (fixed arena, fixed parity, counts) with
        counts the (3,) int32 (corrected, parity_fixed, uncorrectable)
        vector, all on device.  Because the word code is block-local,
        several same-layout arenas may be concatenated along the block
        axis and scrubbed in this ONE launch (how the pool covers all
        three TMR copies)."""
        return self._scrub(buf, parity, mesh=mesh)

    def inject_scrub_arena(self, buf: jax.Array, parity: jax.Array,
                           mask: jax.Array, mesh=None
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Fused corrupt+repair over a packed arena: XOR the fault mask in,
        then run the code's scrub, all inside one jit region.  Returns
        (fixed arena, fixed parity, counts) with counts the (4,) int32
        (injected, corrected, parity_fixed, uncorrectable) vector — the
        fault-campaign convention.  Codes with a dedicated fused kernel
        override this (diagonal parity routes to kernels/inject_scrub);
        the default is correct for every block-local word code."""
        injected = jnp.sum(
            jax.lax.population_count(mask).astype(jnp.int32))
        fixed, par2, counts = self._scrub(buf ^ mask, parity, mesh=mesh)
        return fixed, par2, jnp.concatenate([injected[None], counts])

    def scrub_copies(self, bufs, parities,
                     mesh=None) -> Tuple[list, list, jax.Array]:
        """Scrub N same-layout packed copies in ONE fused launch.

        The word code is block-local (every 32-word block carries its own
        parity row), so N copies of one arena concatenate along the block
        axis into a single buffer and the fused encode->syndrome->correct
        pass covers all of them in one kernel launch — replacing the
        Python loop of per-copy scrubs that serialized the TMR copy axis.

        bufs: list of (n_words,) uint32 arenas sharing one ArenaSpec;
        parities: matching list of (n_blocks, F) tables.  Returns
        (fixed bufs, fixed parities, counts) with counts the (3,) int32
        vector summed across copies — all on device, nothing fetched.
        """
        n = bufs[0].shape[0]
        nb = parities[0].shape[0]
        fixed, par2, counts = self._scrub(jnp.concatenate(list(bufs)),
                                          jnp.concatenate(list(parities)),
                                          mesh=mesh)
        return ([fixed[i * n:(i + 1) * n] for i in range(len(bufs))],
                [par2[i * nb:(i + 1) * nb] for i in range(len(parities))],
                counts)

    def cost_events(self, base, profile, spec):
        return tuple(base) + self._ecc_events(profile, spec)

    checkpoint_redundancy = True


@dataclasses.dataclass(frozen=True)
class DiagParityEcc(ArenaEcc):
    """Diagonal-parity word ECC over the packed arena (paper §IV).

    Wraps the `core.arena` + `kernels/diag_parity` machinery behind the
    scheme protocol; bit-exact against `core.reliability.ReliableStore`
    (same pack, same encode, same fused scrub, same counts).  `impl`
    overrides the `diag_parity` backend (None -> registry default).
    Corrects one flipped bit per 32-word *block* at 3 parity words of
    storage; multi-flip blocks are flagged uncorrectable.
    """

    slopes: Tuple[int, ...] = (1, 2, -1)
    impl: Optional[str] = None
    write_back: bool = False

    code_name = "ecc"

    @property
    def n_parity_words(self) -> int:
        return len(self.slopes)

    def _op(self):
        return backend.dispatch("diag_parity", self.impl)

    def _encode(self, buf: jax.Array) -> jax.Array:
        return self._op().encode(buf, slopes=self.slopes)

    def _scrub(self, buf: jax.Array, parity: jax.Array, mesh=None):
        return self._op().scrub(buf, parity, slopes=self.slopes, mesh=mesh)

    def inject_scrub_arena(self, buf: jax.Array, parity: jax.Array,
                           mask: jax.Array, mesh=None):
        # diagonal parity has a dedicated fused corrupt+repair kernel
        op = backend.dispatch("inject_scrub", self.impl)
        return op(buf, parity, mask, slopes=self.slopes, mesh=mesh)

    def overhead(self) -> CostReport:
        # storage: len(slopes) parity words per 32-word block; latency: the
        # paper's ~26% average ECC overhead with the dedicated extension
        return CostReport(storage_x=1.0 + len(self.slopes) / arena.BLOCK,
                          latency_x=1.26)

    def _ecc_events(self, profile, spec, copies: int = 1):
        from ..costmodel.compile import ecc_events
        return ecc_events(profile, spec, self.slopes, copies=copies)


@dataclasses.dataclass(frozen=True)
class HsiaoSecDed(ArenaEcc):
    """(39,32) Hsiao SEC-DED word code over the packed arena.

    The second code of the zoo (kernels/hsiao_secded, DESIGN.md §18):
    7 odd-weight-column check bits per 32-bit word, packed as 7 parity
    words per block.  Every word decodes independently — one flip in
    each of a block's 32 words is still corrected, where diagonal
    parity corrects one flip per block — and double errors are
    *detected* (reported uncorrectable through `ScrubReport`) instead
    of silently miscorrected.  Storage 1+7/32 vs diag's 1+3/32, and a
    denser encode tree (7 masked-parity families vs 3 rotate-XOR
    slopes): higher coverage, higher maintenance tax.
    """

    impl: Optional[str] = None
    write_back: bool = False

    code_name = "hsiao"

    @property
    def n_parity_words(self) -> int:
        from ..kernels.hsiao_secded.code import N_CHECKS
        return N_CHECKS

    def _op(self):
        return backend.dispatch("hsiao_secded", self.impl)

    def _encode(self, buf: jax.Array) -> jax.Array:
        return self._op().encode(buf)

    def _scrub(self, buf: jax.Array, parity: jax.Array, mesh=None):
        return self._op().scrub(buf, parity, mesh=mesh)

    def overhead(self) -> CostReport:
        # 7 check bits per word of storage; latency follows the denser
        # encode (7 families of masked parities vs 3 diagonal slopes —
        # arXiv:2105.04212's Hamming-vs-parity gap), still well under
        # any TMR discipline's 3x
        return CostReport(storage_x=1.0 + 7.0 / arena.BLOCK,
                          latency_x=1.42)

    def _ecc_events(self, profile, spec, copies: int = 1):
        from ..costmodel.compile import secded_events
        return secded_events(profile, spec, copies=copies)


@dataclasses.dataclass(frozen=True)
class Tmr(Scheme):
    """Triple modular redundancy with per-bit voting (paper §V).

    All three paper disciplines are selectable — 'serial' (3x latency),
    'parallel' (3x area) and 'semi_parallel' (1/3 throughput) — with
    identical output semantics: the discipline changes the execution shape
    of `wrap()` and the `overhead()` accounting, never the voted bits.
    Voting dispatches through the `tmr_vote` backend (kernel | jnp).
    """

    discipline: str = "serial"
    impl: Optional[str] = None

    def __post_init__(self):
        if self.discipline not in TMR_COSTS:
            raise ValueError(f"discipline must be one of {sorted(TMR_COSTS)}")

    @property
    def name(self) -> str:
        return f"tmr-{self.discipline.replace('_', '-')}"

    def _vote(self):
        return backend.dispatch("tmr_vote", self.impl)

    def protect(self, payload: Any) -> Protected:
        # three copies; as immutable jax arrays they alias until corrupted
        return Protected(payload, (payload, payload), self)

    def read(self, prot: Protected) -> Any:
        vote = self._vote()
        c1, c2 = prot.redundancy
        return jax.tree.map(vote, prot.payload, c1, c2)

    def scrub(self, prot: Protected,
              mesh=None) -> Tuple[Protected, ScrubReport]:
        # voting is elementwise — under a mesh GSPMD keeps it shard-local,
        # so there is no explicit shard_map path (mesh accepted for
        # protocol uniformity)
        voted = self.read(prot)
        c1, c2 = prot.redundancy
        # three-way disagreements feed the runtime's RESTART path — the
        # voted word is best-effort there, like an ECC uncorrectable block
        corrected, conflicts = _vote_counts(prot.payload, c1, c2)
        report = ScrubReport(corrected=corrected,
                             parity_fixed=jnp.zeros((), jnp.int32),
                             uncorrectable=conflicts)
        return Protected(voted, (voted, voted), self), report

    def vote_share(self, report: ScrubReport):
        # every TMR repair and every conflict is a copy disagreement
        return report.corrected + report.uncorrectable

    def _redundancy_shardings(self, payload, pspecs, mesh, rules):
        ns = _ns_tree(pspecs, mesh)
        return (ns, ns)

    def corrupt_store(self, prot: Protected, model, key: jax.Array,
                      dt: float = 1.0) -> Protected:
        c1, c2 = prot.redundancy
        k0, k1, k2 = jax.random.split(key, 3)
        return self.adopt(model.corrupt(prot.payload, k0, dt),
                          (model.corrupt(c1, k1, dt),
                           model.corrupt(c2, k2, dt)))

    def wrap(self, serve_fn, sequential: bool = False):
        """TMR-voted serving: `serve_fn(params, *inputs) -> pytree`, called
        as wrapped(p1, p2, p3, *inputs) with per-copy parameter versions.

        serial: three sequential evaluations; parallel/semi_parallel: one
        vmapped evaluation over the stacked replica axis (on a real mesh
        the axis is sharded over 3 replica groups for 'parallel', folded
        into the row/batch capacity for 'semi_parallel').  The voted bits
        are identical either way, so ``sequential=True`` forces the
        serial execution shape regardless of discipline — for single-host
        drivers where stacking three full copies would 3x peak memory —
        while `cost` keeps reporting the discipline's accounting.
        """
        vote = self._vote()

        def serial(p1, p2, p3, *inputs):
            outs = [serve_fn(p, *inputs) for p in (p1, p2, p3)]
            return jax.tree.map(vote, *outs)

        def replicated(p1, p2, p3, *inputs):
            stacked = jax.tree.map(lambda a, b, c: jnp.stack([a, b, c]),
                                   p1, p2, p3)
            outs = jax.vmap(lambda p: serve_fn(p, *inputs))(stacked)
            o1, o2, o3 = (jax.tree.map(lambda x, i=i: x[i], outs)
                          for i in range(3))
            return jax.tree.map(vote, o1, o2, o3)

        wrapped = serial if (sequential or self.discipline == "serial") \
            else replicated
        wrapped.cost = self.overhead()
        return wrapped

    def overhead(self) -> CostReport:
        c = TMR_COSTS[self.discipline]
        return CostReport(storage_x=3.0, latency_x=c.latency_x,
                          area_x=c.area_x, throughput_x=c.throughput_x)

    def cost_events(self, base, profile, spec):
        from ..costmodel.compile import tmr_transform, vote_events
        return tmr_transform(base, self.discipline) \
            + vote_events(profile, spec)


@dataclasses.dataclass(frozen=True)
class Compose(Scheme):
    """Joint configuration: a per-copy arena word code under TMR voting
    (the paper's combined long-term protection, §VI) — any `ArenaEcc`
    (diagonal parity or Hsiao SEC-DED) composes identically.

    Each of the three copies carries its own parity table; `scrub` first
    runs the fused ECC scrub on every copy (correcting all single-bit
    flips per block), then votes per-bit across the scrubbed copies — so
    blocks the word code flags uncorrectable are still recovered whenever
    at least two copies agree.  The report sums the three per-copy ECC
    corrected/parity_fixed counts plus the voted word repairs; its
    `uncorrectable` counts only words still three-way-disagreeing AFTER
    the per-copy scrub (per-copy ECC uncorrectables that the vote
    recovers are demoted to corrections — they no longer trigger the
    runtime's checkpoint-restore path).
    """

    ecc: ArenaEcc = DiagParityEcc()
    tmr: Tmr = Tmr()

    @property
    def name(self) -> str:
        return f"{self.ecc.name}+{self.tmr.name}"

    def protect(self, payload: Any) -> Protected:
        buf, spec = arena.pack(payload)
        parity = self.ecc._encode(buf)
        prot = Protected(payload, ((payload, payload),
                                   (parity, parity, parity)), self)
        prot._packed = (buf, spec)
        return prot

    def read(self, prot: Protected) -> Any:
        (c1, c2), _ = prot.redundancy
        vote = self.tmr._vote()
        return jax.tree.map(vote, prot.payload, c1, c2)

    def scrub(self, prot: Protected,
              mesh=None) -> Tuple[Protected, ScrubReport]:
        # scrub and vote directly on the packed arenas: all three copies
        # share one layout, so the per-copy ECC pass is ONE fused launch
        # over the concatenated copies (scrub_copies) and the vote is three
        # uint32 buffers through the tmr_vote backend; only the voted
        # result is unpacked once.  Counts stay on device (no per-copy
        # Python accumulation).
        (c1, c2), (p0, p1, p2) = prot.redundancy
        packed, spec = [], None
        for i, copy in enumerate((prot.payload, c1, c2)):
            buf, spec = prot._packed if i == 0 and prot._packed is not None \
                else arena.pack(copy)
            packed.append(buf)
        bufs, _, counts = self.ecc.scrub_copies(packed, (p0, p1, p2),
                                                mesh=mesh)
        vbuf = self.tmr._vote()(*bufs)
        voted = arena.unpack(vbuf, spec)
        vpar = self.ecc._encode(vbuf)
        out = Protected(voted, ((voted, voted), (vpar, vpar, vpar)), self)
        out._packed = (vbuf, spec)
        d01, d02, d12 = (bufs[0] != bufs[1], bufs[0] != bufs[2],
                         bufs[1] != bufs[2])
        conflict = d01 & d02 & d12
        report = ScrubReport(
            corrected=counts[0]
            + ((d01 | d02 | d12) & ~conflict).sum(dtype=jnp.int32),
            parity_fixed=counts[1],
            uncorrectable=conflict.sum(dtype=jnp.int32))
        return out, report

    def vote_share(self, report: ScrubReport):
        # only the post-ECC three-way conflicts are separable from the
        # merged report (repaired pairwise disagreements are folded into
        # `corrected` with the per-copy ECC counts)
        return report.uncorrectable

    def corrupt_store(self, prot: Protected, model, key: jax.Array,
                      dt: float = 1.0) -> Protected:
        (c1, c2), parities = prot.redundancy
        k0, k1, k2 = jax.random.split(key, 3)
        return self.adopt(model.corrupt(prot.payload, k0, dt),
                          ((model.corrupt(c1, k1, dt),
                            model.corrupt(c2, k2, dt)), parities))

    def _redundancy_shardings(self, payload, pspecs, mesh, rules):
        ns = _ns_tree(pspecs, mesh)
        pns = self.ecc._redundancy_shardings(payload, pspecs, mesh, rules)
        return ((ns, ns), (pns, pns, pns))

    def overhead(self) -> CostReport:
        e, t = self.ecc.overhead(), self.tmr.overhead()
        return CostReport(storage_x=e.storage_x * t.storage_x,
                          latency_x=e.latency_x * t.latency_x,
                          area_x=e.area_x * t.area_x,
                          throughput_x=e.throughput_x * t.throughput_x)

    def cost_events(self, base, profile, spec):
        # execution triplicates under the TMR discipline; each copy
        # carries its own parity table, so the word-code traffic covers
        # copies=3 blocks (scrub_copies fuses them in one pass)
        from ..costmodel.compile import tmr_transform, vote_events
        return (tmr_transform(base, self.tmr.discipline)
                + vote_events(profile, spec)
                + self.ecc._ecc_events(profile, spec, copies=3))


# --------------------------------------------------------------------------
# scheme registry + spec strings (serve --scheme, campaign grids)
# --------------------------------------------------------------------------
#
# One registry maps spec tokens to scheme factories; everything user-facing
# (serve --scheme validation and help, campaign grids, SCHEME_CHOICES) is
# derived from it, so a new code registered here appears everywhere at once.

_SCHEME_FACTORIES: "dict[str, Tuple[Any, str]]" = {}
_SCHEME_ALIASES: "dict[str, str]" = {}


def register_scheme(token: str, factory, help: str = "",
                    aliases: Tuple[str, ...] = ()) -> None:
    """Register `factory(impl) -> Scheme` under spec token `token`."""
    _SCHEME_FACTORIES[token] = (factory, help)
    for a in aliases:
        _SCHEME_ALIASES[a] = token


def scheme_choices() -> Tuple[str, ...]:
    """Every registered spec token, plus the composition grammar (one
    arena code + one TMR discipline joined by '+')."""
    return tuple(_SCHEME_FACTORIES) + ("ecc+tmr", "hsiao+tmr")


def scheme_help() -> str:
    """One-line-per-token help text assembled from the registry (the
    serve --scheme flag renders this, never a hardcoded list)."""
    lines = [f"{tok}: {hlp}" for tok, (_, hlp) in _SCHEME_FACTORIES.items()]
    lines.append("<code>+tmr[-<discipline>]: per-copy arena code under "
                 "TMR voting (e.g. ecc+tmr-serial, hsiao+tmr)")
    return "; ".join(lines)


register_scheme("off", lambda impl: Unprotected(),
                "no redundancy (baseline)", aliases=("none", "unprotected"))
register_scheme("ecc", lambda impl: DiagParityEcc(impl=impl),
                "diagonal-parity word code, 1 correction per 32-word block,"
                " +3/32 storage")
register_scheme("ecc-wb", lambda impl: DiagParityEcc(impl=impl,
                                                     write_back=True),
                "diagonal parity with write-back-on-read serving")
register_scheme("hsiao", lambda impl: HsiaoSecDed(impl=impl),
                "(39,32) Hsiao SEC-DED, per-word correct + double-error "
                "detect, +7/32 storage")
register_scheme("hsiao-wb", lambda impl: HsiaoSecDed(impl=impl,
                                                     write_back=True),
                "Hsiao SEC-DED with write-back-on-read serving")

_TMR_ALIASES = {"serial": "serial", "parallel": "parallel",
                "semi": "semi_parallel", "semi-parallel": "semi_parallel",
                "semi_parallel": "semi_parallel"}

for _disc, _canon in (("serial", "serial"), ("parallel", "parallel"),
                      ("semi", "semi_parallel")):
    register_scheme(
        f"tmr-{_disc}",
        lambda impl, d=_canon: Tmr(discipline=d, impl=impl),
        f"triple modular redundancy, {_canon.replace('_', '-')} discipline")

SCHEME_CHOICES = scheme_choices()


def _parse_one(token: str, impl: Optional[str]) -> Scheme:
    token = token.strip().lower()
    token = _SCHEME_ALIASES.get(token, token)
    if token in _SCHEME_FACTORIES:
        return _SCHEME_FACTORIES[token][0](impl)
    if token == "tmr" or token.startswith("tmr-"):
        disc = _TMR_ALIASES.get(token[4:] or "serial")
        if disc is None:
            raise ValueError(f"unknown TMR discipline {token[4:]!r} "
                             f"(expected one of {sorted(_TMR_ALIASES)})")
        return Tmr(discipline=disc, impl=impl)
    raise ValueError(f"unknown scheme {token!r} "
                     f"(expected one of {scheme_choices()})")


def standard_grid(impl: Optional[str] = None,
                  include_hsiao: bool = False) -> Tuple[Scheme, ...]:
    """The canonical sweep grid (every scheme family, all disciplines) —
    shared by the campaign benchmarks so they all walk one design space.
    `include_hsiao` extends it with the SEC-DED code zoo variants (solo
    and composed with TMR) behind one flag."""
    grid = (Unprotected(), DiagParityEcc(impl=impl),
            Tmr("serial", impl=impl), Tmr("parallel", impl=impl),
            Tmr("semi_parallel", impl=impl),
            Compose(DiagParityEcc(impl=impl), Tmr("serial", impl=impl)))
    if include_hsiao:
        grid += (HsiaoSecDed(impl=impl),
                 Compose(HsiaoSecDed(impl=impl), Tmr("serial", impl=impl)))
    return grid


def parse_scheme(spec: str, impl: Optional[str] = None) -> Scheme:
    """Parse a scheme spec string: any registered token (``off | ecc |
    ecc-wb | hsiao | hsiao-wb | tmr-<discipline>``) or a composition
    ``<code>+tmr[-<discipline>]`` with discipline serial | parallel |
    semi.  `impl` threads a backend override into every constructed
    scheme."""
    parts = [_parse_one(t, impl) for t in spec.split("+")]
    if len(parts) == 1:
        return parts[0]
    if len(parts) == 2:
        eccs = [p for p in parts if isinstance(p, ArenaEcc)]
        tmrs = [p for p in parts if isinstance(p, Tmr)]
        if len(eccs) == 1 and len(tmrs) == 1:
            return Compose(ecc=eccs[0], tmr=tmrs[0])
    raise ValueError(f"cannot compose scheme spec {spec!r} "
                     "(expected <code>+tmr[-<discipline>])")
