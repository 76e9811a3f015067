"""Continuous-batching reliable serving (DESIGN.md §16).

The scan engine (launch/engine.py) serves one fixed batch to completion:
every request in the batch pays for the longest generation, and a new
request waits for the whole batch to drain.  This module adds the serving
layer that keeps the batch full *without giving up any of the reliability
invariants*:

* **paged KV pool** (`PagedKVPool`) — KV state for all in-flight requests
  lives in fixed-size pages of one pool array per k/v, each page stored
  lane-dense as (L, page_tokens, KV·hd): the kv heads and head_dim merge
  into one trailing axis, so a head_dim off the TPU's 128 lanes (phi3's
  96) cannot push the page axis into the lanes, where every page access
  sweeps the whole pool.  The pool packs
  into the block-aligned uint32 arena (core/arena.py) — every page spans
  a whole number of ECC blocks — so the *same* fused diagonal-parity
  launches that protect the weights cover the KV state: `scrub()` is one
  fused scrub over the whole pool, `inject_scrub()` one fused
  corrupt+repair (kernels/inject_scrub).  Because pages are rewritten by
  every decode tick, parity follows a write-back discipline: the tick and
  admission programs re-encode the pool parity in-program
  (`DiagParityEcc.encode_arena`), so a later scrub never "corrects" fresh
  data toward stale parity.  Page 0 is reserved scratch: empty slots and
  unreserved page-table entries point at it, so masked rows read/write
  real storage that no active request ever depends on.

* **chunk-boundary scheduler** (`ContinuousBatcher`) — requests join and
  leave the in-flight batch only between compiled decode chunks.  The
  tick program has ONE shape (fixed `slots` batch rows, fixed `chunk`
  scan steps, fixed page-table width), so the compile cache stays at one
  tick program plus one admission program per prompt bucket.  Admission
  prefills at the bucket length, scatters the prefilled KV into reserved
  pages and writes the first token — one launch; each tick gathers every
  slot's page table into a (L, slots, S_cap, ...) cache view, scans
  `chunk` decode steps with *per-slot* positions, scatters the pages
  back and appends the new tokens to a per-slot output ring — one launch
  (per copy for the serial TMR discipline; one vmapped launch for
  parallel/semi).

* **zero-sync telemetry contract** — a tick performs no device->host
  data transfer except ONE batched `jax.device_get` of finished rows on
  the ticks where requests complete (completion itself is host-side
  integer arithmetic over the known generation lengths).  Scrub/vote
  counters accumulate on device through `obs.MetricsRegistry`; TMR final
  votes for finished requests are bitwise 2-of-3 majority computed on
  host *from the already-fetched* per-copy rows — same per-bit semantics
  as the `tmr_vote` kernel, zero extra syncs.

Bit-exactness: per-request tokens are independent of what the other
slots are doing.  Every decode op is batch-row-local (masked attention
reads only the row's own pages; page indirection is value-copying), so a
request admitted into a live batch produces exactly the tokens — and
exactly the vote disagreements — it produces when served through the
scheduler alone, under every `standard_grid()` scheme.  Tested in
tests/test_batching.py, including on a forced-host 2x2 mesh.

Typical use (serve.py --server, benchmarks/serve_load.py)::

    spec = BatchSpec(slots=4, page_tokens=16, chunk=8,
                     prompt_buckets=(16,), gen_cap=32)
    b = ContinuousBatcher(cfg, scheme, spec)
    prep = b.prepare(params, key=key, fault=fault)
    results = b.run(poisson_trace(32, rate_rps=8.0, spec=spec,
                                  vocab=cfg.vocab), realtime=True)
    stats = fetch_telemetry({**prep, **b.telemetry()})
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import arena
from ..models.config import ModelConfig
from ..models.steps import make_decode_step, make_prefill_step
from ..obs import (DEFAULT_REGISTRY, RECORDER, LatencyTimeline,
                   MetricsRegistry, Phased, Tracer)
from ..pshard import use_mesh_and_rules
from ..reliability.backend import dispatch as _backend
from ..reliability.scheme import ArenaEcc, Compose, Scheme
from .engine import GenerationEngine, donate_argnums

__all__ = ["BatchSpec", "Request", "RequestResult", "PagedKVPool",
           "ContinuousBatcher", "poisson_trace", "sequential_slot_steps"]


#: named scopes of the tick and admission programs: the phases a device
#: trace of them splits into (`obs.phase_of`)
TICK_PHASES = ("repair", "gather", "step", "scatter", "write_out", "refresh")
ADMIT_PHASES = ("prefill", "place", "refresh")


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class BatchSpec:
    """Static shape of the serving configuration — everything the compiled
    tick program's shapes depend on, so one spec == one tick program.

    slots          : batch rows of the tick program (the max in-flight
                     requests).
    page_tokens    : tokens per KV page.
    chunk          : decode steps per scheduler tick (the join/leave
                     granularity).
    prompt_buckets : admissible prompt lengths; one compiled admission
                     program per bucket (requests carry a bucket length).
    gen_cap        : max tokens a request may ask for.
    n_pages        : pool pages (default: full occupancy, slots views of
                     the whole cache window).
    """

    slots: int = 4
    page_tokens: int = 16
    chunk: int = 8
    prompt_buckets: Tuple[int, ...] = (16,)
    gen_cap: int = 32
    n_pages: Optional[int] = None

    def __post_init__(self):
        if self.slots < 1 or self.chunk < 1 or self.gen_cap < 1:
            raise ValueError(f"slots/chunk/gen_cap must be >= 1: {self}")
        if self.page_tokens < 1:
            raise ValueError(f"page_tokens must be >= 1: {self}")
        if not self.prompt_buckets:
            raise ValueError("need at least one prompt bucket")

    @property
    def max_prompt(self) -> int:
        return max(self.prompt_buckets)

    @property
    def cache_tokens(self) -> int:
        """S_cap: the per-slot cache window every gathered view exposes.
        Includes `chunk` slack so the final tick's overgenerated writes
        (discarded tokens past a request's length) land inside the window
        instead of clamping onto live history."""
        raw = self.max_prompt + self.gen_cap + self.chunk
        return _ceil_div(raw, self.page_tokens) * self.page_tokens

    @property
    def max_pages(self) -> int:
        """Page-table width: pages per slot covering the full window."""
        return self.cache_tokens // self.page_tokens

    @property
    def pool_pages(self) -> int:
        return self.n_pages if self.n_pages is not None \
            else self.slots * self.max_pages

    @property
    def out_cap(self) -> int:
        """Output-ring width: gen_cap plus chunk slack for the final
        tick's overgenerated (discarded) tokens."""
        return self.gen_cap + self.chunk

    def pages_for(self, prompt_len: int, gen: int) -> int:
        """Pages reserved at admission — the whole request up front, so an
        admitted request can never stall mid-stream on allocation."""
        return _ceil_div(prompt_len + gen, self.page_tokens)


@dataclasses.dataclass
class Request:
    """One serving request.  `prompt` length must be a spec bucket."""
    rid: int
    prompt: np.ndarray
    gen: int
    arrival_s: float = 0.0


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: np.ndarray          # (gen,) int32 — voted for TMR schemes
    ttft_s: float               # submit -> first token (queue wait included)
    tpot_samples: List[float]   # per-token seconds from the chunk marks
    vote_disagreements: int     # positions where the 3 copies differed
    timeline: LatencyTimeline


@dataclasses.dataclass
class _Active:
    req: Request
    pages: np.ndarray
    emitted: int
    timeline: LatencyTimeline


class PagedKVPool:
    """Page-granular KV storage for one `BatchSpec`, ECC-protectable.

    Layout: k/v arrays of shape (pool_pages + 1, L, page_tokens, KV·hd)
    in the model compute dtype — page 0 is reserved scratch — with a
    leading 3-copy axis when `copies` (TMR/Compose store three
    independent cache states, one per weight copy; they are never voted
    or parity-shared across copies — each copy's KV is legitimate state
    of *that* copy's generation).  The trailing axis holds the kv heads
    and head_dim merged, in the same row-major order as (KV, hd): the
    packed words, parity rows and word indices are those of a
    (…, KV, hd) pool, while the TPU tiles the merged axis into lanes
    whatever head_dim is.  Only the dense view the decode step reads
    splits it (`ContinuousBatcher._gather`).

    With `ecc`, the whole pool (all copies) packs into ONE block-aligned
    uint32 arena — the word code is block-local and every page spans a
    whole number of ECC blocks, so an uncorrectable block is attributable
    to exactly one page — and carries one parity table.  `scrub()` /
    `inject_scrub()` are each ONE fused launch over that arena, counters
    on device.
    """

    def __init__(self, cfg: ModelConfig, spec: BatchSpec, *,
                 copies: bool, ecc: Optional[ArenaEcc] = None):
        self.cfg, self.spec, self.ecc, self.copies = cfg, spec, ecc, copies
        L, KV, hd = cfg.n_layers, cfg.n_kv, cfg.head_dim
        self.page_shape = (L, spec.page_tokens, KV * hd)
        if ecc is not None:
            pw = arena.words_for(self.page_shape, cfg.cdtype)
            if pw % arena.BLOCK:
                raise ValueError(
                    f"ECC-protected pool needs pages spanning whole "
                    f"{arena.BLOCK}-word blocks; page {self.page_shape} "
                    f"{cfg.cdtype} = {pw} words — raise page_tokens")
        shape = (spec.pool_pages + 1,) + self.page_shape
        if copies:
            shape = (3,) + shape
        self.k = jnp.zeros(shape, cfg.cdtype)
        self.v = jnp.zeros(shape, cfg.cdtype)
        self.arena_spec = arena.arena_spec({"k": self.k, "v": self.v})
        self.parity = None
        if ecc is not None:
            self.parity = ecc.encode_arena(
                arena.pack({"k": self.k, "v": self.v})[0])
        self._free: List[int] = list(range(1, spec.pool_pages + 1))
        self._scrub_fn = None
        self._inject_fns: Dict[Any, Any] = {}

    # -- host-side page allocator -------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[np.ndarray]:
        """Reserve n pages (LIFO — freshly freed pages are reused first,
        which the reuse test relies on); None when short."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        return np.asarray(pages, np.int32)

    def free(self, pages: np.ndarray) -> None:
        for p in reversed(list(map(int, pages))):
            if p <= 0 or p > self.spec.pool_pages:
                raise ValueError(f"bad page id {p}")
            if p in self._free:
                raise ValueError(f"double free of page {p}")
            self._free.append(p)

    # -- fused reliability ops over the packed pool arena ---------------------

    def scrub(self) -> jax.Array:
        """One fused scrub of the whole pool against its parity table;
        returns the on-device (3,) counts (corrected, parity_fixed,
        uncorrectable).  Call between ticks (parity is tick-fresh by the
        write-back discipline)."""
        if self.ecc is None:
            raise ValueError("pool has no ECC (scheme carries no parity)")
        if self._scrub_fn is None:
            ecc, aspec = self.ecc, self.arena_spec

            def run(k, v, parity):
                with jax.named_scope("scrub"):
                    fixed, par2, counts = ecc.scrub_arena(
                        arena.pack({"k": k, "v": v})[0], parity)
                    kv = arena.unpack(fixed, aspec)
                return kv["k"], kv["v"], par2, counts

            self._scrub_fn = jax.jit(run)
        self.k, self.v, self.parity, counts = \
            self._scrub_fn(self.k, self.v, self.parity)
        return counts

    def inject_scrub(self, key: jax.Array, fault, dt: float = 1.0
                     ) -> jax.Array:
        """One fused corrupt+repair launch over the pool arena: sample the
        fault model's XOR word mask, then the `inject_scrub` kernel.
        Returns on-device (4,) counts (injected, corrected, parity_fixed,
        uncorrectable)."""
        if self.ecc is None:
            raise ValueError("pool has no ECC (scheme carries no parity)")
        fkey = (fault, float(dt))
        if fkey not in self._inject_fns:
            ecc, aspec = self.ecc, self.arena_spec

            def run(k, v, parity, key):
                buf = arena.pack({"k": k, "v": v})[0]
                mask = fault.word_mask(key, buf, dt)
                # the scheme picks its fused path (diag parity routes to
                # the dedicated inject_scrub kernel; other codes XOR+scrub
                # inside the same jit region)
                fixed, par2, counts = ecc.inject_scrub_arena(buf, parity,
                                                             mask)
                kv = arena.unpack(fixed, aspec)
                return kv["k"], kv["v"], par2, counts

            self._inject_fns[fkey] = jax.jit(run)
        self.k, self.v, self.parity, counts = \
            self._inject_fns[fkey](self.k, self.v, self.parity, key)
        return counts

    def corrupt(self, key: jax.Array, fault, dt: float = 1.0) -> jax.Array:
        """Corrupt-only exposure: apply one fault-model interval to the
        pool data WITHOUT repairing it — parity stays untouched (it still
        describes the pre-fault bits, which is exactly what a later scrub
        or a write-back read needs to repair against).  Drives the
        write-back-on-read and adaptive-scrub benchmarks, where faults
        must accumulate between repair points.  Returns the on-device
        injected-flip count."""
        fkey = ("corrupt", fault, float(dt))
        if fkey not in self._inject_fns:
            aspec = self.arena_spec

            def run(k, v, key):
                buf = arena.pack({"k": k, "v": v})[0]
                mask = fault.word_mask(key, buf, dt)
                kv = arena.unpack(buf ^ mask, aspec)
                injected = jnp.sum(
                    jax.lax.population_count(mask).astype(jnp.int32))
                return kv["k"], kv["v"], injected

            self._inject_fns[fkey] = jax.jit(run)
        self.k, self.v, injected = self._inject_fns[fkey](self.k, self.v,
                                                          key)
        return injected

    def corrupt_page(self, page: int, *, bit: int = 7, word: int = 0,
                     copy: int = 0) -> None:
        """Test hook: flip one stored bit of one page's k-plane through
        the arena word view (so the flip is exactly what a scrub must
        repair)."""
        buf = arena.pack({"k": self.k, "v": self.v})[0]
        pw = arena.words_for(self.page_shape, self.cfg.cdtype)
        idx = (copy * (self.spec.pool_pages + 1) + page) * pw + word \
            if self.copies else page * pw + word
        buf = buf.at[idx].set(buf[idx] ^ jnp.uint32(1 << bit))
        kv = arena.unpack(buf, self.arena_spec)
        self.k, self.v = kv["k"], kv["v"]


class ContinuousBatcher:
    """Chunk-boundary scheduler over the paged pool (module doc)."""

    def __init__(self, cfg: ModelConfig, scheme: Optional[Scheme] = None,
                 spec: BatchSpec = BatchSpec(), *, mesh=None, rules=None,
                 scrub_every: int = 0, adaptive=None,
                 forced_scrub_ticks: Optional[Sequence[int]] = None,
                 registry: MetricsRegistry = DEFAULT_REGISTRY,
                 tracer: Tracer = RECORDER):
        if cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"continuous batching supports dense/moe decode caches; "
                f"{cfg.family!r} caches are not paged yet")
        self.cfg, self.spec = cfg, spec
        # the engine supplies prepare() (fault keys/scrubs bit-identical
        # to whole-batch serving), the exec mesh and the scheme plumbing;
        # its compiled generation paths are not used by the scheduler.
        self.engine = GenerationEngine(cfg, scheme, gen=spec.gen_cap,
                                       cache_len=spec.cache_tokens,
                                       mesh=mesh, rules=rules)
        self.scheme = self.engine.scheme
        self._copy = self.engine.copy_axis
        self._serial = self.engine._discipline() == "serial"
        self.ecc = self.scheme if isinstance(self.scheme, ArenaEcc) \
            else self.scheme.ecc if isinstance(self.scheme, Compose) else None
        self.pool = PagedKVPool(cfg, spec, copies=self._copy, ecc=self.ecc)
        S, cap = spec.slots, spec.out_cap
        lead = (3,) if self._copy else ()
        self._tok = jnp.zeros(lead + (S, 1), jnp.int32)
        self._out = jnp.zeros(lead + (S, cap), jnp.int32)
        self._pos = jnp.zeros((S,), jnp.int32)
        self.table = np.zeros((S, spec.max_pages), np.int32)
        self._slots: List[Optional[_Active]] = [None] * S
        self.queue: Deque[Tuple[Request, LatencyTimeline]] = deque()
        self.results: Dict[int, RequestResult] = {}
        self.store = None
        self.ticks = 0
        self.decode_slot_steps = 0
        self.scrub_every = int(scrub_every)
        #: optional runtime.AdaptiveScrub: pay-as-you-fault scrub cadence.
        #: Overrides scrub_every; each pool scrub's counts are fetched and
        #: fed back (`record`) — the ONE documented exception to the
        #: zero-sync contract, amortized away exactly when it matters
        #: (quiet stores back off to rare scrubs, hence rare fetches).
        self.adaptive = adaptive
        #: replay hook: scrub at exactly these tick indices (overrides
        #: both cadences) — lets a fixed-cadence run be replayed under a
        #: recorded adaptive schedule for bit-exactness tests
        self._forced_scrub = (None if forced_scrub_ticks is None
                              else frozenset(int(t)
                                             for t in forced_scrub_ticks))
        #: tick indices at which the pool was scrubbed (whatever cadence
        #: chose them) — feed back as forced_scrub_ticks to replay
        self.scrub_ticks: List[int] = []
        #: host callback fired at the top of every tick, before the launch
        #: (fault-injection hook for benchmarks/tests: e.g.
        #: ``b.on_tick = lambda b: b.pool.corrupt(next_key(), fault)``)
        self.on_tick = None
        self._registry = registry
        #: host spans and counters of every tick, admission and request
        #: (the process-wide flight recorder unless given another)
        self.tracer = tracer
        self._admissions = 0
        self._wb = self.ecc is not None and self.ecc.write_back
        self._telem = registry.zeros(
            ["ecc_corrected", "ecc_parity_fixed", "ecc_uncorrectable",
             "ecc_read_corrected", "ecc_read_parity_fixed",
             "ecc_read_uncorrectable"])
        self._tokens_emitted = 0
        self._vote_disagreements = 0
        self._prep: Dict[str, Any] = {}
        self._tick_fn = None
        self._admit_fns: Dict[int, Any] = {}

    # -- program builders -----------------------------------------------------

    def _gather(self, pool, table):
        """(pool_pages+1, L, P, KV·hd)[table (S, MP)] ->
        (L, S, S_cap, KV, hd): every slot's page-table view as a dense
        cache, the merged head axis split only here.  Pure value-copy —
        page identity cannot affect tokens."""
        S, MP = table.shape
        g = pool[table]                                # (S, MP, L, P, KV·hd)
        g = jnp.moveaxis(g, 2, 0)                      # (L, S, MP, P, KV·hd)
        return g.reshape(g.shape[0], S, MP * self.spec.page_tokens,
                         self.cfg.n_kv, self.cfg.head_dim)

    def _scatter(self, pool, table, cache):
        """Inverse of `_gather`: merge the head axes of the mutated views
        (L, S, S_cap, KV, hd) and write them back as pages — the tick's
        whole table, or one slot's row at admission.  Scratch page 0
        appears once per unreserved table entry; the duplicate writes
        race, but nothing ever reads page 0 through a validity mask, so
        the winner is immaterial."""
        S, MP = table.shape
        L = cache.shape[0]
        c = cache.reshape(L, S, MP, self.spec.page_tokens, -1)
        c = jnp.moveaxis(c, 0, 2)                      # (S, MP, L, P, KV·hd)
        return pool.at[table].set(c.astype(pool.dtype))

    def _refresh_parity(self, pk, pv, parity, pages=None):
        """Write-back parity for the pool the program just mutated — in
        the same launch, so parity is never stale between launches.

        With `pages` (traced int32 page ids), only those pages' parity
        rows are re-encoded: the word code is block-local and every page
        spans whole blocks, so refreshed rows are bit-identical to a full
        re-encode, and untouched pages' rows are already fresh from the
        launch that last wrote them (the tick scatter rewrites every
        table page, but pages outside pos..pos+chunk-1 round-trip
        unchanged values).  Duplicate ids (scratch page 0 appears once
        per slot) write identical rows — the .at[].set race is benign.
        Pool-sized encode -> touched-pages encode is the difference
        between parity costing like a scrub and costing like the chunk's
        own KV writes."""
        if self.ecc is None:
            return parity
        if pages is None:
            return self.ecc.encode_arena(arena.pack({"k": pk, "v": pv})[0])
        # page-granular gather (never materialize the full packed pool):
        # pack just the touched pages, encode, scatter the parity rows
        kg = pk[:, pages] if self._copy else pk[pages]
        vg = pv[:, pages] if self._copy else pv[pages]
        rows = self.ecc.encode_arena(arena.pack({"k": kg, "v": vg})[0])
        pwb = arena.words_for(self.pool.page_shape, self.cfg.cdtype) \
            // arena.BLOCK
        nkb = arena.words_for(self.pool.k.shape, self.cfg.cdtype) \
            // arena.BLOCK
        npg = self.spec.pool_pages + 1
        copies = jnp.arange(3 if self._copy else 1, dtype=jnp.int32)
        # global parity-row base per (copy, page), in the gathered pack's
        # own (copy-major, then page) order for both planes
        kbase = (copies[:, None] * npg + pages[None, :]) * pwb
        j = jnp.arange(pwb, dtype=jnp.int32)
        at = jnp.concatenate([(kbase[..., None] + j).reshape(-1),
                              (nkb + kbase[..., None] + j).reshape(-1)])
        return parity.at[at].set(rows)

    def _correct_pages(self, pk, pv, parity, pages):
        """Write-back-on-read (DESIGN.md §18): repair exactly the pages
        this tick is about to read, persisting both the corrected bits
        and their healed parity rows — so hot pages never carry a fault
        into the decode and never wait for the periodic scrub.  Runs in
        the pool layout BEFORE the gather (the gathered cache view is
        transposed per slot, so it cannot pair with parity rows); the
        global parity-row arithmetic is `_refresh_parity`'s.  Duplicate
        ids (scratch page 0 appears once per unreserved table entry)
        correct identical bits to identical values — the scatter race is
        benign, though a fault on scratch page 0 counts once per
        duplicate in the returned (3,) counts (scratch never holds live
        data, so the over-count is cosmetic)."""
        kg = pk[:, pages] if self._copy else pk[pages]
        vg = pv[:, pages] if self._copy else pv[pages]
        buf, gspec = arena.pack({"k": kg, "v": vg})
        pwb = arena.words_for(self.pool.page_shape, self.cfg.cdtype) \
            // arena.BLOCK
        nkb = arena.words_for(self.pool.k.shape, self.cfg.cdtype) \
            // arena.BLOCK
        npg = self.spec.pool_pages + 1
        copies = jnp.arange(3 if self._copy else 1, dtype=jnp.int32)
        kbase = (copies[:, None] * npg + pages[None, :]) * pwb
        j = jnp.arange(pwb, dtype=jnp.int32)
        at = jnp.concatenate([(kbase[..., None] + j).reshape(-1),
                              (nkb + kbase[..., None] + j).reshape(-1)])
        fixed, rows2, counts = self.ecc.scrub_arena(buf, parity[at])
        kv = arena.unpack(fixed, gspec)
        if self._copy:
            pk = pk.at[:, pages].set(kv["k"])
            pv = pv.at[:, pages].set(kv["v"])
        else:
            pk = pk.at[pages].set(kv["k"])
            pv = pv.at[pages].set(kv["v"])
        return pk, pv, parity.at[at].set(rows2), counts

    def _tick_program(self):
        if self._tick_fn is not None:
            return self._tick_fn
        decode = make_decode_step(self.cfg)
        chunk = self.spec.chunk
        copy, serial = self._copy, self._serial
        wb = self.ecc is not None and self.ecc.write_back

        def one(params, tok, pk, pv, pos, table):
            with jax.named_scope("gather"):
                cache = {"pos": pos, "k": self._gather(pk, table),
                         "v": self._gather(pv, table)}

            def body(carry, _):
                tok, cache = carry
                ntok, _, cache = decode(params, tok, cache)
                return (ntok, cache), ntok

            with jax.named_scope("step"):
                (tok, cache), toks = jax.lax.scan(body, (tok, cache), None,
                                                  length=chunk)
            with jax.named_scope("scatter"):
                pk = self._scatter(pk, table, cache["k"])
                pv = self._scatter(pv, table, cache["v"])
            with jax.named_scope("write_out"):
                toks = toks[:, :, 0].T       # (chunk, S, 1) -> (S, chunk)
            return tok, pk, pv, cache["pos"], toks

        def write_out(ob, tk, off):
            return jax.lax.dynamic_update_slice(ob, tk, (off,))

        P, MP = self.spec.page_tokens, self.spec.max_pages
        span = (chunk + P - 2) // P + 1   # max pages a chunk's writes span

        def touched(table, pos):
            """Page ids written this tick: each slot's consecutive table
            entries from pos//P on (clipped — overgeneration past the
            reservation resolves to scratch page 0, as do empty slots'
            all-zero rows and stale pos values)."""
            first = pos // P
            idx = jnp.clip(first[:, None]
                           + jnp.arange(span, dtype=pos.dtype)[None, :],
                           0, MP - 1)
            return jnp.take_along_axis(table, idx, axis=1).reshape(-1)

        def tick(store, tok, out, pk, pv, pos, parity, table, off):
            if wb:
                # correct-on-read: the tick reads every table page through
                # the gather, so repair all of them first — in the SAME
                # launch, before the decode sees a single bit
                with jax.named_scope("repair"):
                    pk, pv, parity, rcounts = self._correct_pages(
                        pk, pv, parity, table.reshape(-1))
            else:
                rcounts = jnp.zeros((3,), jnp.int32)
            if copy:
                def f(args):
                    p, t, k, v = args
                    return one(p, t, k, v, pos, table)
                if serial:   # sequential copies: the 1x in-flight property
                    tok, pk, pv, pos3, toks = jax.lax.map(
                        f, (store, tok, pk, pv))
                else:        # one vmapped launch over the copy axis
                    tok, pk, pv, pos3, toks = jax.vmap(f)(
                        (store, tok, pk, pv))
                pos = pos3[0]
                with jax.named_scope("write_out"):
                    out = jax.vmap(jax.vmap(write_out),
                                   in_axes=(0, 0, None))(out, toks, off)
            else:
                tok, pk, pv, pos, toks = one(store, tok, pk, pv, pos, table)
                with jax.named_scope("write_out"):
                    out = jax.vmap(write_out)(out, toks, off)
            with jax.named_scope("refresh"):
                par = self._refresh_parity(pk, pv, parity,
                                           touched(table, pos - chunk))
            return tok, out, pk, pv, pos, par, rcounts

        donate = donate_argnums(1, 2, 3, 4, 5, 6)
        self._tick_fn = Phased(jax.jit(tick, donate_argnums=donate),
                               TICK_PHASES)
        return self._tick_fn

    def _admit_program(self, plen: int):
        if plen in self._admit_fns:
            return self._admit_fns[plen]
        prefill = make_prefill_step(self.cfg, cache_len=self.spec.cache_tokens)
        copy, serial = self._copy, self._serial

        def place(pool, table_row, cache_kv):
            # (L, 1, S_cap, KV, hd) -> pages (MP, L, P, KV·hd) at table_row
            return self._scatter(pool, table_row[None], cache_kv)

        def admit(store, tok, out, pk, pv, pos, parity, tokens, table_row,
                  slot):
            def one(args):
                params, k, v = args
                with jax.named_scope("prefill"):
                    t0, _, cache = prefill(params, {"tokens": tokens})
                with jax.named_scope("place"):
                    return (t0[0, 0], place(k, table_row, cache["k"]),
                            place(v, table_row, cache["v"]))

            if copy:
                if serial:
                    t0, pk, pv = jax.lax.map(one, (store, pk, pv))
                else:
                    t0, pk, pv = jax.vmap(one)((store, pk, pv))
            else:
                t0, pk, pv = one((store, pk, pv))
            with jax.named_scope("place"):
                tok = tok.at[..., slot, 0].set(t0)
                out = out.at[..., slot, 0].set(t0)
                pos = pos.at[slot].set(plen)
            # place() rewrote the slot's whole table row (scratch included
            # for unreserved entries) — refresh exactly those pages
            with jax.named_scope("refresh"):
                par = self._refresh_parity(pk, pv, parity, table_row)
            return tok, out, pk, pv, pos, par

        donate = donate_argnums(1, 2, 3, 4, 5, 6)
        fn = Phased(jax.jit(admit, donate_argnums=donate), ADMIT_PHASES)
        self._admit_fns[plen] = fn
        return fn

    # -- scheduler ------------------------------------------------------------

    def prepare(self, params: Any, key: Optional[jax.Array] = None,
                fault=None, dt: float = 1.0, *, donate: bool = False
                ) -> Dict[str, Any]:
        """Build the protected serving store (engine.prepare: identical
        fault keys and scrubs as whole-batch serving) and attach it.  With
        `donate`, the store takes the memory of `params`, which the caller
        must not read again."""
        self.store, prep = self.engine.prepare(params, key=key, fault=fault,
                                               dt=dt, donate=donate)
        self._prep = dict(prep)
        return prep

    @property
    def active(self) -> int:
        return sum(a is not None for a in self._slots)

    def submit(self, req: Request) -> None:
        plen = len(req.prompt)
        if plen not in self.spec.prompt_buckets:
            raise ValueError(f"prompt length {plen} not in buckets "
                             f"{self.spec.prompt_buckets}")
        if not 1 <= req.gen <= self.spec.gen_cap:
            raise ValueError(f"gen={req.gen} outside 1..{self.spec.gen_cap}")
        tl = LatencyTimeline()
        tl.begin()                      # TTFT clock includes queue wait
        self.queue.append((req, tl))

    def admit(self) -> int:
        """Admit queued requests (FIFO, no overtaking) while a slot and a
        full upfront page reservation are available.  Returns the number
        admitted; each admission is one compiled launch."""
        if self.store is None:
            raise RuntimeError("call prepare() before serving")
        n = 0
        while self.queue:
            req, tl = self.queue[0]
            slot = next((i for i, a in enumerate(self._slots) if a is None),
                        None)
            if slot is None:
                break
            pages = self.pool.alloc(self.spec.pages_for(len(req.prompt),
                                                        req.gen))
            if pages is None:
                break
            self.queue.popleft()
            self._admit_one(req, tl, slot, pages)
            n += 1
        return n

    def _admit_one(self, req, tl, slot, pages):
        tr, rid = self.tracer, req.rid
        with tr.trace("batcher.admit", rid=rid):
            with tr.trace("admit.launch", rid=rid):
                row = np.zeros(self.spec.max_pages, np.int32)
                row[:len(pages)] = pages
                self.table[slot] = row
                fn = self._admit_program(len(req.prompt))
                tokens = jnp.asarray(np.asarray(req.prompt,
                                                np.int32)[None, :])
                with use_mesh_and_rules(self.engine.exec_mesh,
                                        self.engine.rules):
                    (self._tok, self._out, self.pool.k, self.pool.v,
                     self._pos, self.pool.parity) = fn(
                        self.store, self._tok, self._out, self.pool.k,
                        self.pool.v, self._pos, self.pool.parity, tokens,
                        jnp.asarray(row), jnp.int32(slot))
            with tr.trace("admit.wait", rid=rid):
                jax.block_until_ready(self._tok)  # sync, no data transfer
            tl.mark(1)                            # <- TTFT
            self._slots[slot] = _Active(req=req, pages=pages, emitted=1,
                                        timeline=tl)
        self._admissions += 1
        tr.counter("batcher.admissions", self._admissions)

    def tick(self) -> List[RequestResult]:
        """One scheduler tick: `chunk` decode steps for every slot in one
        launch (per copy when serial), then host-side completion
        bookkeeping.  The ONLY device->host transfer is one batched
        `device_get` of finished rows, and only on ticks where a request
        finishes.  Its host path is recorded as a `batcher.tick` span
        with one child per part: `tick.launch` (page table and offsets
        up, dispatch), `tick.wait`, `tick.finish` (finished rows down,
        vote, free), `tick.scrub` and `tick.scrub_fetch`.  The counter
        tracks `batcher.scrubs` and `batcher.admissions` (running totals)
        step at each pool scrub and admission."""
        spec, tr = self.spec, self.tracer
        with tr.trace("batcher.tick"):
            if self.on_tick is not None:
                self.on_tick(self)   # pre-launch hook (fault injection)
            active = [(i, a) for i, a in enumerate(self._slots)
                      if a is not None]
            off = np.zeros(spec.slots, np.int32)
            for i, a in active:
                off[i] = a.emitted
            with tr.trace("tick.launch"):
                with use_mesh_and_rules(self.engine.exec_mesh,
                                        self.engine.rules):
                    (self._tok, self._out, self.pool.k, self.pool.v,
                     self._pos, self.pool.parity, rcounts) = \
                        self._tick_program()(
                            self.store, self._tok, self._out, self.pool.k,
                            self.pool.v, self._pos, self.pool.parity,
                            jnp.asarray(self.table), jnp.asarray(off))
            with tr.trace("tick.wait"):
                jax.block_until_ready(self._tok)
            with tr.trace("tick.finish"):
                finished = self._complete(active, rcounts)
            if self.ecc is not None and self._scrub_due():
                with tr.trace("tick.scrub"):
                    counts = self.pool.scrub()   # counters stay on device
                    self.scrub_ticks.append(self.ticks)
                    self._telem = self._registry.accumulate(
                        self._telem, {"ecc_corrected": counts[0],
                                      "ecc_parity_fixed": counts[1],
                                      "ecc_uncorrectable": counts[2]})
                if self.adaptive is not None and self._forced_scrub is None:
                    # the documented zero-sync exception: the controller
                    # needs the counts on host to reschedule; one (4,)-int
                    # fetch per scrub, and scrubs get RARER as the
                    # controller backs off
                    with tr.trace("tick.scrub_fetch"):
                        c = np.asarray(jax.device_get(counts))
                        self.adaptive.record(self.ticks, int(c[0]),
                                             int(c[2]), int(c[1]))
                tr.counter("batcher.scrubs", len(self.scrub_ticks))
        return finished

    def _complete(self, active, rcounts) -> List[RequestResult]:
        """Host-side completion after a tick: token marks, then ONE
        batched fetch of every finished row, vote and free."""
        spec = self.spec
        if self._wb:
            # read-path repairs land in their own counters (on device)
            self._telem = self._registry.accumulate(
                self._telem, {"ecc_read_corrected": rcounts[0],
                              "ecc_read_parity_fixed": rcounts[1],
                              "ecc_read_uncorrectable": rcounts[2]})
        self.ticks += 1
        self.decode_slot_steps += spec.chunk * spec.slots
        done: List[Tuple[int, _Active]] = []
        for i, a in active:
            fresh = min(spec.chunk, a.req.gen - a.emitted)
            if fresh > 0:
                a.timeline.mark(fresh)
            a.emitted = min(a.req.gen, a.emitted + spec.chunk)
            if a.emitted >= a.req.gen:
                done.append((i, a))
        finished: List[RequestResult] = []
        if done:
            # ONE batched transfer for every finished row this tick
            rows = jax.device_get([self._out[..., i, :] for i, _ in done])
            for (i, a), row in zip(done, rows):
                finished.append(self._finish(i, a, np.asarray(row)))
        return finished

    def _scrub_due(self) -> bool:
        """Which cadence owns this tick: a forced replay schedule beats
        the adaptive controller beats the fixed interval."""
        if self._forced_scrub is not None:
            return self.ticks in self._forced_scrub
        if self.adaptive is not None:
            return self.adaptive.due(self.ticks)
        return bool(self.scrub_every) and self.ticks % self.scrub_every == 0

    def _finish(self, slot, a, row) -> RequestResult:
        gen = a.req.gen
        if self._copy:
            t = row[:, :gen].astype(np.int32)
            # bitwise 2-of-3 majority — per-bit identical to the tmr_vote
            # kernel, on host from the single already-fetched transfer
            tokens = (t[0] & t[1]) | (t[0] & t[2]) | (t[1] & t[2])
            dis = int(np.sum(~((t[0] == t[1]) & (t[0] == t[2]))))
        else:
            tokens, dis = row[:gen].astype(np.int32), 0
        tl = a.timeline
        res = RequestResult(rid=a.req.rid, tokens=tokens, ttft_s=tl.ttft_s,
                            tpot_samples=list(tl.tpot_samples()),
                            vote_disagreements=dis, timeline=tl)
        # submit -> last token, from the timeline's own clock reads
        self.tracer.add_span("request", tl.start * 1e9, tl.marks[-1][0] * 1e9,
                             rid=a.req.rid, tokens=gen)
        self.results[a.req.rid] = res
        self._tokens_emitted += gen
        self._vote_disagreements += dis
        self.pool.free(a.pages)
        self.table[slot] = 0
        self._slots[slot] = None
        return res

    def drain(self) -> None:
        """Tick until every queued and in-flight request has finished."""
        while self.queue or self.active:
            self.admit()
            if self.active:
                self.tick()
            elif self.queue:
                req, _ = self.queue[0]
                raise RuntimeError(
                    f"request {req.rid} needs "
                    f"{self.spec.pages_for(len(req.prompt), req.gen)} pages "
                    f"but the idle pool has {self.pool.free_pages} of "
                    f"{self.spec.pool_pages} — pool too small")

    def run(self, requests: Sequence[Request], *, realtime: bool = False
            ) -> List[RequestResult]:
        """Serve a trace to completion.  realtime=True paces submissions
        by `arrival_s` (open loop — arrivals never wait for service);
        False submits in arrival order immediately (deterministic, for
        tests)."""
        order = sorted(requests, key=lambda r: r.arrival_s)
        t0 = time.perf_counter()
        i, n = 0, len(order)
        while i < n or self.queue or self.active:
            now = time.perf_counter() - t0
            while i < n and (not realtime or order[i].arrival_s <= now):
                self.submit(order[i])
                i += 1
            self.admit()
            if self.active:
                self.tick()
            elif self.queue:
                self.drain()        # raises: pool too small for the head
            elif realtime and i < n:
                time.sleep(max(0.0, min(0.005,
                                        order[i].arrival_s - now)))
        return [self.results[r.rid] for r in requests]

    def telemetry(self) -> Dict[str, Any]:
        """Schema-valid telemetry dict — device counters plus host tallies;
        fetch once with `obs.fetch_telemetry` after timing stops.  The
        prepare-time scrub counters are folded into the totals, so the
        serve-driver merge idiom ``{**prep, **batcher.telemetry()}``
        yields grand totals rather than letting fresh zeros shadow the
        prepare counts."""
        out: Dict[str, Any] = dict(self._telem)
        for k, v in self._prep.items():
            out[k] = out[k] + v if k in out else v
        out["tokens_emitted"] = np.int32(self._tokens_emitted)
        if self._copy:
            out["tmr_final_disagreements"] = \
                np.int32(self._vote_disagreements)
        return out


# -- load generation and the whole-batch baseline ----------------------------

def poisson_trace(n: int, *, rate_rps: float, spec: BatchSpec, vocab: int,
                  seed: int = 0,
                  gen_choices: Optional[Sequence[int]] = None,
                  gen_weights: Optional[Sequence[float]] = None
                  ) -> List[Request]:
    """Open-loop Poisson trace: exponential inter-arrivals at `rate_rps`,
    prompt lengths drawn from the spec's buckets, generation lengths from
    `gen_choices` (default: a skewed short/long mix over gen_cap —
    the workload continuous batching exists for)."""
    rng = np.random.default_rng(seed)
    if gen_choices is None:
        gen_choices = [max(1, spec.gen_cap // 4), spec.gen_cap]
        gen_weights = [0.75, 0.25]
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, n))
    out = []
    for i in range(n):
        plen = int(rng.choice(np.asarray(spec.prompt_buckets)))
        gen = int(rng.choice(np.asarray(gen_choices), p=gen_weights))
        out.append(Request(rid=i,
                           prompt=rng.integers(0, vocab, (plen,),
                                               dtype=np.int32),
                           gen=gen, arrival_s=float(arrivals[i])))
    return out


def sequential_slot_steps(requests: Sequence[Request], slots: int) -> int:
    """Decode slot-steps whole-batch serving spends on a trace: requests
    grouped `slots` at a time in arrival order, every row of a group
    padded to the group's longest generation (the engine's fixed-batch
    contract).  Compare with `ContinuousBatcher.decode_slot_steps` for
    the machine-independent goodput ratio."""
    order = sorted(requests, key=lambda r: r.arrival_s)
    total = 0
    for g in range(0, len(order), slots):
        grp = order[g:g + slots]
        total += slots * max(r.gen for r in grp)
    return total
