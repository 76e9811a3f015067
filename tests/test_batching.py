"""Continuous-batching reliable serving (DESIGN.md §16).

The acceptance bar: a request admitted into a LIVE batch mid-stream
produces exactly the tokens — and exactly the vote counters — it produces
when served through the scheduler alone (same bucket shapes), for every
standard_grid() scheme, on one device and on a forced-host 2x2 mesh; a
scheduler tick performs at most one device->host sync (the batched
completion fetch), enforced by the transfer guard; and continuous batching
beats sequential whole-batch serving >= 2x in decode slot-steps on a
skewed trace.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import arena
from repro.faults import TransientBitFlips
from repro.launch import (BatchSpec, ContinuousBatcher, GenerationEngine,
                          PagedKVPool, Request, fetch_telemetry,
                          poisson_trace, sequential_slot_steps)
from repro.launch.mesh import make_test_mesh
from repro.models import params as P
from repro.models import transformer as T
from repro.obs import count_host_transfers
from repro.reliability.scheme import parse_scheme, standard_grid

MULTI = jax.device_count() >= 4
P_BIT = 2e-3          # dense enough that ECC counters are live
SPEC = BatchSpec(slots=2, page_tokens=8, chunk=3, prompt_buckets=(4, 8),
                 gen_cap=6)


def _cfg():
    # micro config (shared with test_sharded_engine): tiny but with every
    # shardable dim divisible by the test meshes
    return get_config("phi3-mini-3.8b").smoke().replace(
        n_layers=1, d_model=16, n_heads=2, n_kv=2, d_ff=32, vocab=512)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    params = P.materialize(key, T.model_specs(cfg))
    prompts = {n: np.asarray(jax.random.randint(
        jax.random.fold_in(key, n), (n,), 0, cfg.vocab)) for n in (4, 8)}
    return cfg, key, params, prompts


def _serve_alone(cfg, params, key, scheme, req, mesh=None):
    b = ContinuousBatcher(cfg, scheme, SPEC, mesh=mesh)
    b.prepare(params, key=key, fault=TransientBitFlips(P_BIT))
    return b.run([req])[0]


# -- the acceptance bar: join-live-batch == served-alone ---------------------

@pytest.mark.parametrize("scheme", standard_grid(), ids=lambda s: s.name)
def test_join_live_batch_matches_alone(setup, scheme):
    """rid=9 arrives while both slots are busy, queues, and is admitted
    mid-stream when the short request frees its slot; its tokens and
    per-request vote counter must match the alone run bit for bit."""
    cfg, key, params, prompts = setup
    b = ContinuousBatcher(cfg, scheme, SPEC)
    b.prepare(params, key=key, fault=TransientBitFlips(P_BIT))
    reqs = [Request(0, prompts[8], 6, arrival_s=0.0),
            Request(1, prompts[4], 2, arrival_s=0.0),
            Request(9, prompts[8], 5, arrival_s=0.1)]
    res = {r.rid: r for r in b.run(reqs)}
    alone = _serve_alone(cfg, params, key, scheme, Request(9, prompts[8], 5))
    np.testing.assert_array_equal(res[9].tokens, alone.tokens)
    assert res[9].vote_disagreements == alone.vote_disagreements
    # the mid-stream batch really was live: rid=9 queued behind a full batch
    assert res[9].ttft_s > 0 and len(res[9].tokens) == 5


def test_fault_counters_live(setup):
    """The bit-exactness runs must exercise real corruption — a fault rate
    that never fires would pass vacuously."""
    cfg, key, params, prompts = setup
    b = ContinuousBatcher(cfg, parse_scheme("ecc"), SPEC)
    prep = b.prepare(params, key=key, fault=TransientBitFlips(P_BIT))
    b.run([Request(0, prompts[8], 4)])
    stats = fetch_telemetry({**prep, **b.telemetry()})
    assert int(stats["ecc_corrected"]) > 0
    assert int(stats["tokens_emitted"]) == 4


# -- zero-sync scheduler contract --------------------------------------------

def test_tick_single_transfer_contract(setup):
    """Extends the PR-7 transfer guard to the scheduler: the only
    device->host sync a tick may perform is ONE batched device_get of
    finished rows — so total syncs over a run equal the number of ticks
    on which some request completed, and the telemetry fetch stays one."""
    cfg, key, params, prompts = setup
    scheme = parse_scheme("ecc+tmr")          # worst case: pool parity +
    b = ContinuousBatcher(cfg, scheme, SPEC,  # copy axis + device scrubs
                          scrub_every=2)
    prep = b.prepare(params, key=key, fault=TransientBitFlips(P_BIT))
    b.run([Request(99, prompts[8], 3)])       # warmup: compile everything
    reqs = [Request(0, prompts[8], 6), Request(1, prompts[4], 2),
            Request(2, prompts[8], 5), Request(3, prompts[4], 3)]
    for r in reqs:
        b.submit(r)
    completion_ticks = 0
    with count_host_transfers() as ledger:
        b.admit()
        while b.active or b.queue:
            if b.tick():
                completion_ticks += 1
            b.admit()
    assert completion_ticks > 0
    assert ledger.syncs == completion_ticks, ledger.sites
    assert completion_ticks <= b.ticks
    with count_host_transfers() as ledger2:
        stats = fetch_telemetry({**prep, **b.telemetry()})
    assert ledger2.syncs == 1, ledger2.sites
    assert int(stats["tokens_emitted"]) == 3 + sum(r.gen for r in reqs)
    assert int(stats["ecc_corrected"]) > 0


# -- goodput: continuous batching vs whole-batch serving ---------------------

# -- device phases and the flight recorder -----------------------------------

PHASE_SPEC = BatchSpec(slots=2, page_tokens=16, chunk=4,
                       prompt_buckets=(16,), gen_cap=8)


@pytest.fixture(scope="module")
def smoke_served():
    """`.smoke()` widths under hsiao-wb and off: each scheme's batcher
    after serving two requests, and their tokens."""
    cfg = get_config("phi3-mini-3.8b").smoke()
    key = jax.random.PRNGKey(0)
    params = P.materialize(key, T.model_specs(cfg), dtype=cfg.cdtype)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab, 16, dtype=np.int32), g)
            for i, g in enumerate((8, 5))]
    out = {}
    for name in ("hsiao-wb", "off"):
        b = ContinuousBatcher(cfg, parse_scheme(name), PHASE_SPEC)
        b.prepare(params, key=key)
        out[name] = (b, [r.tokens for r in b.run(reqs)])
    return cfg, key, params, reqs, out


def _mapped(program):
    """Phases of the program's instructions that do work: parameters,
    constants, tuples and loop control left out."""
    from repro.obs.phases import STRUCTURAL
    return [p for n, (p, *_) in program.phase_map.items()
            if program.opcodes[n] not in STRUCTURAL
            and program.opcodes[n] != "loop-control"]


def test_the_tick_is_split_into_its_phases(smoke_served):
    *_, out = smoke_served
    wb, off = out["hsiao-wb"][0]._tick_program(), out["off"][0]._tick_program()
    assert wb.program == off.program == "tick"
    assert {"repair", "gather", "step", "scatter", "refresh"} <= \
        set(_mapped(wb))
    assert {"gather", "step", "scatter"} <= set(_mapped(off))
    assert not {"repair", "refresh"} & set(_mapped(off))
    for prog in (wb, off):
        phases = _mapped(prog)
        assert sum(p is not None for p in phases) >= 0.95 * len(phases)
        # a phase is inherited only from an instruction that owns it
        entries = [e for n, e in prog.phase_map.items()
                   if e[0] is not None and prog.opcodes[n] != "loop-control"]
        assert any(inh for _, _, inh in entries)
        assert {p for p, _, inh in entries if not inh} == \
            {p for p, *_ in entries}


def test_admission_phases_and_lookup_by_instruction_text(smoke_served,
                                                         monkeypatch):
    from repro import obs
    from repro.obs import phases as PH
    *_, out = smoke_served
    b = out["hsiao-wb"][0]
    admit = b._admit_program(16)
    assert admit.program == "admit"
    assert {"prefill", "place", "refresh"} <= set(_mapped(admit))
    # the lookup takes an op's instruction text, as a trace names it;
    # a registry holding this program alone
    tick = b._tick_program()
    monkeypatch.setattr(PH, "_MAPS", {})
    PH.register_phases(tick.compiled.as_text(), tick.phases)
    texts = {}
    for ln in tick.compiled.as_text().splitlines():
        ln = ln.strip().removeprefix("ROOT ")
        if ln.startswith("%") and " = " in ln:
            texts[ln[1:ln.index(" = ")]] = ln.split(", metadata=")[0]
    hits = 0
    for name, (phase, *_) in tick.phase_map.items():
        if phase is not None:
            assert obs.phase_of(texts[name], "tick") == phase
            hits += 1
    assert hits > 10
    assert obs.phase_of("%no_such_instruction.1 = f32[2]{0} add()",
                        "tick") is None
    assert obs.phase_of("not an instruction") is None
    # where two programs of one name disagree on an instruction of the
    # same result type, the lookup refuses to guess
    off = out["off"][0]._tick_program()
    PH.register_phases(off.compiled.as_text(), off.phases)
    disagree = [n for n, (p, sig, _) in tick.phase_map.items()
                if off.phase_map.get(n, (p, None))[1] == sig
                and off.phase_map[n][0] != p]
    assert disagree
    for n in disagree:
        assert obs.phase_of(texts[n], "tick") is None


def test_tokens_are_bit_exact_with_and_without_the_recorder(smoke_served):
    from repro.obs import NULL_TRACER, RECORDER
    cfg, key, params, reqs, out = smoke_served
    b, tokens = out["hsiao-wb"]
    assert b.tracer is RECORDER
    quiet = ContinuousBatcher(cfg, parse_scheme("hsiao-wb"), PHASE_SPEC,
                              tracer=NULL_TRACER)
    quiet.prepare(params, key=key)
    for a, c in zip(tokens, [r.tokens for r in quiet.run(reqs)]):
        np.testing.assert_array_equal(a, c)
    assert NULL_TRACER.events == []


def test_the_recorder_sees_ticks_admissions_and_requests(setup):
    from repro.obs import Tracer
    cfg, key, params, prompts = setup
    tracer = Tracer()
    b = ContinuousBatcher(cfg, parse_scheme("ecc"), SPEC, scrub_every=1,
                          tracer=tracer)
    b.prepare(params, key=key)
    b.run([Request(0, prompts[8], 5), Request(1, prompts[4], 2)])
    spans = tracer.spans()
    ticks = tracer.spans("batcher.tick")
    assert len(ticks) == b.ticks
    by_id = {e["id"]: e for e in spans}
    for e in spans:
        if e["name"].startswith("tick."):
            assert by_id[e["parent"]]["name"] == "batcher.tick"
            parent = by_id[e["parent"]]
            assert parent["ts"] <= e["ts"] and \
                e["ts"] + e["dur"] <= parent["ts"] + parent["dur"]
        if e["name"].startswith("admit."):
            assert by_id[e["parent"]]["name"] == "batcher.admit"
            assert e["rid"] == by_id[e["parent"]]["rid"]
    kids = {e["name"] for e in spans if e["parent"] == ticks[0]["id"]}
    assert kids == {"tick.launch", "tick.wait", "tick.finish", "tick.scrub"}
    assert sorted(e["rid"] for e in tracer.spans("batcher.admit")) == [0, 1]
    reqs = {e["rid"]: e for e in tracer.spans("request")}
    assert set(reqs) == {0, 1}
    res = b.results[0].timeline
    assert reqs[0]["ts"] == int(res.start * 1e9)
    assert reqs[0]["ts"] + reqs[0]["dur"] == int(res.marks[-1][0] * 1e9)
    # two counter tracks, one sample per admission and per pool scrub
    counters = [e for e in tracer.events if e["ph"] == "C"]
    track = lambda n: [e["args"][n] for e in counters if e["name"] == n]
    assert {e["name"] for e in counters} == {"batcher.admissions",
                                             "batcher.scrubs"}
    assert track("batcher.admissions") == [1.0, 2.0]
    assert track("batcher.scrubs") == list(
        map(float, range(1, len(b.scrub_ticks) + 1)))
    assert len(b.scrub_ticks) == b.ticks


def test_tick_single_transfer_contract_with_the_recorder_on(setup):
    """The transfer contract with an enabled recorder of the batcher's
    own, spans checked to have been recorded inside the guarded region."""
    from repro.obs import Tracer
    cfg, key, params, prompts = setup
    tracer = Tracer()
    b = ContinuousBatcher(cfg, parse_scheme("hsiao-wb"), SPEC,
                          scrub_every=2, tracer=tracer)
    b.prepare(params, key=key, fault=TransientBitFlips(P_BIT))
    b.run([Request(99, prompts[8], 3)])       # warmup: compile everything
    n0, ticks0 = len(tracer.spans("batcher.tick")), b.ticks
    for r in [Request(0, prompts[8], 6), Request(1, prompts[4], 2)]:
        b.submit(r)
    completion_ticks = 0
    with count_host_transfers() as ledger:
        b.admit()
        while b.active or b.queue:
            if b.tick():
                completion_ticks += 1
            b.admit()
    assert completion_ticks > 0
    assert ledger.syncs == completion_ticks, ledger.sites
    assert len(tracer.spans("batcher.tick")) - n0 == b.ticks - ticks0 > 0


def test_slot_steps_beat_sequential_2x(setup):
    """On a skewed short/long trace the scheduler recycles the short
    requests' slots while the long ones run; whole-batch serving pads
    every row of a group to the group max.  Machine-independent decode
    slot-step accounting must show >= 2x."""
    cfg, key, params, prompts = setup
    spec = BatchSpec(slots=4, page_tokens=8, chunk=2, prompt_buckets=(4,),
                     gen_cap=16)
    b = ContinuousBatcher(cfg, None, spec)
    b.prepare(params, key=key)
    reqs = [Request(i, prompts[4], 2 if i % 4 else 16,
                    arrival_s=i * 1e-3) for i in range(16)]
    res = b.run(reqs)
    useful = sum(r.gen for r in reqs)
    assert sum(len(r.tokens) for r in res) == useful
    seq = sequential_slot_steps(reqs, spec.slots)
    assert seq >= 2 * b.decode_slot_steps, (seq, b.decode_slot_steps)


def test_poisson_trace_shape():
    trace = poisson_trace(32, rate_rps=8.0, spec=SPEC, vocab=512, seed=3)
    assert len(trace) == 32
    assert all(len(r.prompt) in SPEC.prompt_buckets for r in trace)
    assert all(1 <= r.gen <= SPEC.gen_cap for r in trace)
    arr = [r.arrival_s for r in trace]
    assert arr == sorted(arr) and arr[-1] > 0
    # skewed mix: both short and long generations present
    gens = {r.gen for r in trace}
    assert len(gens) >= 2


# -- scheduler/pool mechanics ------------------------------------------------

def test_admission_validation_and_pool_exhaustion(setup):
    cfg, key, params, prompts = setup
    b = ContinuousBatcher(cfg, None, SPEC)
    b.prepare(params, key=key)
    with pytest.raises(ValueError, match="buckets"):
        b.submit(Request(0, np.zeros(5, np.int32), 2))
    with pytest.raises(ValueError, match="gen"):
        b.submit(Request(0, prompts[4], SPEC.gen_cap + 1))
    # a request whose reservation exceeds the whole pool can never start
    tiny = BatchSpec(slots=2, page_tokens=8, chunk=3, prompt_buckets=(8,),
                     gen_cap=6, n_pages=1)
    b2 = ContinuousBatcher(cfg, None, tiny)
    b2.prepare(params, key=key)
    b2.submit(Request(0, prompts[8], 6))
    with pytest.raises(RuntimeError, match="pool too small"):
        b2.drain()


def test_page_allocator_reuse_and_double_free():
    pool = PagedKVPool(_cfg(), SPEC, copies=False)
    a = pool.alloc(3)
    assert a is not None and pool.free_pages == SPEC.pool_pages - 3
    assert pool.alloc(SPEC.pool_pages) is None    # short -> None, no change
    assert pool.free_pages == SPEC.pool_pages - 3
    pool.free(a)
    assert pool.free_pages == SPEC.pool_pages
    b = pool.alloc(3)
    assert set(map(int, b)) == set(map(int, a))   # freed pages reused
    with pytest.raises(ValueError, match="double free"):
        pool.free(np.concatenate([b, b]))
    with pytest.raises(ValueError, match="bad page"):
        pool.free(np.asarray([0], np.int32))      # scratch is not freeable


def test_page_zero_is_scratch(setup):
    """Empty slots and unreserved table entries point at page 0; whatever
    lands there must never leak into an active request's tokens — covered
    by the join test, but assert the invariant directly."""
    cfg, key, params, prompts = setup
    b = ContinuousBatcher(cfg, None, SPEC)
    b.prepare(params, key=key)
    b.submit(Request(0, prompts[4], 3))
    b.admit()
    assert (b.table[0] == 0).sum() >= 1          # unreserved entries
    assert (b.table[1] == 0).all()               # empty slot
    assert all(p >= 1 for p in b._slots[0].pages)


# -- lane-dense pool pages: (..., KV*hd) is (..., KV, hd) merged ------------

def _split_heads(cfg, x):
    return x.reshape(x.shape[:-1] + (cfg.n_kv, cfg.head_dim))


def _split_pool(cfg, pool):
    """Hold `pool`'s pages as (L, P, KV, hd), the layout before the merge."""
    pool.k, pool.v = _split_heads(cfg, pool.k), _split_heads(cfg, pool.v)
    pool.page_shape = pool.k.shape[-4:]
    pool.arena_spec = arena.arena_spec({"k": pool.k, "v": pool.v})
    return pool


class _UnmergedBatcher(ContinuousBatcher):
    """The same scheduler over a pool that keeps pages as (L, P, KV, hd):
    the reference the merged trailing axis must match word for word."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        _split_pool(self.cfg, self.pool)

    def _gather(self, pool, table):
        S, MP = table.shape
        g = jnp.transpose(pool[table], (2, 0, 1, 3, 4, 5))
        return g.reshape(g.shape[0], S, MP * self.spec.page_tokens,
                         *g.shape[4:])

    def _scatter(self, pool, table, cache):
        S, MP = table.shape
        c = cache.reshape(cache.shape[0], S, MP, self.spec.page_tokens,
                          *cache.shape[3:])
        return pool.at[table].set(
            jnp.transpose(c, (1, 2, 0, 3, 4, 5)).astype(pool.dtype))


def _random_pool(cfg, seed):
    shape = (SPEC.pool_pages + 1, cfg.n_layers, SPEC.page_tokens,
             cfg.n_kv * cfg.head_dim)
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(
        cfg.cdtype)


def test_pool_pages_are_lane_dense_and_pack_in_head_order():
    """The pool stores (L, P, KV*hd) pages, KV*hd off a multiple of 128
    here as on phi3, and packs into the very words of the same values
    held as (L, P, KV, hd)."""
    cfg = _cfg()
    assert (cfg.n_kv * cfg.head_dim) % 128
    pool = PagedKVPool(cfg, SPEC, copies=False, ecc=parse_scheme("hsiao"))
    assert pool.page_shape == (cfg.n_layers, SPEC.page_tokens,
                               cfg.n_kv * cfg.head_dim)
    k, v = _random_pool(cfg, 1), _random_pool(cfg, 2)
    merged = arena.pack({"k": k, "v": v})[0]
    split = arena.pack({"k": _split_heads(cfg, k),
                        "v": _split_heads(cfg, v)})[0]
    np.testing.assert_array_equal(np.asarray(merged), np.asarray(split))
    assert merged.shape[0] == pool.arena_spec.n_words


@pytest.mark.parametrize("word,bit", [(0, 3), (5, 17), (37, 30), (63, 8)])
def test_corrupt_page_flips_the_same_element_as_split_heads(word, bit):
    """`corrupt_page(page, word=w, bit=b)` flips the element with the same
    (layer, token, head, dim) in the merged pool as in a (..., KV, hd)
    one: the word index means what it meant before the merge."""
    cfg = _cfg()
    merged = PagedKVPool(cfg, SPEC, copies=False)
    split = _split_pool(cfg, PagedKVPool(cfg, SPEC, copies=False))
    for pool in (merged, split):
        pool.corrupt_page(3, word=word, bit=bit)
    bits = lambda x: np.asarray(x).view(np.uint16)   # subnormals too
    (pg, l, t, j), = np.argwhere(bits(merged.k))
    (pg2, l2, t2, h2, d2), = np.argwhere(bits(split.k))
    assert (pg, l, t, j // cfg.head_dim, j % cfg.head_dim) == \
        (pg2, l2, t2, h2, d2)
    assert pg == 3


def test_gather_and_scatter_match_the_split_head_reference(setup):
    """The dense view `_gather` forms from merged pages equals the view
    of the same values stored as (..., KV, hd), and `_scatter` writes
    back exactly the pages that reference writes."""
    cfg = setup[0]
    b = ContinuousBatcher(cfg, None, SPEC)
    ref = _UnmergedBatcher(cfg, None, SPEC)
    pool = _random_pool(cfg, 3)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.integers(0, SPEC.pool_pages + 1,
                                     (SPEC.slots, SPEC.max_pages)), jnp.int32)
    view = b._gather(pool, table)
    assert view.shape == (cfg.n_layers, SPEC.slots, SPEC.cache_tokens,
                          cfg.n_kv, cfg.head_dim)
    np.testing.assert_array_equal(
        np.asarray(view), np.asarray(ref._gather(_split_heads(cfg, pool),
                                                 table)))
    # distinct pages, so no write races: the scatters must agree exactly
    table = jnp.asarray(rng.permutation(SPEC.pool_pages + 1)[
        :SPEC.slots * SPEC.max_pages].reshape(SPEC.slots, SPEC.max_pages),
        jnp.int32)
    new = _random_pool(cfg, 4)
    cache = b._gather(new, table)
    np.testing.assert_array_equal(
        np.asarray(_split_heads(cfg, b._scatter(pool, table, cache))),
        np.asarray(ref._scatter(_split_heads(cfg, pool), table, cache)))


@pytest.mark.parametrize("name", ["off", "hsiao-wb", "ecc+tmr-semi"])
def test_served_tokens_and_parity_match_an_unmerged_pool(setup, name):
    """A live batch served from merged pages gives the tokens, the pool
    words and the parity rows of the same batch served from a pool that
    never merges its head axes — faults, write-back repair and a scrub
    included."""
    cfg, key, params, prompts = setup
    reqs = [Request(0, prompts[8], 6), Request(1, prompts[4], 2),
            Request(9, prompts[8], 5, arrival_s=0.1)]

    def serve(cls):
        b = cls(cfg, parse_scheme(name), SPEC, scrub_every=2)
        b.prepare(params, key=key, fault=TransientBitFlips(P_BIT))
        if b.ecc is not None:
            b.pool.corrupt_page(1, word=7, bit=5)
        toks = [r.tokens for r in b.run(reqs)]
        words = arena.pack({"k": b.pool.k, "v": b.pool.v})[0]
        return toks, np.asarray(words), b.pool.parity

    toks, words, parity = serve(ContinuousBatcher)
    toks_ref, words_ref, parity_ref = serve(_UnmergedBatcher)
    for t, r in zip(toks, toks_ref):
        np.testing.assert_array_equal(t, r)
    np.testing.assert_array_equal(words, words_ref)
    if parity is not None:
        np.testing.assert_array_equal(np.asarray(parity),
                                      np.asarray(parity_ref))


# -- engine chunk-compile cache (satellite) ----------------------------------

def test_chunk_cache_bounded_and_bit_exact(setup):
    """Sweeping chunk sizes across one engine keeps the compiled-chunk
    cache LRU-bounded at CHUNK_CACHE_MAX while every chunking stays
    bit-exact against the unchunked scan."""
    cfg, key, params, prompts = setup
    eng = GenerationEngine(cfg, parse_scheme("ecc"), gen=16)
    store, _ = eng.prepare(params, key=key, fault=TransientBitFlips(P_BIT))
    batch = {"tokens": np.asarray(prompts[8])[None, :]}
    ref = np.asarray(eng.generate_scan(store, batch)[0])
    sizes = set()
    for chunk in (1, 3, 5, 6, 7, 9, 11, 15):
        toks, _, _ = eng.generate_chunked(store, batch, chunk=chunk)
        np.testing.assert_array_equal(np.asarray(toks), ref,
                                      err_msg=f"chunk={chunk}")
        sizes.update(eng._chunk_sizes(chunk))
        assert len(eng._chunk_built) <= eng.CHUNK_CACHE_MAX
    assert len(sizes) > eng.CHUNK_CACHE_MAX      # eviction actually fired
    # tail decomposition covers gen-1 steps from {chunk} | {2^k < chunk}
    for chunk in range(1, 20):
        parts = list(eng._chunk_sizes(chunk))
        assert sum(parts) == eng.gen - 1
        assert all(n == chunk or (n & (n - 1)) == 0 for n in parts)


# -- forced-host mesh (subprocess on single-device hosts) --------------------

needs_devices = pytest.mark.skipif(
    not MULTI, reason="needs >= 4 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")

MESH_SCHEMES = ["ecc", "tmr-parallel", "ecc+tmr-serial"]


@needs_devices
@pytest.mark.parametrize("name", MESH_SCHEMES)
def test_join_matches_alone_on_mesh(setup, name):
    """The acceptance bar's second half: same join-vs-alone bit-exactness
    with the scheduler running on a forced-host 2x2 mesh."""
    cfg, key, params, prompts = setup
    scheme = parse_scheme(name)
    mesh = make_test_mesh(2, 2)
    b = ContinuousBatcher(cfg, scheme, SPEC, mesh=mesh)
    b.prepare(params, key=key, fault=TransientBitFlips(P_BIT))
    reqs = [Request(0, prompts[8], 6, arrival_s=0.0),
            Request(1, prompts[4], 2, arrival_s=0.0),
            Request(9, prompts[8], 5, arrival_s=0.1)]
    res = {r.rid: r for r in b.run(reqs)}
    alone = _serve_alone(cfg, params, key, scheme,
                         Request(9, prompts[8], 5), mesh=mesh)
    np.testing.assert_array_equal(res[9].tokens, alone.tokens)
    assert res[9].vote_disagreements == alone.vote_disagreements
    # and the mesh run matches the single-device scheduler bit for bit
    single = _serve_alone(cfg, params, key, scheme,
                          Request(9, prompts[8], 5))
    np.testing.assert_array_equal(alone.tokens, single.tokens)


@pytest.mark.slow
@pytest.mark.skipif(MULTI, reason="already running with >= 4 devices")
def test_mesh_suite_subprocess():
    """Single-device hosts: re-run this file's native mesh tests with 4
    forced host devices (jax pins the device count at first init)."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu", PYTHONPATH=src)
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.abspath(__file__), "-k", "mesh and not subprocess"],
        env=env, capture_output=True, text=True, timeout=1500)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
