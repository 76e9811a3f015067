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

from repro.configs import get_config
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
