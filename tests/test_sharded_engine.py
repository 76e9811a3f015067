"""Sharded generation engine (DESIGN.md §14): bit-exactness against the
single-device engine on forced-host-device meshes.

The native tests need >= 4 devices (6 for copy groups of two) and skip
otherwise; on single-device hosts the slow wrapper test re-invokes this
file in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=6`` (jax locks the
device count at first init, so the flag cannot be set in-process).  The CI
sharded smoke job sets the flag and runs the native tests directly.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.faults import TransientBitFlips
from repro.launch.engine import GenerationEngine, fetch_telemetry
from repro.launch.mesh import fold_copy_axis, make_test_mesh
from repro.models import params as P
from repro.models import transformer as T
from repro.reliability.scheme import parse_scheme, standard_grid

MULTI = jax.device_count() >= 4
needs_devices = pytest.mark.skipif(
    not MULTI, reason="needs >= 4 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")

#: >= 3 shapes per the acceptance bar: pure DP, DP x TP, and the
#: data%3==0 shape where concurrent TMR folds its copy axis
MESHES = [(2, 1), (2, 2), (3, 1)]
P_BIT = 2e-3   # dense enough that ECC/vote counters are nonzero
B, PROMPT, GEN = 2, 4, 3


def _cfg():
    # micro config with every shardable dim divisible by the test meshes
    return get_config("phi3-mini-3.8b").smoke().replace(
        n_layers=1, d_model=16, n_heads=2, n_kv=2, d_ff=32, vocab=512)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    params = P.materialize(key, T.model_specs(cfg))
    batch = {"tokens": jax.random.randint(key, (B, PROMPT), 0, cfg.vocab)}
    return cfg, key, params, batch


@pytest.fixture(scope="module")
def references(setup):
    """Single-device tokens + telemetry per scheme, under the same fault
    keys every sharded run replays."""
    cfg, key, params, batch = setup
    fault = TransientBitFlips(P_BIT)
    refs = {}
    for scheme in standard_grid():
        eng = GenerationEngine(cfg, scheme, gen=GEN)
        store, prep = eng.prepare(params, key=key, fault=fault)
        toks, tel = eng.generate(store, batch)
        refs[scheme.name] = (np.asarray(toks),
                            fetch_telemetry({**prep, **tel}))
    return refs


@needs_devices
@pytest.mark.parametrize("mesh_shape", MESHES,
                         ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("scheme", standard_grid(), ids=lambda s: s.name)
def test_sharded_bit_exact(setup, references, scheme, mesh_shape):
    """Identical tokens AND identical scrub/vote counters vs single-device
    for every standard_grid scheme on every test mesh."""
    cfg, key, params, batch = setup
    eng = GenerationEngine(cfg, scheme, gen=GEN,
                           mesh=make_test_mesh(*mesh_shape))
    store, prep = eng.prepare(params, key=key,
                              fault=TransientBitFlips(P_BIT))
    toks, tel = eng.generate(store, batch)
    got = fetch_telemetry({**prep, **tel})
    ref_toks, ref_tel = references[scheme.name]
    np.testing.assert_array_equal(np.asarray(toks), ref_toks)
    assert set(got) == set(ref_tel)
    for k in ref_tel:
        np.testing.assert_array_equal(got[k], ref_tel[k], err_msg=k)


@needs_devices
def test_fault_counters_nonzero(references):
    """The bit-exactness assertions must compare *live* counters — a fault
    rate that never fires would vacuously pass."""
    assert int(references["ecc"][1]["ecc_corrected"]) > 0
    assert int(references["ecc+tmr-serial"][1]["ecc_corrected"]) > 0


@needs_devices
def test_fold_copy_axis_and_exec_mesh():
    base = make_test_mesh(3, 1)
    folded = fold_copy_axis(base)
    assert folded.axis_names == ("copy", "data", "model")
    assert folded.shape["copy"] == 3 and folded.shape["data"] == 1
    # idempotent on an already-folded mesh
    assert fold_copy_axis(folded) is folded
    cfg = _cfg()
    par = GenerationEngine(cfg, parse_scheme("tmr-parallel"), gen=2,
                           mesh=base)
    assert "copy" in par.exec_mesh.axis_names
    # serial runs one copy at a time — nothing to fold
    ser = GenerationEngine(cfg, parse_scheme("tmr-serial"), gen=2,
                           mesh=base)
    assert "copy" not in ser.exec_mesh.axis_names
    # 2x2: data=2 cannot host 3 copies -> unfolded
    par22 = GenerationEngine(cfg, parse_scheme("tmr-parallel"), gen=2,
                             mesh=make_test_mesh(2, 2))
    assert par22.exec_mesh.axis_names == ("data", "model")


@needs_devices
def test_copies_built_on_their_own_devices(setup):
    """On a copy-folded 3x1 mesh each TMR copy is built on its own device:
    every shard of copy c lives on copy group c, so no device ever holds
    two copies — and tokens and counters equal the single-device run."""
    cfg, key, params, batch = setup
    scheme = parse_scheme("ecc+tmr-parallel")
    fault = TransientBitFlips(P_BIT)
    ref = GenerationEngine(cfg, scheme, gen=GEN)
    store, prep = ref.prepare(params, key=key, fault=fault)
    ref_toks, ref_tel = ref.generate(store, batch)
    ref_stats = fetch_telemetry({**prep, **ref_tel})

    eng = GenerationEngine(cfg, scheme, gen=GEN, mesh=make_test_mesh(3, 1))
    store, prep = eng.prepare(params, key=key, fault=fault)
    homes = eng._copy_devices()
    assert len(set(homes)) == 3
    for leaf in jax.tree.leaves(store):
        for shard in leaf.addressable_shards:
            assert shard.device == homes[shard.index[0].start or 0]
    toks, tel = eng.generate(store, batch)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref_toks))
    got = fetch_telemetry({**prep, **tel})
    assert int(got["ecc_corrected"]) > 0
    for k in ref_stats:
        np.testing.assert_array_equal(got[k], ref_stats[k], err_msg=k)


@pytest.mark.skipif(jax.device_count() < 6, reason="needs >= 6 devices")
@pytest.mark.parametrize("mesh_shape", [(6, 1), (3, 2)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("spec", ["tmr-parallel", "tmr-semi"])
def test_replicas_on_multi_device_copy_groups(setup, references, spec,
                                              mesh_shape):
    """Where each copy group holds two devices, each copy runs as the
    single-copy program sharded over its own group, whole or chunked:
    tokens and vote counters equal the single-device run."""
    cfg, key, params, batch = setup
    eng = GenerationEngine(cfg, parse_scheme(spec), gen=GEN,
                           mesh=make_test_mesh(*mesh_shape))
    groups = eng._copy_meshes()
    assert eng._replicated and [m.size for m in groups] == [2, 2, 2]
    store, _ = eng.prepare(params, key=key, fault=TransientBitFlips(P_BIT))
    for c, part in enumerate(eng._replicas(store)):
        for leaf in jax.tree.leaves(part):
            assert leaf.sharding.mesh.devices.tolist() == \
                groups[c].devices.tolist()
    ref_toks, ref_tel = references[eng.scheme.name]
    toks, tel = eng.generate(store, batch)
    chunked, tel_c, _ = eng.generate_chunked(store, batch, chunk=1)
    for t, got in ((toks, fetch_telemetry(tel)),
                   (chunked, fetch_telemetry(tel_c))):
        np.testing.assert_array_equal(np.asarray(t), ref_toks)
        for k in ref_tel:
            np.testing.assert_array_equal(got[k], ref_tel[k], err_msg=k)


@needs_devices
def test_protected_device_put_roundtrip(setup):
    """Protected stores round-trip through jax.device_put with the
    scheme-aware sharded PartitionSpecs: same bits, same scrub reports."""
    cfg, key, params, _ = setup
    from repro.models.params import partition_specs
    mesh = make_test_mesh(2, 2)
    pspecs = partition_specs(T.model_specs(cfg), mesh)
    fault = TransientBitFlips(P_BIT)
    for scheme in standard_grid():
        dirty = scheme.corrupt_store(scheme.protect(params), fault, key)
        placed = jax.device_put(dirty, scheme.shardings(params, pspecs,
                                                        mesh))
        for a, b in zip(jax.tree.leaves(dirty), jax.tree.leaves(placed)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        out0, rep0 = scheme.scrub(dirty)
        out1, rep1 = scheme.scrub(placed, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(rep0.corrected),
                                      np.asarray(rep1.corrected))
        np.testing.assert_array_equal(np.asarray(rep0.uncorrectable),
                                      np.asarray(rep1.uncorrectable))
        for a, b in zip(jax.tree.leaves(scheme.read(out0)),
                        jax.tree.leaves(scheme.read(out1))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@needs_devices
def test_canonical_parts_mixed_shardings():
    """Eager concatenation of arrays with MIXED shardings is exact — jax
    0.4.37 doubled every value per replicated mesh axis, which once needed
    a canonicalizing guard; `arena.pack` and `scrub_copies` concatenate
    such parts directly."""
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = make_test_mesh(2, 2)
    a = jnp.arange(8, dtype=jnp.uint32)
    b = jnp.arange(100, 108, dtype=jnp.uint32)
    want = np.concatenate([np.arange(8, dtype=np.uint32),
                           np.arange(100, 108, dtype=np.uint32)])
    for sa, sb in [(("data",), (None,)), (("data",), ("model",)),
                   ((None,), ("data",))]:
        aa = jax.device_put(a, NamedSharding(mesh, PartitionSpec(*sa)))
        bb = jax.device_put(b, NamedSharding(mesh, PartitionSpec(*sb)))
        np.testing.assert_array_equal(np.asarray(jnp.concatenate([aa, bb])),
                                      want)


@needs_devices
def test_sharded_scrub_ops_match():
    """scrub_sharded / inject_scrub_sharded == their single-launch ops —
    fixed words, parity and counts — including a block count that does NOT
    divide the shard count (zero-padding path)."""
    from repro.kernels.diag_parity import encode_parity, scrub, scrub_sharded
    from repro.kernels.inject_scrub import (inject_scrub,
                                            inject_scrub_sharded)
    mesh = make_test_mesh(2, 2)
    key = jax.random.PRNGKey(3)
    nb = 37   # not a multiple of the 4-way shard count
    buf = jax.random.bits(key, (nb * 32,), dtype=jnp.uint32)
    parity = encode_parity(buf)
    bits = jax.random.bernoulli(jax.random.fold_in(key, 1), 5e-4,
                                (nb * 32, 32))
    mask = (bits.astype(jnp.uint32)
            << jnp.arange(32, dtype=jnp.uint32)).sum(axis=1,
                                                     dtype=jnp.uint32)
    corrupted = buf ^ mask

    f0, p0, c0 = scrub(corrupted, parity)
    f1, p1, c1 = scrub_sharded(corrupted, parity, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    assert int(c0[0]) > 0   # live counters, not vacuous zeros

    g0, q0, d0 = inject_scrub(buf, parity, mask)
    g1, q1, d1 = inject_scrub_sharded(buf, parity, mask, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    assert int(d0[0]) > 0


@pytest.mark.slow
@pytest.mark.skipif(MULTI, reason="already running with >= 4 devices")
def test_sharded_suite_subprocess():
    """Single-device hosts: run this file's native tests in a subprocess
    with 6 forced host devices (the 4-device meshes take the first four),
    so tier-1 covers the sharded engine everywhere (the CI sharded job
    runs them natively)."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=6",
               JAX_PLATFORMS="cpu", PYTHONPATH=src)
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=1500)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
