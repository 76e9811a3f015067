"""Compile the serving path's Pallas kernels for a described TPU v5e.

Nothing runs here: each kernel is lowered with ``interpret=False`` and
compiled by the TPU compiler for a v5e chip that is described, not
attached (`jax.experimental.topologies`), at the arena sizes the protected
phi3-mini-3.8b serving path launches.  The compiler refuses what the
Pallas interpreter accepts — blocks off the (8, 128) tiling, unsigned
reductions, more VMEM than a kernel may use — so these tests guard the
chip without using it.  The interpret-mode sweeps in test_kernels.py stay
the oracle for the bits.

The engine's TMR programs on a copy-folded 3x1 mesh of the described
2x2 host compile here too: the replica programs each copy runs on its own
chip, and the in-scan vote programs, whose Mosaic vote XLA cannot
partition (it has to run inside `shard_map`).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.core import arena
from repro.kernels.diag_parity import encode_parity, scrub as diag_scrub
from repro.kernels.hsiao_secded import encode_hsiao, scrub as hsiao_scrub
from repro.kernels.inject_scrub import inject_scrub
from repro.kernels.tmr_vote import vote
from repro.launch.batching import BatchSpec
from repro.launch.engine import GenerationEngine
from repro.models.params import Spec
from repro.models.transformer import model_specs
from repro.pshard import spec_for, use_mesh_and_rules
from repro.reliability import parse_scheme

V5E_HBM = 16 << 30


def _arena_blocks():
    """Blocks per launch on the phi3-mini path: one layer of the largest
    weight leaf (the slab a weight scrub walks) and the whole paged KV
    pool of the chip smoke test's server (4 slots, prompt 128, gen 32,
    chunk 8, 16-token pages), both in bfloat16."""
    cfg = get_config("phi3-mini-3.8b")
    layer = arena.words_for((cfg.d_model, 2 * cfg.d_ff), cfg.cdtype)
    spec = BatchSpec(slots=4, page_tokens=16, chunk=8,
                     prompt_buckets=(128,), gen_cap=32)
    page = arena.words_for((cfg.n_layers, spec.page_tokens, cfg.n_kv,
                            cfg.head_dim), cfg.cdtype)
    pool = 2 * (spec.pool_pages + 1) * page
    return {"weight_slab": layer // arena.BLOCK,
            "kv_pool": pool // arena.BLOCK}


SIZES = _arena_blocks()


def _diag_encode(nb):
    return (lambda b: encode_parity(b, interpret=False),
            [((nb * 32,), jnp.uint32)])


def _diag_scrub(nb):
    return (lambda b, p: diag_scrub(b, p, interpret=False),
            [((nb * 32,), jnp.uint32), ((nb, 3), jnp.uint32)])


def _hsiao_encode(nb):
    return (lambda b: encode_hsiao(b, interpret=False),
            [((nb * 32,), jnp.uint32)])


def _hsiao_scrub(nb):
    return (lambda b, p: hsiao_scrub(b, p, interpret=False),
            [((nb * 32,), jnp.uint32), ((nb, 7), jnp.uint32)])


def _inject_scrub(nb):
    return (lambda b, p, m: inject_scrub(b, p, m, interpret=False),
            [((nb * 32,), jnp.uint32), ((nb, 3), jnp.uint32),
             ((nb * 32,), jnp.uint32)])


def _tmr_vote(nb):
    return (lambda a, b, c: vote(a, b, c, interpret=False),
            [((nb * 32,), jnp.uint32)] * 3)


KERNELS = {"diag_parity_encode": _diag_encode,
           "diag_parity_scrub": _diag_scrub,
           "hsiao_secded_encode": _hsiao_encode,
           "hsiao_secded_scrub": _hsiao_scrub,
           "inject_scrub": _inject_scrub,
           "tmr_vote": _tmr_vote}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def devices(topo):
    """The described chips, with the persistent compilation cache off: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(devices):
    return SingleDeviceSharding(devices[0])


@pytest.fixture
def compiled_vote(monkeypatch):
    """The engine's vote as the Mosaic kernel, as on a TPU (the CPU backend
    would pick the interpreter)."""
    monkeypatch.setattr("repro.kernels.tmr_vote.ops.use_interpret",
                        lambda: False)


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, size):
    fn, shapes = KERNELS[kernel](SIZES[size])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{kernel} did not compile to a Mosaic kernel"
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < V5E_HBM, (kernel, size, peak)


def test_token_vote_compiles_for_v5e(one_chip):
    """The per-step vote of generated token ids (B, 1) int32."""
    s = jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda a, b, c: vote(a, b, c, interpret=False)
                       ).lower(s, s, s).compile()
    assert "tpu_custom_call" in compiled.as_text()


B, PROMPT, GEN, CHUNK = 4, 16, 8, 4


def _engine_3x1(devices, **kw):
    """ecc+tmr-parallel on `--mesh 3x1` of the described 2x2 host: the copy
    axis folds one copy onto each of three chips."""
    mesh = Mesh(np.asarray(devices[:3]).reshape(3, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("phi3-mini-3.8b").smoke()
    return cfg, GenerationEngine(cfg, parse_scheme("ecc+tmr-parallel"),
                                 gen=GEN, mesh=mesh, **kw)


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _store(cfg, eng, copies):
    """Abstract stacked store leaves, (3, ...) or one copy's (1, ...)."""
    return jax.tree.map(
        lambda s, n: jax.ShapeDtypeStruct(
            (copies,) + s.shape, s.resolved_dtype(cfg.cdtype), sharding=n),
        model_specs(cfg), eng._param_shardings(stacked=True),
        is_leaf=lambda x: isinstance(x, Spec))


def _mosaic(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_replica_programs_compile_for_v5e_3x1(devices, compiled_vote):
    """Each copy's prefill, scan and decode chunk on its own chip, and the
    final vote of the three sequences on copy 0's chip."""
    cfg, eng = _engine_3x1(devices)
    assert eng._replicated
    fns, chunk = eng._build(PROMPT), eng._build_chunk(CHUNK)
    homes = eng._copy_devices()
    assert len(set(homes)) == 3
    for c, home in enumerate(homes):
        one = SingleDeviceSharding(home)
        part = _abstract(_store(cfg, eng, 1), one)
        batch = {"tokens": jax.ShapeDtypeStruct((B, PROMPT), jnp.int32,
                                                sharding=one)}
        fns["replica_prefill"].lower(c, part, batch).compile()
        fns["replica_scan"].lower(c, part, batch).compile()
        tok, _, cache = jax.eval_shape(
            lambda p, b: fns["replica_prefill"](c, p, b), part, batch)
        chunk["replica_chunk"].lower(c, part, _abstract(tok, one),
                                     _abstract(cache, one)).compile()
    seq = jax.ShapeDtypeStruct((B, GEN), jnp.int32,
                               sharding=SingleDeviceSharding(homes[0]))
    assert _mosaic(jax.jit(eng._tmr()._vote()).lower(seq, seq, seq)
                   .compile())


def test_in_scan_vote_programs_compile_for_v5e_3x1(devices, compiled_vote):
    """With `vote_every` the three copies run as one program partitioned
    over the copy axis, voting inside the scan through `shard_map`."""
    cfg, eng = _engine_3x1(devices, vote_every=2, vote_cache=True)
    assert not eng._replicated
    mesh = eng.exec_mesh
    store = _store(cfg, eng, 3)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (B, PROMPT), jnp.int32, sharding=NamedSharding(
            mesh, spec_for((B, PROMPT), ("batch", None), mesh, eng.rules)))}
    with use_mesh_and_rules(mesh, eng.rules):
        fns = eng._build(PROMPT)
        assert _mosaic(fns["tmr_scan"].lower(store, batch).compile())
        prefill = fns["tmr_prefill"].lower(store, batch).compile()
        tok3, _, cache3 = jax.eval_shape(fns["tmr_prefill"], store, batch)
        outs = prefill.output_shardings
        tok3 = jax.ShapeDtypeStruct(tok3.shape, tok3.dtype,
                                    sharding=outs[0])
        cache3 = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            cache3, outs[2])
        step = jax.ShapeDtypeStruct((), jnp.int32)
        assert _mosaic(eng._build_chunk(CHUNK)["tmr_chunk"]
                       .lower(store, tok3, cache3, step).compile())
        seq3 = jax.ShapeDtypeStruct((3, B, GEN), jnp.int32,
                                    sharding=NamedSharding(
                                        mesh, PartitionSpec("copy")))
        assert _mosaic(jax.jit(lambda s: eng._voter()(s[0], s[1], s[2]))
                       .lower(seq3).compile())
