"""Logical-axis sharding resolution + an 8-device mini dry-run in a
subprocess (the main test process must keep 1 device)."""
import json
import os
import subprocess
import sys

import pytest

from repro.models.params import Spec
from repro.pshard import DEFAULT_RULES, ShardingRules, spec_for


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape

    @property
    def axis_names(self):
        return tuple(self.shape)


def test_divisibility_degrades_to_replication():
    mesh = FakeMesh({"data": 16, "model": 16})
    # kv heads = 8 do not divide model=16 -> replicated
    spec = spec_for((8, 128), ("kv_heads", None), mesh, DEFAULT_RULES)
    assert spec == type(spec)(None, None)


def test_axis_never_used_twice():
    mesh = FakeMesh({"data": 4, "model": 4})
    spec = spec_for((64, 64, 64), ("batch", "kv_seq", "kv_heads"), mesh,
                    DEFAULT_RULES)
    # batch -> data, kv_seq -> model, kv_heads would reuse model -> None
    assert spec[2] is None


def test_fsdp_two_dim_sharding():
    mesh = FakeMesh({"data": 8, "model": 8})
    spec = spec_for((512, 1024), ("model_dim", "ff"), mesh, DEFAULT_RULES)
    assert spec[0] == "data" and spec[1] == "model"


def test_rules_replace():
    r = DEFAULT_RULES.replace(kv_seq=())
    assert r.axes_for("kv_seq") == ()
    assert DEFAULT_RULES.axes_for("kv_seq") == ("model",)


def test_copy_axis_rule_degrades_without_copy_mesh():
    # the "copy" logical axis resolves only on fold_copy_axis meshes;
    # plain data x model meshes replicate the stacked copies
    plain = FakeMesh({"data": 4, "model": 4})
    spec = spec_for((3, 64), ("copy", None), plain, DEFAULT_RULES)
    assert spec[0] is None
    folded = FakeMesh({"copy": 3, "data": 2, "model": 4})
    spec = spec_for((3, 64), ("copy", None), folded, DEFAULT_RULES)
    assert spec[0] == "copy"


def test_arena_block_rule_whole_mesh():
    mesh = FakeMesh({"data": 4, "model": 4})
    spec = spec_for((160, 3), ("arena_block", None), mesh, DEFAULT_RULES)
    assert spec[0] == ("data", "model")
    # indivisible block counts degrade to replication, never error
    spec = spec_for((7, 3), ("arena_block", None), mesh, DEFAULT_RULES)
    assert spec[0] is None


def test_mesh_guard_names_xla_flags():
    from repro.launch.mesh import make_test_mesh
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        make_test_mesh(64, 64)   # no host exposes 4096 devices


def test_fold_copy_axis_indivisible():
    from repro.launch.mesh import fold_copy_axis, make_test_mesh
    mesh = make_test_mesh(1, 1)
    assert fold_copy_axis(mesh) is None   # data=1 cannot host 3 copies


@pytest.mark.slow
def test_mini_dryrun_8_devices(tmp_path):
    """Lower+compile a smoke config against a forced 8-device mesh in a
    subprocess; proves the sharding rules produce a coherent program."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import sys
sys.path.insert(0, %r)
from repro.configs import get_config
from repro.models import params as P, transformer as T
from repro.models.steps import make_train_step, init_train_state
from repro.optim import AdamWConfig
from repro.pshard import DEFAULT_RULES, use_mesh_and_rules
from repro.models.params import abstractify

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_config("qwen2.5-14b").smoke().replace(d_model=64, d_ff=128, vocab=256)
with use_mesh_and_rules(mesh, DEFAULT_RULES):
    specs = T.model_specs(cfg)
    params = abstractify(specs, mesh)
    state = {"params": params,
             "opt": {"m": abstractify(specs, mesh),
                     "v": abstractify(specs, mesh),
                     "count": jax.ShapeDtypeStruct((), jnp.int32)}}
    batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
    step = make_train_step(cfg, AdamWConfig())
    compiled = jax.jit(step).lower(state, batch).compile()
    print("COMPILED", compiled.memory_analysis().temp_size_in_bytes >= 0)
"""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", script % os.path.abspath(src)],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=600)
    assert "COMPILED True" in out.stdout, out.stderr[-2000:]
