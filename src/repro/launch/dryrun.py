import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "DRYRUN_XLA_FLAGS",
    "--xla_force_host_platform_device_count=512 "
    # LICM hoists a loop-invariant convert(residual-stack) out of the
    # backward while-loop: one fp32 copy of ALL saved layer inputs
    # (+11.9 GiB/device on deepseek-67b train_4k, the single largest buffer).
    # Disabling the pass converts per-slice instead: same bandwidth, 1/95th
    # the memory.  Measured in EXPERIMENTS.md §Perf.
    "--xla_disable_hlo_passes=while-loop-invariant-code-motion")
# ^ MUST run before any other import: jax locks the device count on first init.

import argparse
import functools
import json
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs import get_config, list_archs
from ..models.config import ModelConfig
from ..models.steps import make_decode_step, make_prefill_step, make_train_step
from ..optim import AdamWConfig
from ..pshard import use_mesh_and_rules
from .hlo_stats import parse_collectives
from .mesh import make_production_mesh
from .specs import SHAPES, abstract_inputs, arch_rules, skip_reason

__doc__ = """Multi-pod dry-run: lower + compile every (arch x shape x mesh)
cell with abstract inputs, prove it fits (memory_analysis) and extract the
roofline terms (cost_analysis + collective parsing).  No arrays are ever
allocated for the full configs."""


def lower_cell(arch: str, shape_name: str, mesh, rules_extra: Optional[dict] = None,
               donate: bool = True):
    """Returns (lowered, inputs-dict)."""
    serve = SHAPES[shape_name].kind != "train"
    rules = arch_rules(arch, rules_extra, serve=serve)
    with use_mesh_and_rules(mesh, rules):
        inp = abstract_inputs(arch, shape_name, mesh, rules)
        cfg: ModelConfig = inp["cfg"]
        kind = inp["shape"].kind
        if kind == "train":
            from ..models.params import partition_specs
            from ..models.transformer import model_specs
            # clamp microbatches so each slice still divides the DP axes
            dp = 1
            for ax in ("pod", "data"):
                if ax in mesh.axis_names:
                    dp *= mesh.shape[ax]
            K = min(inp["policy"]["microbatches"],
                    max(1, inp["shape"].batch // dp))
            pspecs = partition_specs(model_specs(cfg), mesh, rules)
            step = make_train_step(cfg, AdamWConfig(), microbatches=K,
                                   param_pspecs=pspecs,
                                   grad_dtype=jnp.dtype(inp["policy"]["grad_dtype"]))
            fn = jax.jit(step, donate_argnums=(0,) if donate else ())
            lowered = fn.lower(inp["state"], inp["batch"])
        elif kind == "prefill":
            step = make_prefill_step(cfg)
            lowered = jax.jit(step).lower(inp["params"], inp["batch"])
        else:
            step = make_decode_step(cfg)
            fn = jax.jit(step, donate_argnums=(2,) if donate else ())
            lowered = fn.lower(inp["params"], inp["token"], inp["cache"])
    return lowered, inp


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules_extra: Optional[dict] = None,
             save_hlo: Optional[str] = None) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "skipped": reason}

    t0 = time.time()
    lowered, _ = lower_cell(arch, shape_name, mesh, rules_extra)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)

    result = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "devices": int(n_dev),
        "kind": shape.kind,
        "seq": shape.seq,
        "batch": shape.batch,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        # memory_analysis is per-device
        "arg_bytes": int(ma.argument_size_in_bytes),
        "out_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes": int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                          + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
        # cost_analysis is per-device
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "collectives": {
            "per_op_bytes": colls.per_op_bytes,
            "per_op_count": colls.per_op_count,
            "per_op_group": colls.per_op_group,
            "link_traffic_bytes": colls.link_traffic_bytes(),
        },
    }
    return result


def run_engine_cell(arch: str, scheme_spec: str = "tmr-parallel",
                    batch: int = 20, prompt_len: int = 64,
                    gen: int = 8) -> Dict[str, Any]:
    """Lower + compile the sharded generation engine's hot program on the
    dedicated TMR serving mesh (copy=3 x data=5 x model=16 — 240 chips of a
    256-chip pod, DESIGN.md §14) with abstract sharded inputs: proves the
    copy-folded store and KV caches produce a coherent program and reports
    its per-device memory/collective footprint without allocating a single
    parameter.  Concurrent TMR there runs each copy as a replica, so the
    hot program is copy 0's single-copy scan on its 5x16 group."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..models.params import abstractify, partition_specs
    from ..models.transformer import model_specs
    from ..optim.sharding_rules import copy_stack_pspec
    from ..pshard import spec_for
    from ..reliability import parse_scheme
    from .engine import GenerationEngine
    from .mesh import make_tmr_serving_mesh

    mesh = make_tmr_serving_mesh()
    cfg = get_config(arch)
    engine = GenerationEngine(cfg, parse_scheme(scheme_spec), gen=gen,
                              mesh=mesh)
    emesh, rules = engine.exec_mesh, engine.rules
    with use_mesh_and_rules(emesh, rules):
        specs = model_specs(cfg)
        one = abstractify(specs, emesh, rules=rules)
        pspecs = partition_specs(specs, emesh, rules)
        store = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                (3,) + a.shape, a.dtype,
                sharding=NamedSharding(emesh, copy_stack_pspec(
                    s, emesh, rules=rules))),
            one, pspecs)
        tokens = jax.ShapeDtypeStruct(
            (batch, prompt_len), jnp.int32,
            sharding=NamedSharding(emesh, spec_for(
                (batch, prompt_len), ("batch", None), emesh, rules)))
        fns = engine._build(prompt_len)
        if engine._replicated:
            group = engine._copy_meshes()[0]
            store = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    (1,) + a.shape[1:], a.dtype, sharding=NamedSharding(
                        group, PartitionSpec(None, *a.sharding.spec[1:]))),
                store)
            tokens = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype,
                                          sharding=NamedSharding(
                group, spec_for(tokens.shape, ("batch", None), group,
                                rules)))
            fn = functools.partial(fns["replica_scan"].lower, 0)
        else:
            fn = (fns["tmr_scan"] if engine.copy_axis
                  else fns["single_scan"]).lower
        t0 = time.time()
        lowered = fn(store, {"tokens": tokens})
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    colls = parse_collectives(compiled.as_text())
    return {
        "arch": arch, "cell": "engine", "scheme": scheme_spec,
        "mesh": dict(emesh.shape), "devices": int(emesh.devices.size),
        "batch": batch, "prompt_len": prompt_len, "gen": gen,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "peak_bytes": int(ma.argument_size_in_bytes
                          + ma.output_size_in_bytes + ma.temp_size_in_bytes
                          - ma.alias_size_in_bytes),
        "collectives": {
            "per_op_bytes": colls.per_op_bytes,
            "per_op_count": colls.per_op_count,
            "link_traffic_bytes": colls.link_traffic_bytes(),
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true",
                    help="run single-pod AND multi-pod")
    ap.add_argument("--out", default=None, help="append JSONL results here")
    ap.add_argument("--rules", default=None,
                    help='JSON sharding-rule overrides, e.g. \'{"kv_seq": []}\'')
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--engine-cell", action="store_true",
                    help="lower the sharded generation engine (one copy's "
                         "program) on "
                         "the copy x data x model TMR serving mesh instead "
                         "of the train/prefill/decode cells")
    ap.add_argument("--scheme", default="tmr-parallel",
                    help="protection scheme for --engine-cell")
    args = ap.parse_args()

    if args.engine_cell:
        arch = "phi3-mini-3.8b" if args.arch == "all" else args.arch
        tag = f"{arch} x engine[{args.scheme}] x 3x5x16"
        try:
            res = run_engine_cell(arch, args.scheme)
        except Exception as e:
            print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
            sys.exit(1)
        gb = res["peak_bytes"] / 2**30
        print(f"[ OK ] {tag}: peak {gb:.2f} GiB/dev, "
              f"collectives {res['collectives']['per_op_count']}, "
              f"compile {res['compile_s']}s", flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(res) + "\n")
        sys.exit(0)

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    rules_extra = json.loads(args.rules) if args.rules else None

    ok = True
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    res = run_cell(arch, shape, mp, rules_extra, args.save_hlo)
                except Exception as e:  # a failing cell is a bug in the system
                    ok = False
                    res = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "error": f"{type(e).__name__}: {e}"}
                    print(f"[FAIL] {tag}: {res['error']}", flush=True)
                else:
                    if "skipped" in res:
                        print(f"[SKIP] {tag}: {res['skipped']}", flush=True)
                    else:
                        gb = res["peak_bytes"] / 2**30
                        print(f"[ OK ] {tag}: peak {gb:.2f} GiB/dev, "
                              f"{res['flops']/1e12:.2f} TF/dev, "
                              f"compile {res['compile_s']}s", flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(res) + "\n")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
