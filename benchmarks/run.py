"""Benchmark harness — one module per paper table/figure.

Prints ``name,value,derived`` CSV rows (per-call rows carry microseconds,
``*.total_wall_s`` rows carry seconds); ``--json PATH`` additionally
writes the same rows machine-readably (the ``BENCH_*.json`` trajectory
artifact CI uploads) — per-call rows as ``us_per_call``, wall-clock
totals as ``{"kind": "time", "seconds": ...}`` so check_regression.py
compares like units.  Run with:
    PYTHONPATH=src python -m benchmarks.run [--only fig4_mult,...] \
        [--json bench.json] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

try:                      # package execution: python -m benchmarks.<mod>
    from . import _path   # noqa: F401
except ImportError:       # direct script execution
    import _path          # noqa: F401

MODULES = ["fig4_mult", "fig4_nn", "fig5_weights", "ecc_overhead",
           "tmr_tradeoff", "kernels_bench", "campaign_mc", "netlist_bench",
           "serve_bench", "serve_load", "obs_overhead", "mmpu_cost",
           "ecc_frontier"]


def provenance() -> dict:
    """Run provenance stamped onto every JSON row: a bench number without
    its git SHA, backend resolution and device shape is unreproducible.
    `backend` records the *resolved* implementation per op (the REPRO_IMPL
    env var / registered defaults actually in effect), so a row measured
    against jnp fallbacks can never masquerade as a kernel number."""
    import subprocess
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__))).stdout.strip() \
            or None
    except (OSError, subprocess.SubprocessError):
        sha = None
    import jax
    from repro.reliability import backend
    return {
        "git_sha": sha,
        "backend": {op: backend.resolve(op) for op in backend.ops()},
        "platform": jax.default_backend(),
        # forced-host device count IS the bench mesh capacity: sharded
        # serve rows appear exactly when this is >= 4 (DESIGN.md §14)
        "devices": jax.device_count(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(MODULES))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON to PATH")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink trial budgets (sets REPRO_BENCH_SMOKE=1 "
                         "for modules that scale with it)")
    args = ap.parse_args()
    mods = args.only.split(",") if args.only else MODULES
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    stamp = provenance()
    print("name,value,derived")
    rows = []
    failures = 0
    for name in mods:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=[name])
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us:.3f},{derived}", flush=True)
                rows.append({"module": name, "name": row_name,
                             "us_per_call": round(us, 3),
                             "derived": str(derived), **stamp})
        except Exception:
            failures += 1
            err = traceback.format_exc(limit=2)
            print(f"{name}.ERROR,0,{err!r}", flush=True)
            rows.append({"module": name, "name": f"{name}.ERROR",
                         "us_per_call": 0.0, "derived": err, **stamp})
        # wall-clock totals are a different unit from the per-call rows:
        # record them as kind=time seconds, never as a microsecond
        # us_per_call (the old mislabeling check_regression had to absorb)
        wall_s = time.time() - t0
        print(f"{name}.total_wall_s,{wall_s:.3f},unit=s", flush=True)
        rows.append({"module": name, "name": f"{name}.total_wall_s",
                     "kind": "time", "seconds": round(wall_s, 3),
                     "derived": "unit=s", **stamp})
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"modules": mods, "smoke": bool(args.smoke),
                       "failures": failures, "unix_time": int(time.time()),
                       "provenance": stamp, "rows": rows}, f, indent=1)
        print(f"# wrote {len(rows)} rows to {args.json}", file=sys.stderr)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
