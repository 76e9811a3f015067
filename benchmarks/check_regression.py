"""CI bench regression guard: compare a fresh smoke `bench.json` against
the committed `benchmarks/baseline.json`.

Rows from the guarded modules (netlist_bench, campaign_mc, serve_bench,
serve_load, mmpu_cost, ecc_frontier) are compared by name on their
throughput signals:

* ratio signals from `derived` (``speedup_vs_scan=`` for the netlist
  engines, ``speedup_vs_loop=`` / ``tmr_amortization=`` for the serving
  engine, ``goodput_gain=`` for the continuous-batching scheduler) are
  machine-INDEPENDENT and compared directly — they catch
  engine-relative regressions regardless of how fast the CI runner is;
* model signals (``cycles_per_token=`` / ``energy_pj_per_token=`` from
  the mMPU cost projections) are machine-independent too but LOWER is
  better: they guard the hardware-grounded cost axis directly;
* absolute signals (``gate_evals_per_s=`` / ``tok_s=`` rates,
  ``ttft_p50/p99=`` / ``tpot_p50/p99=`` latency tails,
  ``us_per_call`` timings >= 10µs, ``*.total_wall_s`` seconds) are first
  normalized by the *median* worse-than-baseline factor across all
  absolute rows — the machine-speed factor between the baseline box and
  the CI runner — so a uniformly slower runner passes while a single row
  that regressed on top of the machine factor fails.

A row regresses when it is worse than (normalized) baseline by more than
``--tolerance`` (default 2.0 — the guard fails on >2x throughput
regressions).  Rows missing on either side are reported but never fail
the guard (benches evolve).  The blind spot by construction: a change
that slows *every* absolute row uniformly looks like a slow machine —
that case is covered by the ratio rows and by re-baselining locally.

    python -m benchmarks.check_regression bench.json            # guard
    python -m benchmarks.check_regression bench.json --update   # re-baseline
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, Tuple

GUARDED_MODULES = ("netlist_bench", "campaign_mc", "serve_bench",
                   "serve_load", "mmpu_cost",
                   "ecc_frontier")
DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__), "baseline.json")
_RATE_RE = re.compile(r"(gate_evals_per_s|tok_s)=([0-9.eE+-]+)")
_RATIO_RE = re.compile(
    r"(speedup_vs_scan|speedup_vs_loop|tmr_amortization"
    r"|goodput_gain|adaptive_speedup)=([0-9.eE+-]+)x")
# mMPU cost-model projections (benchmarks.mmpu_cost): machine-INDEPENDENT
# analytic numbers — pure shape arithmetic, identical on any runner — so
# they are compared directly (no machine normalization) and lower is
# better: a cost-model change that inflates a scheme's projected
# cycles/energy per token beyond tolerance fails the guard.
_MODEL_RE = re.compile(
    r"(cycles_per_token|energy_pj_per_token)=([0-9.eE+-]+)")
# latency-tail metrics from serve_bench's chunked rows: lower-better
# times, machine-normalized like any other absolute timing.  Guarding
# p99 alongside p50 catches tail-only regressions (a fatter distribution
# with an unchanged median).
_LAT_RE = re.compile(
    r"(ttft_p50|ttft_p99|tpot_p50|tpot_p99)=([0-9.eE+-]+)us")
MIN_US = 10.0   # ignore sub-10µs timings: pure dispatch noise


def extract_metrics(rows) -> Dict[str, Tuple[str, float]]:
    """row list -> {metric key: (kind, value)}; kind is 'ratio' (machine-
    independent, higher better), 'model' (machine-independent, lower
    better — the mMPU cost projections), 'rate' (higher better) or 'time'
    (lower better).  Wall-clock totals arrive as ``{"kind": "time", "seconds"}``
    rows (benchmarks.run) and are kept in seconds."""
    out: Dict[str, Tuple[str, float]] = {}  # kinds: ratio|model|rate|time
    for r in rows:
        if r.get("module") not in GUARDED_MODULES:
            continue
        name, us = r["name"], float(r.get("us_per_call", 0.0))
        derived = r.get("derived", "")
        for label, val in _RATIO_RE.findall(derived):
            out[f"{name}:{label}"] = ("ratio", float(val))
        for label, val in _MODEL_RE.findall(derived):
            out[f"{name}:{label}"] = ("model", float(val))
        for label, val in _LAT_RE.findall(derived):
            if float(val) >= MIN_US:
                out[f"{name}:{label}"] = ("time", float(val))
        rate = _RATE_RE.search(derived)
        if rate:
            out[f"{name}:{rate.group(1)}"] = ("rate", float(rate.group(2)))
        elif "seconds" in r:
            out[f"{name}:seconds"] = ("time", float(r["seconds"]))
        elif us >= MIN_US:
            out[f"{name}:us_per_call"] = ("time", us)
    return out


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    if not n:
        return 1.0
    return xs[n // 2] if n % 2 else (xs[n // 2 - 1] + xs[n // 2]) / 2.0


def compare(baseline: Dict[str, Tuple[str, float]],
            fresh: Dict[str, Tuple[str, float]],
            tolerance: float) -> Tuple[list, list]:
    regressions, notes = [], []
    # worse_x > 1 means the fresh run is worse than baseline on that row
    worse: Dict[str, Tuple[str, float]] = {}
    for key in sorted(baseline):
        if key not in fresh:
            notes.append(f"missing in fresh run: {key}")
            continue
        kind, base = baseline[key]
        _, new = fresh[key]
        if base <= 0 or new <= 0:
            continue
        worse[key] = (kind, base / new if kind in ("rate", "ratio")
                      else new / base)
    # machine-speed factor: median worse_x over the absolute rows only.
    # Clamped at 1.0 — a FASTER machine must not inflate rows that merely
    # failed to speed up as much as the median (heterogeneous per-row
    # speedups between boxes would otherwise fail spuriously); only a
    # slower machine gets its uniform factor divided out.
    machine = max(1.0, _median([w for kind, w in worse.values()
                                if kind not in ("ratio", "model")]))
    notes.append(f"machine-speed factor (median absolute worse_x, "
                 f"clamped >= 1): {machine:.2f}")
    for key, (kind, w) in sorted(worse.items()):
        eff = w if kind in ("ratio", "model") else w / machine
        line = (f"{key}: baseline={baseline[key][1]:.4g} "
                f"fresh={fresh[key][1]:.4g} worse_x={w:.2f}"
                + ("" if kind in ("ratio", "model")
                   else f" normalized={eff:.2f}"))
        (regressions if eff > tolerance else notes).append(line)
    for key in sorted(set(fresh) - set(baseline)):
        notes.append(f"new row (not in baseline): {key}")
    return regressions, notes


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("bench_json", help="fresh bench.json from benchmarks.run")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--tolerance", type=float, default=2.0,
                    help="fail when a row is worse than (machine-"
                         "normalized) baseline by more than this factor")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the baseline from the fresh run and exit")
    args = ap.parse_args()

    with open(args.bench_json) as f:
        fresh_doc = json.load(f)
    fresh = extract_metrics(fresh_doc.get("rows", []))

    if args.update:
        with open(args.baseline, "w") as f:
            json.dump({"modules": list(GUARDED_MODULES),
                       "smoke": fresh_doc.get("smoke"),
                       "source_unix_time": fresh_doc.get("unix_time"),
                       "metrics": {k: {"kind": kind, "value": v}
                                   for k, (kind, v) in sorted(fresh.items())}},
                      f, indent=1)
        print(f"# baseline updated: {args.baseline} ({len(fresh)} metrics)")
        return

    with open(args.baseline) as f:
        base_doc = json.load(f)
    if bool(base_doc.get("smoke")) != bool(fresh_doc.get("smoke")):
        sys.exit(f"smoke-mode mismatch: baseline smoke={base_doc.get('smoke')}"
                 f" vs fresh smoke={fresh_doc.get('smoke')} — the configs "
                 "differ (multiplier width, trial budgets), so the rows are "
                 "not comparable; re-run benchmarks.run with matching --smoke"
                 " or --update the baseline")
    baseline = {k: (m["kind"], float(m["value"]))
                for k, m in base_doc["metrics"].items()}

    regressions, notes = compare(baseline, fresh, args.tolerance)
    for line in notes:
        print(f"[bench-guard] ok: {line}")
    for line in regressions:
        print(f"[bench-guard] REGRESSION: {line}", file=sys.stderr)
    if regressions:
        sys.exit(f"{len(regressions)} bench row(s) regressed by more than "
                 f"{args.tolerance}x vs {args.baseline}")
    print(f"[bench-guard] {len(notes)} row(s) within {args.tolerance}x "
          f"of baseline")


if __name__ == "__main__":
    main()
