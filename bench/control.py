"""Readings for a cell's limits: the program's and the control's gaps.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1 2 3

For each seed, one whole run of the cell (set-up, window, drain) and then
the comparison twice over the same sampled requests: the widest gap of
the served tokens (the program's reading) and the widest gap of the
tokens that the float8 reference ranks first (the control's reading,
which decides the run's `correct`: it has to come out false).  One JSON
line per seed.  Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    from bench import run as R
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    loaded = R.load_cell(args.workload)
    R.jax_environment()
    devices = R.tpu_devices(int(loaded["cell"]["chips"]))
    from bench.flops import peaks
    for seed in args.seeds:
        res = R.run(loaded, seed, args.seconds, False, devices,
                    peaks(devices[0].device_kind),
                    setup_start=time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "gap": res["program_gap"],
                          "control_gap": res["checks"]["worst_gap"]
                          ["value"], "correct": res["correct"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
