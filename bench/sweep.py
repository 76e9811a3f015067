"""The knee of an open-loop cell: one set-up, then a window per rate.

    python3 bench/sweep.py --workload <name> --seconds <s> --rates 0.5 1 2

For each offered rate, in the order given: requests due and completed,
the time-to-first-token median and 90th percentile from the due time,
and the queue's growth (requests waiting, mean of the window's last third
minus its first third).  The knee is the highest rate whose queue does
not grow.  One JSON line per rate.  Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    from bench import run as R
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    loaded = R.load_cell(args.workload)
    R.jax_environment()
    R.tpu_devices(int(loaded["cell"]["chips"]))
    from bench.serve import Cell
    cell = Cell(loaded["conf"], loaded["mix"], args.seed)
    for rate in args.rates:
        w = cell.open_loop(args.seconds, rate_rps=rate)
        ttft = np.asarray([s.first - s.due for s in w.requests]) * 1e3
        q = np.asarray(w.queue_after_turn, float)
        third = max(1, len(q) // 3)
        print(json.dumps({
            "rate_rps": rate, "due": len(w.requests),
            "ticks": w.ticks, "window_s": w.seconds,
            "ttft_p50_ms": float(np.percentile(ttft, 50)),
            "ttft_p90_ms": float(np.percentile(ttft, 90)),
            "queue_growth": float(q[-third:].mean() - q[:third].mean()),
            "queue_max": int(q.max()) if q.size else 0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
