"""Several runs of one cell in one process, with what the program's flight
recorder saw in each.

    python3 bench/flight.py --workload <name> --seconds 51 \\
        --seeds 1 2 3 [--recorder on|off|both] [--trace 1]

Each seed builds and warms the cell as `bench/run.py` does, drives its
window and prints one JSON line: the end-to-end metrics, the longest
tick calls on the harness's clock and the recorder's slowest ticks in
the window, split by child span (`tick.launch`, `tick.wait`,
`tick.finish`, `tick.scrub`, `tick.scrub_fetch`).  This tells a tick
that stalls on the device (`tick.wait`) from one that stalls on the host.
With ``--recorder off`` the batcher records into `NULL_TRACER` during the
window; ``both`` runs each seed with the recorder on, then off, to price
it.  Served tokens are not compared with the reference here.

With ``--trace 1`` the window runs under the profiler, and the line adds
the tick's device time by phase, each phase split into the ops whose own
scope gave it and those that inherited it (``<phase>~inherited``), the
ops no scope covers (``unscoped``), the idle inside tick runs and inside
the recorder's ``batcher.tick`` spans, and the admission's phases.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _named(split) -> dict:
    """A split keyed by (phase, inherited) or None, as JSON keys."""
    out = {}
    for k, v in (split or {}).items():
        name = "unscoped" if k is None else \
            k[0] + ("~inherited" if k[1] else "")
        out[name] = v
    return out


def device_phases(trace, window) -> dict:
    """The tick's and the admission's device time by phase in a traced
    window (ms per run)."""
    from bench import scopes

    runs = trace.program_runs("tick")
    tick_ms = sum(runs) / len(runs) * 1e-6 if runs else None
    split = _named(scopes.phase_split(trace, "tick", inherited=True))
    return {"tick_ms": tick_ms, "phase_ms": split,
            "idle_in_tick_runs_ms": None if tick_ms is None or not split
            else tick_ms - sum(split.values()),
            "tick_idle_ms": scopes.tick_idle_ms(trace, window),
            "admit_phase_ms": _named(
                scopes.phase_split(trace, "admit", inherited=True))}


def run_once(loaded, seed: int, seconds: float, recorder: bool,
             traced: bool = False) -> dict:
    import jax
    from bench.context import Context, reader
    from bench.serve import Cell
    from bench import scopes
    from bench import trace as TR

    start = time.perf_counter()
    cell = Cell(loaded["conf"], loaded["mix"], seed)
    setup_s = time.perf_counter() - start
    obs = scopes.program_obs()
    if not recorder:
        from repro.obs import NULL_TRACER
        cell.batcher.tracer = NULL_TRACER
    log_dir = None
    if traced:
        log_dir = Path(tempfile.mkdtemp(prefix="flight-trace-"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    w = cell.run_window(seconds, traced,
                        jax.profiler.stop_trace if traced else None)
    cell.close()
    del cell
    gc.collect()
    ctx = Context(conf=loaded["conf"], mix=loaded["mix"], window=w,
                  peaks={}, setup_s=setup_s)
    line = {"seed": seed, "recorder": recorder, "traced": traced,
            "setup_s": setup_s, "failed": w.failed, "ticks": w.ticks,
            "longest_tick_calls_ms": [x * 1e3 for x in sorted(w.tick_s)[-3:]]}
    for m in loaded["end_to_end"]:
        line[m["name"]] = reader(m["name"])(ctx)
    if obs is not None and recorder:
        line["slowest_ticks"] = obs.RECORDER.slowest(
            "batcher.tick", 3, int(w.t0 * 1e9), int(w.t1 * 1e9))
        for t in line["slowest_ticks"]:
            t["start_s"] -= w.t0            # from the window's opening
        line["recorder_dropped"] = obs.RECORDER.dropped
    if traced:
        line.update(device_phases(TR.load(TR.find(log_dir)), w))
        shutil.rmtree(log_dir, ignore_errors=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--recorder", choices=("on", "off", "both"),
                    default="on")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.run import jax_environment, load_cell, tpu_devices
    loaded = load_cell(args.workload)
    jax_environment()
    try:
        tpu_devices(int(loaded["cell"]["chips"]))
    except RuntimeError as e:
        print(f"[flight] {e}", file=sys.stderr)
        return 3
    print(f"[flight] process start to devices "
          f"{time.perf_counter() - T_START:.3f}s", file=sys.stderr)
    modes = {"on": [True], "off": [False], "both": [True, False]}
    for seed in args.seeds:
        for rec in modes[args.recorder]:
            print(json.dumps(run_once(loaded, seed, args.seconds, rec,
                                      bool(args.trace))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
