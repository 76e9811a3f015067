"""The chip benchmark: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Reads the cell from `BENCHMARK.json`, its configuration from
``bench/configs/`` and its traffic mix from ``bench/traffic/``; builds
and warms the serving program (the set-up), drives it for `--seconds`
(`bench.serve`), drains it, reads the metrics through their readers in
``bench/metrics/``, then frees the program and compares a sample of what
it served with the float32 reference (`bench.check`).  The last line of
standard output is one JSON object; the last lines of standard error give
each number compared beside its limit.

It runs on the machine it is started on and needs a TPU with as many
chips as the cell asks for: without one it exits non-zero and prints no
result.  JAX's compilation cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, or else in ``.jax_cache`` at the root
of the checkout.  With ``--trace 1`` the window runs under the profiler
and the result carries the per-layer metrics instead of the end-to-end
ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def load_cell(name: str) -> dict:
    """The cell's entry, configuration, mix and the metrics it reports."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return {"cell": cell,
            "conf": json.loads((ROOT / entry["file"]).read_text()),
            "mix": json.loads((ROOT / "bench" / "traffic"
                               / f"{cell['traffic']}.json").read_text()),
            "end_to_end": e2e, "per_layer": layer}


def jax_environment() -> None:
    """Compilation cache at a fixed path, and no runtime logs under /tmp;
    both before the backend starts."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def tpu_devices(chips: int):
    """The chips the cell asks for; raises where there are none."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {devices[0].platform!r} "
                           f"devices")
    if len(devices) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX found "
                           f"{len(devices)}")
    return devices[:chips]


class CompileCount:
    """Programs compiled or loaded from the cache while armed."""

    def __init__(self):
        import jax
        self.n, self.armed = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.armed and ("compile" in name or "cache_retrieval" in name):
            self.n += 1


def run(loaded: dict, seed: int, seconds: float, traced: bool,
        devices, peaks: dict, *, setup_start: float,
        control: bool = False) -> dict:
    """One run of a loaded cell on `devices`, whose published peaks are
    `peaks`; returns the result line.  With `control` the compared tokens
    are the control's (`bench/control.py`), so that `correct` is the
    control's verdict; the benchmark's own runs never set it."""
    import jax
    from . import check
    from .context import Context, reader
    from .serve import Cell
    from . import trace as TR

    conf, mix = loaded["conf"], loaded["mix"]
    cell = Cell(conf, mix, seed)
    setup_s = time.perf_counter() - setup_start
    cell_phases = {"start": cell.phases.pop("start") - setup_start,
                   **cell.phases}

    log_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if traced \
        else None
    compiles = CompileCount()
    gc_pauses: list = []

    def on_gc(phase, info):
        if phase == "start":
            on_gc.t = time.perf_counter()
        elif compiles.armed:
            gc_pauses.append(time.perf_counter() - on_gc.t)

    gc.callbacks.append(on_gc)
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)

    def close():
        compiles.armed = False
        if traced:
            jax.profiler.stop_trace()

    compiles.armed = True
    window = cell.run_window(seconds, traced, close)
    gc.callbacks.remove(on_gc)
    stats = cell.stats()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    cell.close()
    del cell
    gc.collect()

    ctx = Context(conf=conf, mix=mix, window=window, peaks=peaks,
                  setup_s=setup_s)
    result_device = {"platform": devices[0].platform,
                     "kind": devices[0].device_kind, "count": len(devices),
                     "memory_peak_bytes": int(peak)}
    breakdown = None
    if traced:
        ctx.trace = TR.load(TR.find(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        busy = ctx.mean_busy_share()
        result_device["busy_s"] = busy * ctx.trace.window_ns * 1e-9
        result_device["window_s"] = ctx.trace.window_ns * 1e-9
        breakdown = {"device_ops": ctx.trace.top_ops(10),
                     "idle_gaps": ctx.trace.idle_gaps(10)}
    metrics = {}
    for m in loaded["per_layer" if traced else "end_to_end"]:
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    picked = check.sample(window.requests, conf["check"]["sample"], seed)
    cmp = check.compare(conf, mix, seed, picked, control=control)
    checks = {"worst_gap": {"value": cmp["control_gap" if control
                                         else "gap"],
                            "limit": conf["check"]["gap_limit"]}}
    if "ecc_uncorrectable" in stats:
        checks["uncorrectable"] = {
            "value": stats["ecc_uncorrectable"]
            + stats.get("ecc_read_uncorrectable", 0), "limit": 0}
    correct = bool(picked) and all(c["value"] <= c["limit"]
                                   for c in checks.values())

    log = sys.stderr
    print(f"[bench] window {window.seconds:.3f}s: "
          f"{len(window.requests)} requests, "
          f"{window.tokens_in_window()} tokens, {window.ticks} ticks, "
          f"{window.admissions} admissions; compiled or loaded in the "
          f"window: {compiles.n}", file=log)
    print(f"[bench] ecc telemetry {stats}", file=log)
    print("[bench] longest turns, ticks and collector pauses in the "
          "window (ms): " + "; ".join(
              " ".join(f"{x * 1e3:.1f}" for x in sorted(v)[-3:])
              for v in (window.turn_s, window.tick_s, gc_pauses)),
          file=log)
    print(f"[bench] set-up {setup_s:.3f}s: " + ", ".join(
        f"{k} {v:.3f}s" for k, v in cell_phases.items()), file=log)
    if loaded["mix"]["loop"] == "open":
        print("[bench] ttft_ms in due order: " + " ".join(
            f"{(s.first - s.due) * 1e3:.1f}" for s in ctx.due_in_window()),
            file=log)
    print(f"[bench] compared {len(picked)} requests, {cmp['tokens']} "
          f"served tokens", file=log)
    result = {"correct": correct, "attempted": len(window.requests),
              "failed": window.failed, "metrics": metrics,
              "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        result["program_gap"] = cmp["gap"]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"[bench] the program is not in this checkout "
              f"({ROOT / 'src' / 'repro'} is missing)", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    loaded = load_cell(args.workload)
    jax_environment()
    try:
        devices = tpu_devices(int(loaded["cell"]["chips"]))
    except RuntimeError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    from bench.flops import peaks
    from bench.run import run as run_cell
    result = run_cell(loaded, args.seed, args.seconds, bool(args.trace),
                      devices, peaks(devices[0].device_kind),
                      setup_start=T_START)
    for name, c in result["checks"].items():
        print(f"[bench] check {name}: {c['value']!r} (limit "
              f"{c['limit']!r})", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
