"""Record a small device trace of the serving path together with what
the program says of it, for the phase tests.

    python bench/record_scopes.py --out bench/data/scopes_sample

Runs the three turns of `bench/record_trace.py` (a `ContinuousBatcher`
at `.smoke()` widths under `hsiao-wb`, warmed, then admit and tick under
the harness's annotations) and writes two files: ``<out>.xplane.pb.gz``,
the trace, and ``<out>.json``, the phase map of each program the batcher
compiled (`repro.obs.phase_maps`), the flight recorder's spans inside
the window and the window's opening and close, both on the
``perf_counter`` clock.  Prints the tick's device time by phase.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from bench import scopes
    from bench import trace as TR
    from bench.serve import Window
    from repro import obs
    from repro.configs import get_config
    from repro.launch.batching import BatchSpec, ContinuousBatcher, Request
    from repro.models import params as P
    from repro.models import transformer as T
    from repro.reliability import parse_scheme

    cfg = get_config("phi3-mini-3.8b").smoke()
    spec = BatchSpec(slots=2, page_tokens=16, chunk=4, prompt_buckets=(16,),
                     gen_cap=12)
    b = ContinuousBatcher(cfg, parse_scheme("hsiao-wb"), spec)
    key = jax.random.PRNGKey(0)
    b.prepare(P.materialize(key, T.model_specs(cfg), dtype=cfg.cdtype),
              key=key)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab, 16, dtype=np.int32), 9)
            for i in range(4)]
    b.run(reqs[:2])                                   # compiles
    for r in reqs[2:]:
        b.submit(r)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    log_dir = tempfile.mkdtemp(prefix="scopes-")
    with jax.profiler.trace(log_dir, profiler_options=opts):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.admit"):
                    b.admit()
                with jax.profiler.TraceAnnotation("bench.tick"):
                    b.tick()
        t1 = time.perf_counter()
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    trace_path = out.with_name(out.name + ".xplane.pb.gz")
    trace_path.write_bytes(gzip.compress(TR.find(Path(log_dir)).read_bytes()))
    shutil.rmtree(log_dir, ignore_errors=True)
    spans = [e for e in obs.RECORDER.spans()
             if t0 * 1e9 <= e["ts"] and e["ts"] + e["dur"] <= t1 * 1e9]
    doc = {"window_perf_s": [t0, t1], "phases": obs.phase_maps(),
           "spans": spans}
    json_path = out.with_name(out.name + ".json")
    json_path.write_text(json.dumps(doc, separators=(",", ":")))
    print(f"trace: {trace_path} {trace_path.stat().st_size} bytes; "
          f"{json_path} {json_path.stat().st_size} bytes")
    print("maps: " + ", ".join(f"{p} {[len(m) for m in ms]}"
                               for p, ms in doc["phases"].items()))
    tr = TR.load(trace_path)
    print(f"tick ms by phase: {scopes.phase_split(tr, 'tick')}; "
          f"runs {tr.program_runs('tick')}")
    print(f"idle ms per tick inside batcher.tick: "
          f"{scopes.tick_idle_ms(tr, Window(t0=t0, t1=t1))}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
