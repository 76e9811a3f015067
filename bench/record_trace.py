"""Record a small device trace of the serving path, for the trace tests.

    python bench/record_trace.py --out trace_sample

Builds a `ContinuousBatcher` at `.smoke()` widths under `hsiao-wb`, warms
its programs, then traces three turns of admit and tick under the host
annotations the harness uses.  Prints each plane's lines and the most
frequent event names, so that the reduction in `bench/trace.py` can be
checked against what the profiler writes on this device.
"""
from __future__ import annotations

import argparse
import collections
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
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
    with jax.profiler.trace(args.out, profiler_options=opts), \
            jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.admit"):
                b.admit()
            with jax.profiler.TraceAnnotation("bench.tick"):
                b.tick()
    path = next(Path(args.out).rglob("*.xplane.pb"))
    print(f"trace: {path} {path.stat().st_size} bytes")
    data = jax.profiler.ProfileData.from_file(str(path))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  line {line.name!r}: {len(evs)} events; "
                  f"{names.most_common(12)}")
            for e in evs[:3]:
                print(f"    ev {e.name!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} stats={list(e.stats)[:8]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
