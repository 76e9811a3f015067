"""What a metric reader is given: the cell, its window and its trace.

Each metric is a file ``bench/metrics/<name>.py`` with one function,
``read(ctx) -> float | None``; the harness finds it by the name in
`BENCHMARK.json`.  A reader that finds nothing to read returns None, and
the metric is left out of the result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import List, Optional

import numpy as np

from . import flops as F
from .serve import Window
from .trace import Trace

METRICS = Path(__file__).resolve().parent / "metrics"


@dataclasses.dataclass
class Context:
    conf: dict
    mix: dict
    window: Window
    peaks: dict
    setup_s: float
    trace: Optional[Trace] = None

    @property
    def dtype_bytes(self) -> int:
        import jax.numpy as jnp
        return jnp.dtype(self.conf["dtype"]).itemsize

    def due_in_window(self) -> List:
        """Open loop: every request due inside the window."""
        w = self.window
        return [s for s in w.requests if w.t0 <= s.due < w.t1]

    def delivered_flops(self, prefill: bool = True) -> float:
        """Model operations of the work delivered inside the window: each
        decoded token marked in it at its own cache length and, with
        `prefill`, the prefill of each admission whose first token came
        in it."""
        w, c = self.window, self.conf
        total = 0.0
        for s in w.requests:
            n_before = 0
            for i, (t, n) in enumerate(s.marks):
                if w.t0 < t <= w.t1:
                    if i == 0:
                        if prefill:
                            total += F.prefill_flops(c, len(s.prompt))
                    else:
                        ctx = len(s.prompt) + n_before + np.arange(n)
                        total += sum(F.token_flops(c, float(x)) for x in ctx)
                n_before += n
        return total

    def mean_busy_share(self) -> Optional[float]:
        t = self.trace
        if t is None:
            return None
        return float(np.mean([t.busy_ns(c) for c in t.chips()])) / t.window_ns


def p90(values) -> Optional[float]:
    v = np.asarray([x for x in values if np.isfinite(x)], float)
    return float(np.percentile(v, 90)) if v.size else None


def reader(name: str):
    path = METRICS / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
