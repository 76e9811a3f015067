"""Reduction of a profiler trace (``*.xplane.pb``) to device intervals.

What the TPU profiler writes, as read here: one plane per chip named
``/device:TPU:<n>`` with a line ``XLA Modules`` (one event per program
run, named ``jit_<function>(<fingerprint>)``) and a line ``XLA Ops`` (one
event per HLO instruction run, named by the instruction's HLO text, e.g.
``%scrub_hsiao_kernel.3 = (u32[...], ...) custom-call(u32[...] %a, ...)``;
a ``while`` op spans the ops of its body).  The host plane ``/host:CPU``
holds the harness's `jax.profiler.TraceAnnotation` spans (``bench.*``).
Times are in nanoseconds on one clock.

Everything is clipped to the harness's ``bench.window`` span.
"""
from __future__ import annotations

import collections
import dataclasses
import gzip
import re
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE = re.compile(r"\b(" + "|".join(DTYPE_BYTES) + r")\[([\d,]*)\]")
_OP = re.compile(r"%([\w.\-]+?)(?:\.\d+)? = ")

Event = Tuple[str, float, float]           # name, start_ns, duration_ns


@dataclasses.dataclass
class Trace:
    """Device and host events of one traced window."""
    window: Tuple[float, float]
    ops: Dict[int, List[Event]]            # chip -> XLA Ops events
    modules: Dict[int, List[Event]]        # chip -> XLA Modules events
    host: List[Event]                      # bench.* annotations

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def _clip(self, events: List[Event]) -> List[Event]:
        a, b = self.window
        return [e for e in events if a <= e[1] and e[1] + e[2] <= b]

    def busy_intervals(self, chip: int) -> np.ndarray:
        """Union of the chip's op intervals inside the window, (n, 2)."""
        a, b = self.window
        iv = sorted((max(s, a), min(s + d, b)) for _, s, d in self.ops[chip]
                    if d > 0 and s + d > a and s < b)
        out: List[List[float]] = []
        for s, e in iv:
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return np.asarray(out, float).reshape(-1, 2)

    def busy_ns(self, chip: int) -> float:
        iv = self.busy_intervals(chip)
        return float((iv[:, 1] - iv[:, 0]).sum())

    def chips(self) -> List[int]:
        return sorted(self.ops)

    def program_runs(self, function: str) -> List[float]:
        """Device durations (ns) of each run of the jitted `function`
        inside the window, over every chip."""
        pre = f"jit_{function}("
        return [d for c in self.chips() for n, s, d in
                self._clip(self.modules[c]) if n.startswith(pre)]

    def op_runs(self, base: str) -> List[Tuple[str, float]]:
        """(HLO text, duration ns) of each run of instructions named
        `base` (numbering stripped) inside the window, over every chip."""
        out = []
        for c in self.chips():
            for n, s, d in self._clip(self.ops[c]):
                m = _OP.match(n)
                if m and m.group(1) == base:
                    out.append((n, d))
        return out

    def top_ops(self, k: int = 10) -> List[List]:
        """The k instructions that took most device time, by name without
        numbering and the start of their HLO text (result type first).
        A `while` spans the ops of its body, so the list may add up to
        more than the window."""
        tot: Dict[str, float] = collections.Counter()
        for c in self.chips():
            for n, s, d in self._clip(self.ops[c]):
                m = _OP.match(n)
                key = m.group(1) + " " + n[m.end():] if m else n
                tot[key[:100]] += d
        return [[n, t * 1e-9 / len(self.chips())]
                for n, t in tot.most_common(k)]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Idle device time inside the window, by the host annotation
        that covers it (``host`` where none does), chip-averaged."""
        spans = sorted((s, s + d, n) for n, s, d in self.host
                       if n != "bench.window")
        tot: Dict[str, float] = collections.Counter()
        for c in self.chips():
            iv = self.busy_intervals(c)
            edges = np.concatenate([[self.window[0]], iv.ravel(),
                                    [self.window[1]]]).reshape(-1, 2)
            for g0, g1 in edges:
                if g1 <= g0:
                    continue
                covered = 0.0
                for s, e, n in spans:
                    o = min(e, g1) - max(s, g0)
                    if o > 0:
                        tot[n] += o
                        covered += o
                tot["host"] += max(0.0, (g1 - g0) - covered)
        return [[n, t * 1e-9 / len(self.chips())]
                for n, t in tot.most_common(k)]


def hlo_bytes(text: str) -> int:
    """Bytes of every array shape in an instruction's HLO text: its
    results and its operands, each counted once as written."""
    head = text.split(", custom_call_target")[0].split(", kind=")[0]
    n = 0
    for dt, dims in _SHAPE.findall(head):
        size = 1
        for x in filter(None, dims.split(",")):
            size *= int(x)
        n += size * DTYPE_BYTES[dt]
    return n


def load(path: Path) -> Trace:
    """Read a trace file (``.xplane.pb`` or gzipped ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    data = ProfileData.from_serialized_xspace(raw)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    ev = [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events]
                    (ops if line.name == "XLA Ops" else modules)[chip] = ev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events if e.name.startswith("bench.")]
    win = [(s, s + d) for n, s, d in host if n == "bench.window"]
    if not ops or not win:
        raise ValueError(f"{path}: no TPU plane or no bench.window span")
    for c in ops:
        modules.setdefault(c, [])
    return Trace(window=win[0], ops=ops, modules=modules, host=host)


def find(log_dir: Path) -> Path:
    return next(Path(log_dir).rglob("*.xplane.pb"))
