"""Device time of a program by phase, and device idle inside the
program's own host spans: the reduction behind the ``tick_*_ms`` metrics.

The serving program registers, for each program it compiles, the named
scope ("phase") each HLO instruction runs under (`repro.obs.phase_of`),
and records its host spans in a flight recorder (`repro.obs.RECORDER`)
on the ``perf_counter`` clock.  Here an op's time is its self time: its
duration less the ops nested in it, so a ``while`` counts through its
body.  A program without a phase registry or recorder gives None
throughout, and so does a trace with no run of the program.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from .trace import Event, Trace


def program_obs():
    """The program's observability module where it has a phase registry
    and a flight recorder, else None."""
    try:
        from repro import obs
    except ImportError:
        return None
    if hasattr(obs, "phase_of") and hasattr(obs, "RECORDER"):
        return obs
    return None


def self_times(events: Sequence[Event]) -> List[Tuple[str, float, float]]:
    """(text, start, self time) of each event, in start order: its
    duration less the time of the events nested directly in it."""
    out: List[List] = []
    stack: List[Tuple[int, float]] = []        # (index in out, end)
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1]:
            stack.pop()
        if stack:
            i, end = stack[-1]
            out[i][2] -= min(d, end - s)
        out.append([name, s, float(d)])
        stack.append((len(out) - 1, s + d))
    return [tuple(e) for e in out]


def ops_in_runs(trace: Trace, function: str
                ) -> Tuple[int, List[Tuple[str, float]]]:
    """(runs of ``jit_<function>`` inside the window, (text, self ns) of
    every op that started inside one of them), over every chip."""
    pre = f"jit_{function}("
    a, b = trace.window
    runs, ops = 0, []
    for c in trace.chips():
        spans = sorted((s, s + d) for n, s, d in trace.modules[c]
                       if n.startswith(pre) and a <= s and s + d <= b)
        runs += len(spans)
        if not spans:
            continue
        k = 0
        for text, s, self_ns in self_times(trace.ops[c]):
            while k < len(spans) and s >= spans[k][1]:
                k += 1
            if k == len(spans):
                break
            if s >= spans[k][0]:
                ops.append((text, self_ns))
    return runs, ops


def phase_split(trace: Trace, function: str, inherited: bool = False
                ) -> Optional[Dict]:
    """Device ms per run of ``jit_<function>`` by phase (None: the ops
    no scope covers), or None where the program registered no map of
    it or the window holds no run.  With `inherited` the keys are
    (phase, whether the op took it from a loop, an operand or a user
    rather than its own scope), and None stays None."""
    obs = program_obs()
    if obs is None or (inherited and not hasattr(obs, "phase_lookup")):
        return None
    runs, ops = ops_in_runs(trace, function)
    if not runs:
        return None
    look = obs.phase_lookup if inherited else obs.phase_of
    memo: Dict[str, object] = {}
    split: Dict[object, float] = {}
    for text, self_ns in ops:
        if text not in memo:
            memo[text] = look(text, function)
        p = memo[text]
        split[p] = split.get(p, 0.0) + self_ns
    if not any(p is not None for p in split):
        return None
    return {p: t / runs * 1e-6 for p, t in split.items()}


def phase_ms(trace: Trace, function: str, phase: str) -> Optional[float]:
    """Device ms per run of ``jit_<function>`` in ops of `phase`."""
    split = phase_split(trace, function)
    return None if split is None else split.get(phase, 0.0)


def aligned_spans(trace: Trace, window, name: str = "batcher.tick",
                  anchor: str = "bench.tick"
                  ) -> Optional[List[Tuple[float, float]]]:
    """The recorder's `name` spans inside the harness's window, moved
    onto the trace's clock: by the window's opening first, then by the
    median offset between each span and the `anchor` annotation that
    encloses it (their midpoints)."""
    obs = program_obs()
    if obs is None:
        return None
    t0, t1 = window.t0 * 1e9, window.t1 * 1e9
    spans = [(e["ts"], e["ts"] + e["dur"]) for e in obs.RECORDER.spans(name)
             if t0 <= e["ts"] and e["ts"] + e["dur"] <= t1]
    anchors = [(s, s + d) for n, s, d in trace.host if n == anchor]
    if not spans or not anchors:
        return None
    coarse = trace.window[0] - t0
    offsets = []
    for s, e in spans:
        mid = (s + e) / 2
        for a, b in anchors:
            if a <= mid + coarse <= b:
                offsets.append((a + b) / 2 - mid)
                break
    if not offsets:
        return None
    off = statistics.median(offsets)
    return [(s + off, e + off) for s, e in spans]


def idle_in_spans(trace: Trace, spans: Sequence[Tuple[float, float]]
                  ) -> float:
    """Device idle ns inside `spans` (clipped to the window),
    chip-averaged."""
    a, b = trace.window
    total = 0.0
    for c in trace.chips():
        busy = trace.busy_intervals(c)
        for s, e in spans:
            s, e = max(s, a), min(e, b)
            if e <= s:
                continue
            lo = busy[:, 0].clip(s, e)
            hi = busy[:, 1].clip(s, e)
            total += (e - s) - float((hi - lo).sum())
    return total / len(trace.chips())


def tick_idle_ms(trace: Trace, window) -> Optional[float]:
    """Device idle per tick inside the program's ``batcher.tick`` spans
    (ms): the idle that the tick's own host path leaves."""
    spans = aligned_spans(trace, window)
    if not spans:
        return None
    return idle_in_spans(trace, spans) / len(spans) * 1e-6
