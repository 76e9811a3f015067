"""The flight recorder: host spans, instants and counters in a bounded
ring, written out as a Chrome trace (Perfetto-loadable) plus a JSONL
metrics log, with zero device syncs (DESIGN.md §15).

A span is a host interval around a launch, a wait or a piece of host
work.  Each span gets an `id`, a `parent` (the span open on the same
thread when it began) and an optional `rid`: the spans of one request
share its `rid`.  Timestamps are `time.perf_counter_ns()`, the clock of
`time.perf_counter()`.  Each span also enters
`jax.profiler.TraceAnnotation(name)`, so that in any profile it lands on
the host plane, on the device trace's clock; with no profiler running
that costs a check.  Nothing here touches a device array, so recording
never adds a host sync to a timed region; the transfer-guard tests run
with recording on to prove it.

    tracer = Tracer()
    with tracer.trace("prefill", batch=4):
        tok = fns["prefill"](store, batch)
        jax.block_until_ready(tok)          # sync point, not a transfer
    tracer.write_chrome("trace.json")        # load in Perfetto / chrome://tracing
    tracer.write_jsonl("metrics.jsonl")

A tracer keeps every event and record unless it is given a `capacity`:
then they go to rings of that many entries (`deque(maxlen=…)`), and what
falls off the far end is counted in `dropped`.  `RECORDER` is the
process-wide recorder, enabled and bounded at `CAPACITY`, so an
always-on recorder never grows without limit; serving code records into
it unless it is given another tracer.  A disabled tracer (``Tracer(enabled=False)``, or the
shared `NULL_TRACER`) makes every call a no-op.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import threading
import time
from typing import Any, Deque, Dict, Iterable, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "NULL_TRACER", "RECORDER", "CAPACITY"]

#: events (and, separately, metric records) the process recorder keeps
CAPACITY = 1 << 14


class Tracer:
    """Collects Chrome-trace events (complete spans, instants, counters)
    and JSONL metric records, all of them or (with `capacity`) the last
    `capacity` of each in a ring.  Thread-safe appends."""

    def __init__(self, enabled: bool = True, pid: int = 0,
                 capacity: Optional[int] = None):
        self.enabled = enabled
        self.pid = pid if pid else os.getpid()
        self._events: Deque[Dict[str, Any]] = collections.deque(
            maxlen=capacity)
        self._records: Deque[Dict[str, Any]] = collections.deque(
            maxlen=capacity)
        #: events and records pushed out of the full rings
        self.dropped = 0
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._t0 = time.perf_counter_ns()

    @property
    def events(self) -> List[Dict[str, Any]]:
        """The events the ring holds, oldest first (a copy)."""
        with self._lock:
            return list(self._events)

    @property
    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._records)

    def _push(self, ring: Deque, item: Dict[str, Any]) -> None:
        with self._lock:
            if len(ring) == ring.maxlen:
                self.dropped += 1
            ring.append(item)

    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @staticmethod
    def _tid() -> int:
        return threading.get_ident() % 2 ** 31

    # -- event emission ----------------------------------------------------

    @contextlib.contextmanager
    def trace(self, name: str, rid: Optional[int] = None, **args: Any):
        """Span a region: one complete ('ph': 'X') event, and the same
        name as a profiler annotation."""
        if not self.enabled:
            yield self
            return
        sid = next(self._ids)
        stack = self._stack()
        parent = stack[-1] if stack else None
        stack.append(sid)
        with TraceAnnotation(name):
            ts = time.perf_counter_ns()
            try:
                yield self
            finally:
                end = time.perf_counter_ns()
                stack.pop()
                self._push(self._events, self._span(
                    name, ts, end, sid, parent, rid, args))

    def add_span(self, name: str, start_ns: int, end_ns: int, *,
                 rid: Optional[int] = None, parent: Optional[int] = None,
                 **args: Any) -> None:
        """Record a span from timestamps already taken on the
        `perf_counter` clock (no clock read of its own)."""
        if self.enabled:
            self._push(self._events, self._span(
                name, int(start_ns), int(end_ns), next(self._ids), parent,
                rid, args))

    def _span(self, name, ts, end, sid, parent, rid, args):
        ev = {"name": name, "ph": "X", "ts": ts, "dur": end - ts,
              "pid": self.pid, "tid": self._tid(), "id": sid,
              "parent": parent}
        if rid is not None:
            ev["rid"] = rid
        if args:
            ev["args"] = args
        return ev

    def instant(self, name: str, **args: Any) -> None:
        """A zero-duration marker (heartbeats, decisions, restores)."""
        if not self.enabled:
            return
        self._push(self._events,
                   {"name": name, "ph": "i", "s": "t",
                    "ts": time.perf_counter_ns(), "pid": self.pid,
                    "tid": self._tid(), **({"args": args} if args else {})})

    def counter(self, name: str, value: float) -> None:
        """A Chrome counter track sample (step times, correction counts)."""
        if not self.enabled:
            return
        self._push(self._events,
                   {"name": name, "ph": "C", "ts": time.perf_counter_ns(),
                    "pid": self.pid, "tid": 0,
                    "args": {name: float(value)}})

    def metrics(self, record: Dict[str, Any], kind: str = "metrics") -> None:
        """Append one structured record to the JSONL metrics log (fetched
        telemetry snapshots, latency summaries, bench rows)."""
        if not self.enabled:
            return
        self._push(self._records,
                   {"t_us": (time.perf_counter_ns() - self._t0) / 1e3,
                    "kind": kind, **_jsonable(record)})

    # -- reading -----------------------------------------------------------

    def spans(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        """The complete spans the ring holds (named `name`, if given),
        in the order they ended; times in perf_counter ns."""
        return [e for e in self.events if e["ph"] == "X"
                and (name is None or e["name"] == name)]

    def slowest(self, name: str = "batcher.tick", k: int = 5,
                since_ns: int = 0, until_ns: Optional[int] = None
                ) -> List[Dict[str, Any]]:
        """The k longest `name` spans that began in [since_ns, until_ns],
        longest first, each with the time of its child spans by name (ms)
        and the rest, not in a child."""
        spans = self.spans()
        kids: Dict[int, Dict[str, float]] = collections.defaultdict(
            collections.Counter)
        for e in spans:
            if e["parent"] is not None:
                kids[e["parent"]][e["name"]] += e["dur"] / 1e6
        top = sorted((e for e in spans if e["name"] == name
                      and since_ns <= e["ts"]
                      and (until_ns is None or e["ts"] <= until_ns)),
                     key=lambda e: e["dur"], reverse=True)[:k]
        out = []
        for e in top:
            parts = dict(kids.get(e["id"], {}))
            ms = e["dur"] / 1e6
            out.append({"ms": ms, "start_s": e["ts"] / 1e9,
                        "children_ms": parts,
                        "other_ms": ms - sum(parts.values())})
        return out

    # -- output ------------------------------------------------------------

    def chrome_trace(self) -> Dict[str, Any]:
        """The Chrome trace document: valid for Perfetto and
        chrome://tracing (``traceEvents`` array of phase events), times
        in microseconds from the tracer's creation."""
        out = []
        for e in self.events:
            e = dict(e, ts=(e["ts"] - self._t0) / 1e3)
            if "dur" in e:
                e["dur"] = e["dur"] / 1e3
            out.append(e)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def write_chrome(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)

    def write_jsonl(self, path: str,
                    extra: Optional[Iterable[Dict[str, Any]]] = None) -> None:
        records = self.records
        if extra:
            records += [_jsonable(r) for r in extra]
        with open(path, "w") as f:
            for rec in records:
                f.write(json.dumps(rec) + "\n")


def _jsonable(record: Dict[str, Any]) -> Dict[str, Any]:
    """Coerce numpy/jax scalars and arrays (already fetched!) to plain
    JSON types; leaves everything else alone."""
    out = {}
    for k, v in record.items():
        if hasattr(v, "tolist"):
            v = v.tolist()
        elif hasattr(v, "item"):
            v = v.item()
        out[k] = v
    return out


#: Shared disabled tracer: instrumented code paths given it pay only a
#: truthiness check.
NULL_TRACER = Tracer(enabled=False)

#: The process-wide flight recorder: enabled and bounded.
RECORDER = Tracer(capacity=CAPACITY)
