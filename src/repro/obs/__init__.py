"""On-device telemetry subsystem (DESIGN.md §15).

One observability layer threaded through kernels, schemes, the generation
engine and the runtime:

* `MetricsRegistry` / `SCHEMA` — named, schema-validated on-device
  counters; `fetch_telemetry` is the single device->host sync.
* `Tracer` / `RECORDER` — the flight recorder: host spans (id, parent,
  request id) and counters in a bounded ring on the `perf_counter` clock,
  each span also a profiler annotation; Chrome-trace (Perfetto) JSON plus
  a JSONL metrics log, zero device syncs.  `RECORDER` is the process-wide
  one, always on.
* `Phased` / `phase_of` / `phase_lookup` — device phases: a program
  compiled ahead of time registers which named scope each of its
  instructions runs under (its own, or inherited), so that a device
  trace's op events can be reduced by phase.
* `LatencyTimeline` / `Histogram` — TTFT/TPOT latency tails from
  per-chunk host timestamps.
* `DriftDetector` — observed correction rates vs the closed-form model,
  the health signal feeding `HeartbeatMonitor`.
* `count_host_transfers` — the transfer guard that *enforces* the
  single-sync invariant in tests.
"""
from .drift import DriftDetector, DriftStatus
from .guard import TransferLedger, count_host_transfers
from .latency import Histogram, LatencyTimeline
from .registry import (DEFAULT_REGISTRY, SCHEMA, MetricSpec, MetricsRegistry,
                       ScrubMetrics, fetch_telemetry)
from .phases import (Phased, load_phase_maps, parse_phases, phase_lookup,
                     phase_maps, phase_of, register_phases)
from .trace import CAPACITY, NULL_TRACER, RECORDER, Tracer

__all__ = [
    "DEFAULT_REGISTRY", "SCHEMA", "MetricSpec", "MetricsRegistry",
    "ScrubMetrics", "fetch_telemetry",
    "Tracer", "NULL_TRACER", "RECORDER", "CAPACITY",
    "Phased", "phase_of", "phase_lookup", "phase_maps", "load_phase_maps",
    "parse_phases", "register_phases",
    "Histogram", "LatencyTimeline",
    "DriftDetector", "DriftStatus",
    "TransferLedger", "count_host_transfers",
]
