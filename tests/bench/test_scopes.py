"""Device time by phase and idle inside the program's tick spans
(`bench/scopes.py` and the ``tick_*_ms`` readers): on synthetic traces
with nested ops, and on a trace recorded on a TPU v5e chip.

`bench/data/scopes_sample.*` is `bench/record_scopes.py`'s output: the
serving program at `.smoke()` widths under `hsiao-wb`, three turns of
admit + tick, with the phase maps of the programs it compiled and the
flight recorder's spans."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import scopes
from bench import trace as TR
from bench.context import Context, reader
from bench.serve import Window
from repro import obs
from repro.obs import phases as PH

DATA = Path(__file__).resolve().parents[2] / "bench/data"
PHASE_READERS = {"repair": ["tick_repair_ms.decode", "tick_repair_ms.prefill"],
                 "refresh": ["tick_refresh_ms.decode"],
                 "gather": ["tick_gather_ms.decode"],
                 "scatter": ["tick_scatter_ms.decode"],
                 "step": ["tick_step_ms.decode"]}
IDLE_READERS = ["tick_idle_ms.decode", "tick_idle_ms.prefill"]

# a tick program: the gather is a loop whose body carries no scope of its
# own, the step a fusion, and an XLA copy that no scope covers
TICK_HLO = """HloModule jit_tick, is_scheduled=true

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte = f32[8]{0} get-tuple-element(%p), index=1
  %dus = f32[8]{0} dynamic-update-slice(%gte), metadata={op_name="x"}
  ROOT %t = (s32[], f32[8]{0}) tuple(%p, %dus)
}

%cond (p: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] compare(%p.1), direction=LT
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %while.1 = (s32[], f32[8]{0}) while(%a), condition=%cond, body=%body, metadata={op_name="jit(tick)/gather/while"}
  %fusion.2 = f32[8]{0} fusion(%while.1), kind=kLoop, calls=%f, metadata={op_name="jit(tick)/step/while/body/dot_general"}
  %copy.3 = f32[16]{0} copy(%fusion.2)
  %fusion.4 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%g, metadata={op_name="jit(tick)/vmap(scatter)/scatter"}
  ROOT %fusion.5 = f32[4]{0} fusion(%fusion.4), kind=kLoop, calls=%h, metadata={op_name="jit(tick)/repair/jit(scrub_hsiao_kernel)/pallas_call"}
}
"""


@pytest.fixture
def registry(monkeypatch):
    """An empty phase registry and flight recorder of the test's own."""
    monkeypatch.setattr(PH, "_MAPS", {})
    rec = obs.Tracer()
    monkeypatch.setattr(obs, "RECORDER", rec)
    return rec


def _ctx(trace, window=None):
    return Context(conf={}, mix={"chunk": 4},
                   window=window or Window(t0=0.0, t1=1.0),
                   peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
                   setup_s=0.0, trace=trace)


def _op(name, rtype, opcode="fusion"):
    return f"%{name} = {rtype} {opcode}(f32[8]{{0:T(128)}} %x)"


def _synthetic(t0=1_000.0):
    """Two tick runs and one admit run on one chip, ns from t0."""
    ops, mods = [], []
    for k in range(2):
        s = t0 + k * 1_000
        mods.append(("jit_tick(1)", s, 600.0))
        ops += [(_op("while.1", "(s32[], f32[8]{0:T(128)})", "while"),
                 s, 300.0),
                (_op("dus", "f32[8]{0}", "dynamic-update-slice"),
                 s + 10, 100.0),
                (_op("dus", "f32[8]{0}", "dynamic-update-slice"),
                 s + 150, 100.0),
                (_op("fusion.2", "f32[8]{0:T(128)}"), s + 300, 150.0),
                (_op("copy.3", "f32[16]{0}", "copy"), s + 450, 50.0),
                (_op("fusion.4", "f32[8]{0}"), s + 500, 60.0),
                (_op("fusion.5", "f32[4]{0}"), s + 560, 40.0)]
    mods.append(("jit_admit(2)", t0 + 2_000, 100.0))
    ops.append((_op("fusion.2", "f32[8]{0}"), t0 + 2_000, 100.0))
    return TR.Trace(window=(t0, t0 + 3_000), ops={0: ops},
                    modules={0: mods}, host=[])


def test_self_time_subtracts_nested_ops():
    events = [("while", 0.0, 100.0), ("a", 10.0, 20.0), ("b", 40.0, 50.0),
              ("c", 50.0, 10.0), ("after", 100.0, 5.0)]
    got = {n: t for n, _, t in scopes.self_times(events)}
    assert got == {"while": 30.0, "a": 20.0, "b": 40.0, "c": 10.0,
                   "after": 5.0}
    assert sum(got.values()) == 105.0        # the union of the intervals


def test_phase_split_counts_self_time_per_tick_run(registry):
    PH.register_phases(TICK_HLO, ("repair", "gather", "step", "scatter"))
    tr = _synthetic()
    split = scopes.phase_split(tr, "tick")
    # per run: the loop's 100 ns of its own plus its body's 200 ns (the
    # body inherits the loop's phase), the copy its operand's
    assert split == pytest.approx({"gather": 300e-6, "step": 200e-6,
                                   "scatter": 60e-6, "repair": 40e-6})
    assert sum(split.values()) * 1e6 == pytest.approx(
        np.mean(tr.program_runs("tick")))
    ctx = _ctx(tr)
    for phase, names in PHASE_READERS.items():
        for name in names:
            assert reader(name)(ctx) == pytest.approx(split.get(phase, 0.0))
    # the admission's op of the same name is not the tick's
    assert scopes.phase_split(tr, "admit") is None
    # what each phase took by inheritance: the loop body's update and
    # the copy; the loop itself, the fusions and the kernel own theirs
    assert scopes.phase_split(tr, "tick", inherited=True) == pytest.approx(
        {("gather", False): 100e-6, ("gather", True): 200e-6,
         ("step", False): 150e-6, ("step", True): 50e-6,
         ("scatter", False): 60e-6, ("repair", False): 40e-6})


def test_a_program_without_phases_or_recorder_reads_nothing(registry,
                                                             monkeypatch):
    tr = _synthetic()
    ctx = _ctx(tr)
    # nothing registered: no map of the tick
    for names in PHASE_READERS.values():
        assert all(reader(n)(ctx) is None for n in names)
    assert all(reader(n)(ctx) is None for n in IDLE_READERS)
    # a program older than the registry and the recorder
    PH.register_phases(TICK_HLO, ("gather", "step"))
    monkeypatch.delattr(obs, "phase_of")
    monkeypatch.delattr(obs, "RECORDER")
    assert scopes.program_obs() is None
    for names in PHASE_READERS.values():
        assert all(reader(n)(ctx) is None for n in names)
    assert all(reader(n)(ctx) is None for n in IDLE_READERS)


def test_recorder_spans_align_to_the_enclosing_annotations(registry):
    """Host spans recorded on the perf clock land inside the bench.tick
    annotations of a trace whose clock starts elsewhere, and the idle
    inside them is counted against the device's busy intervals."""
    perf0 = 5_000_000_000                  # window opening, perf ns
    trace0 = 40_000_000.0                  # the same instant, trace ns
    ticks = [(1_000_000, 3_000_000), (5_000_000, 6_500_000)]
    host = [("bench.window", trace0 + 2_000, 10_000_000.0)]
    for s, e in ticks:
        host.append(("bench.tick", trace0 + s - 20_000, e - s + 40_000.0))
        registry.add_span("batcher.tick", perf0 + s, perf0 + e)
    registry.add_span("batcher.tick", perf0 - 900_000, perf0 - 100_000)
    ops = [("%fusion.1 = f32[8]{0} fusion()", trace0 + 1_500_000,
            1_000_000.0),
           ("%fusion.1 = f32[8]{0} fusion()", trace0 + 5_000_000,
            1_500_000.0)]
    tr = TR.Trace(window=(trace0 + 2_000, trace0 + 10_002_000),
                  ops={0: ops}, modules={0: []}, host=host)
    w = Window(t0=perf0 / 1e9, t1=(perf0 + 10_000_000) / 1e9)
    spans = scopes.aligned_spans(tr, w)
    assert len(spans) == 2                 # the one before the window left
    for (s, e), (a, b) in zip(spans, [(trace0 + s, trace0 + e)
                                      for s, e in ticks]):
        assert s == pytest.approx(a, abs=1) and e == pytest.approx(b, abs=1)
    # idle inside the spans: 2 ms - 1 ms busy in the first, 0 in the second
    got = scopes.tick_idle_ms(tr, w)
    assert got == pytest.approx(0.5)
    for name in IDLE_READERS:
        assert reader(name)(_ctx(tr, w)) == pytest.approx(0.5)


# -- the chip-recorded sample --------------------------------------------------

@pytest.fixture
def sample(registry):
    doc = json.loads((DATA / "scopes_sample.json").read_text())
    PH.load_phase_maps(doc["phases"])
    for e in doc["spans"]:
        registry.add_span(e["name"], e["ts"], e["ts"] + e["dur"],
                          rid=e.get("rid"), parent=e["parent"])
    tr = TR.load(DATA / "scopes_sample.xplane.pb.gz")
    t0, t1 = doc["window_perf_s"]
    return tr, Window(t0=t0, t1=t1), doc


def test_the_sample_tick_maps_to_its_phases(sample):
    tr, _, doc = sample
    assert set(doc["phases"]) == {"tick", "admit"}
    runs, ops = scopes.ops_in_runs(tr, "tick")
    assert runs == len(tr.program_runs("tick")) == 3
    mapped = sum(t for text, t in ops if obs.phase_of(text, "tick"))
    assert mapped >= 0.9 * sum(t for _, t in ops)
    split = scopes.phase_split(tr, "tick")
    assert {"repair", "gather", "step", "scatter", "refresh"} <= set(split)
    tick_ms = np.mean(tr.program_runs("tick")) * 1e-6
    assert sum(split.values()) <= tick_ms * 1.001
    assert sum(split.values()) >= 0.9 * tick_ms
    admit = scopes.phase_split(tr, "admit")
    assert {"prefill", "place", "refresh"} <= set(admit)


def test_the_sample_tick_spans_lie_inside_their_annotations(sample):
    tr, w, _ = sample
    spans = scopes.aligned_spans(tr, w)
    annots = sorted((s, s + d) for n, s, d in tr.host if n == "bench.tick")
    assert len(spans) == len(annots) == 3
    for (s, e), (a, b) in zip(sorted(spans), annots):
        assert a - 1e6 <= s < e <= b + 1e6


def test_the_readers_on_the_sample(sample):
    tr, w, _ = sample
    ctx = _ctx(tr, w)
    split = scopes.phase_split(tr, "tick")
    for phase, names in PHASE_READERS.items():
        for name in names:
            assert reader(name)(ctx) == pytest.approx(split[phase])
            assert reader(name)(ctx) > 0
    tick_host_ms = np.mean([e["dur"] for e in obs.RECORDER.spans(
        "batcher.tick")]) * 1e-6
    for name in IDLE_READERS:
        idle = reader(name)(ctx)
        assert 0 < idle < tick_host_ms


def test_flight_parts_the_sample_tick_into_own_and_inherited(sample):
    """`bench/flight.py --trace 1`'s reduction: each phase parted into
    the ops whose own scope gave it and those that inherited it, adding
    up to the phase split the readers use."""
    from bench import flight
    tr, w, _ = sample
    line = flight.device_phases(tr, w)
    split = scopes.phase_split(tr, "tick")
    assert line["tick_ms"] == pytest.approx(
        np.mean(tr.program_runs("tick")) * 1e-6)
    by_phase = {}
    for k, v in line["phase_ms"].items():
        p = None if k == "unscoped" else k.removesuffix("~inherited")
        by_phase[p] = by_phase.get(p, 0.0) + v
    assert by_phase == pytest.approx(split)
    assert any(k.endswith("~inherited") for k in line["phase_ms"])
    assert line["idle_in_tick_runs_ms"] == pytest.approx(
        line["tick_ms"] - sum(split.values()))
    assert line["tick_idle_ms"] == pytest.approx(scopes.tick_idle_ms(tr, w))
    assert {"prefill", "place", "refresh"} <= {
        k.removesuffix("~inherited") for k in line["admit_phase_ms"]}


def test_flight_runs_split_the_slowest_ticks_by_child_span():
    """`bench/flight.py` at a size the CPU holds: with the recorder, the
    window's slowest ticks and their parts; with NULL_TRACER, none."""
    from bench import flight
    ROOT = Path(__file__).resolve().parents[2]
    mix = json.loads((ROOT / "bench/traffic/decode-off.json").read_text())
    mix.update(slots=2, clients=2, chunk=4, prompt_buckets=[16],
               gen={"dist": "pareto", "min": 8, "max": 40, "alpha": 1.0},
               gen_cap=40)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    loaded = {"cell": {"chips": 1}, "mix": mix,
              "conf": dict(name="small", n_layers=2, d_model=128, n_heads=4,
                           n_kv=2, d_ff=256, vocab=500, norm_eps=1e-5,
                           rope_theta=1e4, dtype="bfloat16"),
              "end_to_end": [m for m in bench["end_to_end"]
                             if m["name"] == "tokens_per_s"]}
    on = flight.run_once(loaded, 2 ** 31 + 5, 1.0, True)
    assert on["ticks"] > 0 and on["failed"] == 0 and on["tokens_per_s"] > 0
    slow = on["slowest_ticks"]
    assert 0 < len(slow) <= 3
    for t in slow:
        assert {"tick.launch", "tick.wait", "tick.finish"} <= \
            set(t["children_ms"])
        assert 0 <= t["start_s"] <= 1.5
    assert slow[0]["ms"] == pytest.approx(
        max(on["longest_tick_calls_ms"]), rel=0.5)
    off = flight.run_once(loaded, 2 ** 31 + 5, 1.0, False)
    assert "slowest_ticks" not in off and off["tokens_per_s"] > 0
