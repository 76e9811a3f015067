"""Observability subsystem (DESIGN.md §15).

Covers the four pillars end to end:

* `MetricsRegistry` — fixed schema, device-side accumulation that
  round-trips under jit / vmap / shard_map (psum'd sharded counters ==
  the single-device counts), and `fetch` as the one host sync;
* `Tracer` — Chrome-trace (Perfetto-loadable) JSON validity and the
  JSONL metrics log;
* latency tails — `Histogram` / `LatencyTimeline` math on synthetic
  timestamps, and chunk-compiled generation bit-exact vs the one-launch
  scan for every scheme in `standard_grid()`;
* drift + monitor — `DriftDetector` hot/cold/evidence-floor verdicts,
  the structured `ScrubMetrics` monitor record (and the deprecated
  bare-int shim).

The transfer-guard tests are the acceptance teeth: with telemetry AND
tracing enabled, the engine's timed generation region performs exactly
ONE device->host sync (the `fetch_telemetry` call) for every scheme in
the grid.  Like test_sharded_engine.py, the shard_map test needs >= 4
devices and is re-run in a subprocess with forced host devices on
single-device hosts.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.analytics import ScrubTrajectory, expected_scrub_rates
from repro.faults import TransientBitFlips
from repro.launch.engine import GenerationEngine, fetch_telemetry
from repro.models import params as P
from repro.models import transformer as T
from repro.obs import (DEFAULT_REGISTRY, NULL_TRACER, DriftDetector,
                       Histogram, LatencyTimeline, MetricsRegistry,
                       MetricSpec, ScrubMetrics, Tracer,
                       count_host_transfers)
from repro.reliability import DiagParityEcc, parse_scheme, standard_grid
from repro.runtime.monitor import Decision, HeartbeatMonitor

MULTI = jax.device_count() >= 4
needs_devices = pytest.mark.skipif(
    not MULTI, reason="needs >= 4 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")

B, PROMPT, GEN = 2, 4, 6
P_BIT = 2e-3   # dense enough that scrub/vote counters are nonzero


def _cfg():
    return get_config("phi3-mini-3.8b").smoke().replace(
        n_layers=1, d_model=16, n_heads=2, n_kv=2, d_ff=32, vocab=512)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    params = P.materialize(key, T.model_specs(cfg))
    batch = {"tokens": jax.random.randint(key, (B, PROMPT), 0, cfg.vocab)}
    return cfg, key, params, batch


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_registry_schema_is_closed():
    reg = DEFAULT_REGISTRY
    assert "ecc_corrected" in reg.names and "tokens_emitted" in reg.names
    with pytest.raises(KeyError, match="unknown metric"):
        reg.spec("adhoc_counter")
    with pytest.raises(KeyError, match="adhoc_counter"):
        reg.fetch({"adhoc_counter": jnp.zeros(())})
    with pytest.raises(ValueError, match="duplicate"):
        MetricsRegistry([MetricSpec("a"), MetricSpec("a")])
    with pytest.raises(ValueError, match="kind"):
        MetricSpec("a", kind="histogram")


def test_registry_accumulate_semantics():
    reg = DEFAULT_REGISTRY
    m = reg.zeros(["ecc_corrected", "tmr_step_disagreements"])
    assert m["ecc_corrected"].shape == ()
    assert m["tmr_step_disagreements"].shape == (0,)
    m = reg.accumulate(m, {"ecc_corrected": 3,
                           "tmr_step_disagreements": jnp.array([1, 2])})
    m = reg.accumulate(m, {"ecc_corrected": 4,
                           "tmr_step_disagreements": 7})
    fetched = reg.fetch(m)
    assert int(fetched["ecc_corrected"]) == 7          # counter: adds
    np.testing.assert_array_equal(fetched["tmr_step_disagreements"],
                                  [1, 2, 7])           # series: stacks


def test_registry_accumulate_under_jit_and_vmap():
    reg = DEFAULT_REGISTRY

    @jax.jit
    def run(xs):
        m = reg.zeros(["ecc_corrected", "faults_injected"])
        for x in xs:                      # unrolled device-side adds
            m = reg.accumulate(m, {"ecc_corrected": x,
                                   "faults_injected": 2 * x})
        return m

    out = reg.fetch(run(jnp.arange(5, dtype=jnp.int32)))
    assert int(out["ecc_corrected"]) == 10
    assert int(out["faults_injected"]) == 20

    per_row = jax.vmap(lambda x: reg.accumulate(
        reg.zeros(["ecc_corrected"]), {"ecc_corrected": x})["ecc_corrected"])
    xs = jnp.arange(8, dtype=jnp.int32)
    assert int(per_row(xs).sum()) == int(xs.sum())


@needs_devices
def test_registry_psum_matches_single_device():
    """Counters accumulated per shard and psum'd inside shard_map equal
    the single-device totals bit for bit (DESIGN.md §14)."""
    from jax.sharding import Mesh, PartitionSpec

    reg = DEFAULT_REGISTRY
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    xs = jnp.arange(16, dtype=jnp.int32)

    def body(x):
        m = reg.accumulate(reg.zeros(["ecc_corrected", "faults_injected"]),
                           {"ecc_corrected": x.sum(),
                            "faults_injected": (x * 2).sum()})
        return reg.psum(m, "data")

    sharded = jax.shard_map(body, mesh=mesh,
                            in_specs=PartitionSpec("data"),
                            out_specs=PartitionSpec())(xs)
    single = reg.accumulate(reg.zeros(["ecc_corrected", "faults_injected"]),
                            {"ecc_corrected": xs.sum(),
                             "faults_injected": (xs * 2).sum()})
    got, want = reg.fetch(sharded), reg.fetch(single)
    assert int(got["ecc_corrected"]) == int(want["ecc_corrected"]) == 120
    assert int(got["faults_injected"]) == int(want["faults_injected"])


@pytest.mark.slow
@pytest.mark.skipif(MULTI, reason="already running with >= 4 devices")
def test_psum_subprocess():
    """Single-device hosts: run the psum test with 4 forced host devices
    (jax locks the device count at first init)."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu", PYTHONPATH=src)
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         "-k", "psum_matches_single_device", os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]


def test_scrub_into_accumulates_on_device(setup):
    """scheme.scrub_into folds ScrubReports into a registry accumulator
    with device adds; repeated scrubs sum; one fetch at the end."""
    cfg, key, params, _ = setup
    scheme = DiagParityEcc()
    prot = scheme.corrupt_store(scheme.protect(params),
                                TransientBitFlips(P_BIT), key)
    names = ["ecc_corrected", "ecc_parity_fixed", "ecc_uncorrectable"]
    metrics = DEFAULT_REGISTRY.zeros(names)
    prot, metrics = scheme.scrub_into(prot, metrics)
    once = fetch_telemetry(metrics)
    assert once["ecc_corrected"] > 0          # live counters, not vacuous
    # second scrub of the now-clean store adds zero
    _, metrics = scheme.scrub_into(prot, metrics)
    twice = fetch_telemetry(metrics)
    assert int(twice["ecc_corrected"]) == int(once["ecc_corrected"])
    for v in metrics.values():
        assert isinstance(v, jax.Array)       # never left the device

    tmr = parse_scheme("tmr-parallel")
    tprot = tmr.corrupt_store(tmr.protect(params),
                              TransientBitFlips(P_BIT), key)
    tmet = DEFAULT_REGISTRY.zeros(["ecc_corrected", "ecc_parity_fixed",
                                   "ecc_uncorrectable",
                                   "tmr_final_disagreements"])
    _, tmet = tmr.scrub_into(tprot, tmet)
    tstats = fetch_telemetry(tmet)
    # voting schemes surface their vote share through the registry
    assert int(tstats["tmr_final_disagreements"]) > 0


# --------------------------------------------------------------------------
# tracer: Chrome trace + JSONL
# --------------------------------------------------------------------------

def test_chrome_trace_is_valid(tmp_path):
    tracer = Tracer(enabled=True, pid=7)
    with tracer.trace("outer", scheme="ecc"):
        with tracer.trace("inner"):
            pass
    tracer.instant("restore", step=3)
    tracer.counter("step_s", 0.25)
    tracer.metrics({"loss": jnp.float32(1.5), "step": 2}, kind="heartbeat")

    doc = tracer.chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert [e["name"] for e in doc["traceEvents"]] == [
        "inner", "outer", "restore", "step_s"]     # spans close inner-first
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "i", "C")
        assert isinstance(ev["ts"], float) and ev["ts"] >= 0
        assert ev["pid"] == 7
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
    outer = doc["traceEvents"][1]
    assert outer["args"] == {"scheme": "ecc"}
    # spans nest: inner lies within outer
    inner = doc["traceEvents"][0]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3

    path = tmp_path / "trace.json"
    tracer.write_chrome(str(path))
    with open(path) as f:
        assert json.load(f) == json.loads(json.dumps(doc))

    jl = tmp_path / "metrics.jsonl"
    tracer.write_jsonl(str(jl), extra=[{"kind": "extra", "v": 1}])
    lines = [json.loads(ln) for ln in jl.read_text().splitlines()]
    assert lines[0]["kind"] == "heartbeat"
    assert lines[0]["loss"] == 1.5             # jnp scalar -> plain float
    assert lines[1] == {"kind": "extra", "v": 1}


def test_null_tracer_records_nothing(tmp_path):
    with NULL_TRACER.trace("span"):
        NULL_TRACER.instant("i")
        NULL_TRACER.counter("c", 1.0)
        NULL_TRACER.metrics({"x": 1})
    assert NULL_TRACER.events == [] and NULL_TRACER.records == []


# --------------------------------------------------------------------------
# latency tails
# --------------------------------------------------------------------------

def test_histogram_tails():
    h = Histogram([1.0, 2.0, 3.0])
    h.record(4.0)
    h.extend([5.0, 6.0])
    m = h.merge(Histogram([7.0]))
    assert len(m) == 7 and m.percentile(50) == 4.0
    s = m.summary()
    assert s["count"] == 7 and s["min"] == 1.0 and s["max"] == 7.0
    assert Histogram().summary() == {"count": 0}
    assert np.isnan(Histogram().percentile(99))
    # ndarray input (the LatencyTimeline.summary path) must not be
    # truth-tested
    assert len(Histogram(np.arange(3.0))) == 3


def test_latency_timeline_math():
    tl = LatencyTimeline(start=10.0,
                         marks=[(10.5, 1), (10.9, 2), (11.5, 3)])
    assert tl.ttft_s == pytest.approx(0.5)
    np.testing.assert_allclose(tl.tpot_samples(),
                               [0.2, 0.2, 0.2, 0.2, 0.2])
    assert tl.tokens() == 6 and tl.total_s() == pytest.approx(1.5)
    s = tl.summary()
    assert s["tpot_p50"] == pytest.approx(0.2)
    assert s["tokens"] == 6
    fresh = LatencyTimeline()
    with pytest.raises(RuntimeError, match="begin"):
        fresh.mark(1)
    assert np.isnan(fresh.ttft_s)


# --------------------------------------------------------------------------
# chunked generation: bit-exact + timeline
# --------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", standard_grid(), ids=lambda s: s.name)
def test_chunked_matches_unchunked(setup, scheme):
    """Chunk-compiled generation (including a remainder chunk) is
    bit-exact vs the one-launch scan, with a populated timeline."""
    cfg, key, params, batch = setup
    eng = GenerationEngine(cfg, scheme, gen=GEN)
    store, prep = eng.prepare(params, key=key, fault=TransientBitFlips(P_BIT))
    ref, ref_tel = eng.generate(store, batch)
    out, tel, tl = eng.generate_chunked(store, batch, chunk=4)  # 1+4+1
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref),
                                  err_msg=scheme.name)
    want = fetch_telemetry({**prep, **ref_tel})
    got = fetch_telemetry({**prep, **tel})
    assert set(got) == set(want)
    for k in want:
        if k != "tmr_step_disagreements":   # chunked samples at chunk ends
            np.testing.assert_array_equal(np.asarray(got[k]).sum(),
                                          np.asarray(want[k]).sum(),
                                          err_msg=k)
    assert tl.tokens() == GEN
    assert len(tl.marks) == 3 and not np.isnan(tl.ttft_s)


def test_chunked_matches_vote_every(setup):
    """The in-scan vote schedule survives chunking at ANY chunk size: the
    chunk launches thread the global step offset, so (step+1) %
    vote_every fires at the same steps as the unchunked scan."""
    cfg, key, params, batch = setup
    eng = GenerationEngine(cfg, parse_scheme("tmr-parallel"), gen=GEN,
                           vote_every=2, vote_cache=True)
    store, _ = eng.prepare(params, key=key, fault=TransientBitFlips(P_BIT))
    ref, ref_tel = eng.generate(store, batch)
    for chunk in (1, 3, GEN):
        out, tel, _ = eng.generate_chunked(store, batch, chunk=chunk)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref),
                                      err_msg=f"chunk={chunk}")
        np.testing.assert_array_equal(
            np.asarray(fetch_telemetry(tel)["tmr_step_disagreements"]),
            np.asarray(fetch_telemetry(ref_tel)["tmr_step_disagreements"]),
            err_msg=f"chunk={chunk}")


def test_chunked_gen_one_edge(setup):
    cfg, key, params, batch = setup
    eng = GenerationEngine(cfg, gen=1)
    ref, _ = eng.generate(params, batch)
    out, _, tl = eng.generate_chunked(params, batch, chunk=4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert tl.tokens() == 1                     # prefill mark only


# --------------------------------------------------------------------------
# the transfer guard: single-sync telemetry invariant (acceptance)
# --------------------------------------------------------------------------

def test_transfer_guard_counts_explicit_reads():
    x = jnp.arange(4)
    with count_host_transfers() as ledger:
        jax.block_until_ready(x)            # sync point, NOT a transfer
        assert ledger.syncs == 0
        jax.device_get([x, x * 2, {"a": x}])   # one call, one sync
        assert ledger.syncs == 1
        x.tolist()
        (x + 1).item(0)
        assert ledger.syncs == 3
    assert any("jax.device_get" in s for s in ledger.sites)
    # restored outside the context
    jax.device_get(x)
    assert ledger.syncs == 3


@pytest.mark.parametrize("scheme", standard_grid(), ids=lambda s: s.name)
def test_generation_region_single_sync(setup, scheme):
    """THE invariant (ISSUE 7 acceptance): with telemetry enabled, the
    timed region — generate + block_until_ready + fetch_telemetry —
    performs exactly one device->host sync, for every grid scheme."""
    cfg, key, params, batch = setup
    eng = GenerationEngine(cfg, scheme, gen=GEN)
    store, prep = eng.prepare(params, key=key,
                              fault=TransientBitFlips(P_BIT))
    jax.block_until_ready(eng.generate(store, batch)[0])      # warmup
    store = jax.block_until_ready(store)
    with count_host_transfers() as ledger:
        out, telem = eng.generate(store, batch)
        jax.block_until_ready(out)
        stats = fetch_telemetry({**prep, **telem})
    assert ledger.syncs == 1, ledger.sites
    assert "tokens_emitted" in stats


def test_chunked_region_single_sync_with_tracing(setup):
    """Chunked generation with an ENABLED tracer and live timeline marks
    still performs exactly one sync — spans and marks are wall-clock
    reads, not device transfers."""
    cfg, key, params, batch = setup
    scheme = parse_scheme("ecc+tmr-parallel")
    eng = GenerationEngine(cfg, scheme, gen=GEN)
    store, prep = eng.prepare(params, key=key,
                              fault=TransientBitFlips(P_BIT))
    jax.block_until_ready(
        eng.generate_chunked(store, batch, chunk=2)[0])       # warmup
    store = jax.block_until_ready(store)
    tracer = Tracer(enabled=True)
    with count_host_transfers() as ledger:
        out, telem, tl = eng.generate_chunked(store, batch, chunk=2,
                                              tracer=tracer)
        stats = fetch_telemetry({**prep, **telem})
    assert ledger.syncs == 1, ledger.sites
    assert int(stats["tokens_emitted"]) == B * GEN
    assert tl.tokens() == GEN
    assert any(e["name"] == "decode_chunk" for e in tracer.events) \
        or any(e["name"] == "tmr_decode_chunk" for e in tracer.events)


# --------------------------------------------------------------------------
# drift detector
# --------------------------------------------------------------------------

def test_drift_detector_verdicts():
    det = DriftDetector(1e-3, 10)
    exp = det.expected_per_scrub
    assert exp > 0
    # on-model stream: never drifts
    for _ in range(40):
        status = det.observe(int(round(exp)))
    assert not status.drifting and 0.5 < status.ratio < 2.0

    hot = DriftDetector(1e-3, 10)
    for _ in range(4):
        status = hot.observe(int(round(exp * 10)))
    assert status.drifting and status.hot

    cold = DriftDetector(1e-3, 10)
    for _ in range(4):
        status = cold.observe(0)
    assert status.drifting and not status.hot and status.ratio == 0.0

    d = status.as_dict()
    assert d["drifting"] and not d["drift_hot"]
    assert d["drift_n_scrubs"] == 4


def test_drift_detector_evidence_floor():
    """Sparse-fault runs (expected events << 1 per scrub) never flag on
    noise: the verdict needs min_events of evidence first."""
    det = DriftDetector(1e-7, 4)     # expectation ~1e-3 events/scrub
    for _ in range(20):
        status = det.observe(0)
    assert not status.drifting
    # one unexplained burst is still below the floor...
    assert not det.observe(2).drifting
    # ...but a sustained hot stream accumulates evidence and fires
    for _ in range(10):
        status = det.observe(2)
    assert status.drifting and status.hot

    with pytest.raises(ValueError, match="p_bit"):
        DriftDetector(-1e-3, 4)


def test_drift_detector_no_prior():
    """p_bit=0 (no model): silence is fine, any corrections are
    unexplained (ratio inf) once evidence accumulates."""
    det = DriftDetector(0.0, 0)
    assert not det.observe(0).drifting
    for _ in range(8):
        status = det.observe(1)
    assert status.ratio == float("inf") and status.drifting and status.hot


def test_drift_from_trajectory_and_analytics():
    traj = ScrubTrajectory(n_blocks=10)
    exp = expected_scrub_rates(1e-3, 10)
    per_scrub = exp["corrected_per_scrub"] + 2 * exp["uncorrectable_per_scrub"]
    for step in range(12):
        traj.add(step, int(round(per_scrub)), 0, 0)
    assert traj.rate_per_scrub() == pytest.approx(round(per_scrub))
    assert traj.drift_ratio(1e-3) == pytest.approx(1.0, rel=0.15)
    assert "drift_ratio" in traj.summary(p_bit=1e-3)
    det, status = DriftDetector.from_trajectory(traj, 1e-3)
    assert status.n_scrubs == 12 and not status.drifting
    # observed corrections with no model prior -> inf
    assert traj.drift_ratio(0.0) == float("inf")


# --------------------------------------------------------------------------
# monitor: structured scrub records (bare-int triple removed)
# --------------------------------------------------------------------------

def test_monitor_structured_scrub_record():
    mon = HeartbeatMonitor()
    rec = ScrubMetrics(corrected=5, parity_fixed=1, uncorrectable=0,
                       injected=3, vote_disagreements=2)
    assert mon.record_scrub(rec) == Decision.CONTINUE
    s = mon.summary()
    assert s["bits_corrected"] == 5 and s["parity_fixed"] == 1
    assert s["vote_disagreements"] == 2 and s["faults_injected"] == 3
    assert mon.record_scrub(
        ScrubMetrics(corrected=0, uncorrectable=2)) == Decision.RESTART
    assert any("uncorrectable" in f for f in mon.flags)


def test_monitor_drift_integration():
    det = DriftDetector(1e-3, 10)
    mon = HeartbeatMonitor(drift=det)
    hot = int(round(det.expected_per_scrub * 10))
    for _ in range(4):
        mon.record_scrub(ScrubMetrics(corrected=hot))
    assert any("drift" in f and "hot" in f for f in mon.flags)
    # the flag fires once on the transition, not every scrub
    assert sum("drift" in f for f in mon.flags) == 1
    assert mon.summary()["drift"]["drift_hot"]


def test_scrub_metrics_from_fetched():
    rec = ScrubMetrics.from_fetched(
        {"ecc_corrected": jnp.int32(3), "ecc_uncorrectable": 1,
         "ecc_injected": np.int32(7),
         "tmr_step_disagreements": jnp.array([1, 0, 2]),
         "tmr_final_disagreements": jnp.int32(4)})
    assert rec.corrected == 3 and rec.uncorrectable == 1
    assert rec.injected == 7
    assert rec.vote_disagreements == 4 + 3      # final + summed series
