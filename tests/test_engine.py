"""Scan-compiled generation engine (DESIGN.md §13).

Bit-exactness contracts:
* one-launch prefill+scan generation == the interpreted Python-loop
  reference, for every config family and every scheme in standard_grid();
* the engine's TMR/Compose paths == the legacy PR-4 sequential path
  (three full generations + one final vote) under identical fault keys;
* vote-every-k == vote-at-end when no faults are injected.

Plus engine telemetry (on-device counters, single fetch), TTFT, the
and the TrainLoop eval hook.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.faults import TransientBitFlips
from repro.launch.engine import (GenerationEngine, fetch_telemetry,
                                 make_eval_hook)
from repro.models import params as P
from repro.models import transformer as T
from repro.models.steps import make_decode_step, make_prefill_step
from repro.reliability import Compose, DiagParityEcc, Tmr, parse_scheme, \
    standard_grid

B, PROMPT, GEN = 2, 8, 5

ARCH_BY_FAMILY = {
    "dense": "phi3-mini-3.8b",
    "moe": "phi3.5-moe-42b-a6.6b",
    "vlm": "llama-3.2-vision-11b",
    "encdec": "seamless-m4t-medium",
    "ssm": "mamba2-130m",
}


def _setup(family):
    cfg = get_config(ARCH_BY_FAMILY[family]).smoke()
    key = jax.random.PRNGKey(0)
    params = P.materialize(key, T.model_specs(cfg))
    batch = {"tokens": jax.random.randint(key, (B, PROMPT), 0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["vis_emb"] = jax.random.normal(
            key, (B, cfg.vis_tokens, cfg.vis_dim), jnp.float32)
    if cfg.family == "encdec":
        batch["enc_emb"] = jax.random.normal(
            key, (B, PROMPT, cfg.d_model), jnp.float32)
    return cfg, key, params, batch


def _assert_scan_matches_loop(family, spec, p_bit=0.0):
    cfg, key, params, batch = _setup(family)
    engine = GenerationEngine(cfg, parse_scheme(spec), gen=GEN)
    fault = TransientBitFlips(p_bit) if p_bit else None
    store, _ = engine.prepare(params, key=key, fault=fault)
    scan, _ = engine.generate_scan(store, batch)
    loop, _ = engine.generate_loop(store, batch)
    assert scan.shape == (B, GEN) and scan.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(scan), np.asarray(loop))


# every config family through the tentpole paths (single scan, vmapped
# copy axis, fused 3-copy scrub + copy axis) ...
@pytest.mark.parametrize("family", sorted(ARCH_BY_FAMILY))
@pytest.mark.parametrize("spec", ["off", "tmr-parallel",
                                  "ecc+tmr-parallel"])
def test_scan_matches_loop_per_family(family, spec):
    _assert_scan_matches_loop(family, spec, p_bit=1e-4)


# ... and the remaining standard_grid() schemes on the dense family, so
# every scheme in the grid is covered scan-vs-loop
@pytest.mark.parametrize("spec", ["ecc", "tmr-serial", "tmr-semi",
                                  "ecc+tmr"])
def test_scan_matches_loop_remaining_grid_schemes(spec):
    _assert_scan_matches_loop("dense", spec, p_bit=1e-4)


def test_standard_grid_is_fully_covered():
    """The two parametrizations above must jointly cover standard_grid()
    (fails if the grid grows without this file keeping up)."""
    covered = {parse_scheme(s).name for s in
               ("off", "tmr-parallel", "ecc+tmr-parallel", "ecc",
                "tmr-serial", "tmr-semi", "ecc+tmr")}
    assert {s.name for s in standard_grid()} <= covered


def test_engine_tmr_matches_legacy_sequential_path():
    """Acceptance: engine TMR generations are bit-exact vs the PR-4 path
    (three sequential full generations, one final per-bit vote) under
    identical fault keys (fold_in(key, 100+i) per copy)."""
    cfg, key, params, batch = _setup("dense")
    fault = TransientBitFlips(1e-4)
    prefill = jax.jit(make_prefill_step(cfg, cache_len=PROMPT + GEN))
    decode = jax.jit(make_decode_step(cfg))

    def run_copy(p):
        tok, _, cache = prefill(p, batch)
        toks = [tok]
        for _ in range(GEN - 1):
            tok, _, cache = decode(p, tok, cache)
            toks.append(tok)
        return jnp.concatenate(toks, axis=1)

    copies = [fault.corrupt(params, jax.random.fold_in(key, 100 + i))
              for i in range(3)]
    for disc in ("serial", "parallel", "semi_parallel"):
        scheme = Tmr(disc)
        legacy = scheme.wrap(run_copy, sequential=True)(*copies)
        engine = GenerationEngine(cfg, scheme, gen=GEN)
        store, _ = engine.prepare(params, key=key, fault=fault)
        out, _ = engine.generate(store, batch)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(legacy),
                                      err_msg=disc)


def test_engine_compose_matches_legacy_sequential_path():
    """Compose: per-copy ECC scrub (legacy: a Python loop of three) + TMR
    vote must be bit-exact vs the engine's one-launch scrub + copy axis."""
    cfg, key, params, batch = _setup("dense")
    fault = TransientBitFlips(2e-4)
    scheme = Compose(DiagParityEcc(), Tmr("parallel"))
    prefill = jax.jit(make_prefill_step(cfg, cache_len=PROMPT + GEN))
    decode = jax.jit(make_decode_step(cfg))

    def run_copy(p):
        tok, _, cache = prefill(p, batch)
        toks = [tok]
        for _ in range(GEN - 1):
            tok, _, cache = decode(p, tok, cache)
            toks.append(tok)
        return jnp.concatenate(toks, axis=1)

    prot = scheme.ecc.protect(params)
    fixed_copies = []
    for i in range(3):
        bad = fault.corrupt(params, jax.random.fold_in(key, 100 + i))
        fixed, _ = scheme.ecc.scrub(scheme.ecc.adopt(bad, prot.redundancy))
        fixed_copies.append(fixed.payload)
    legacy = scheme.tmr.wrap(run_copy, sequential=True)(*fixed_copies)

    engine = GenerationEngine(cfg, scheme, gen=GEN)
    store, prep = engine.prepare(params, key=key, fault=fault)
    out, _ = engine.generate(store, batch)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(legacy))
    stats = fetch_telemetry(prep)
    assert stats["ecc_corrected"] > 0      # the injection actually landed


def test_vote_every_matches_vote_at_end_without_faults():
    """In-scan voting every k steps must be a no-op when the copies are
    identical (no faults): same tokens as vote-at-end and as a single
    unprotected generation."""
    cfg, key, params, batch = _setup("dense")
    single, _ = GenerationEngine(cfg, gen=GEN).generate(params, batch)
    scheme = Tmr("parallel")
    outs = []
    for kw in (dict(vote_every=0), dict(vote_every=2),
               dict(vote_every=2, vote_cache=True), dict(vote_every=1)):
        engine = GenerationEngine(cfg, scheme, gen=GEN, **kw)
        store, _ = engine.prepare(params)
        out, _ = engine.generate(store, batch)
        outs.append((kw, out))
    for kw, out in outs:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(single),
                                      err_msg=str(kw))


def test_in_scan_voting_stops_divergence_compounding():
    """With one heavily corrupted copy and two clean ones, in-scan voting
    (tokens + caches, every step) pins the token stream to the 2-of-3
    clean majority, and the stacked per-step disagreement counters come
    back one per generated token (prefill token included)."""
    cfg, key, params, batch = _setup("dense")
    bad = TransientBitFlips(3e-3).corrupt(params, jax.random.fold_in(key, 7))
    store = jax.tree.map(lambda a, b, c: jnp.stack([a, b, c]),
                         params, bad, params)
    clean, _ = GenerationEngine(cfg, gen=GEN).generate(params, batch)
    engine = GenerationEngine(cfg, Tmr("parallel"), gen=GEN, vote_every=1,
                              vote_cache=True)
    out, telem = engine.generate(store, batch)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
    stats = fetch_telemetry(telem)
    # counters are sampled BEFORE each vote: the corrupted copy's divergent
    # proposals must be visible even though voting then overrides them
    assert stats["tmr_step_disagreements"].sum() > 0
    assert stats["tmr_step_disagreements"].shape == (GEN,)


def test_telemetry_stays_on_device_until_fetch():
    cfg, key, params, batch = _setup("dense")
    engine = GenerationEngine(cfg, Tmr("parallel"), gen=GEN)
    store, _ = engine.prepare(params, key=key, fault=TransientBitFlips(1e-4))
    out, telem = engine.generate(store, batch)
    for v in telem.values():
        assert isinstance(v, jax.Array)     # no host transfer yet
    stats = fetch_telemetry(telem)
    assert set(stats) == {"tmr_step_disagreements",
                          "tmr_final_disagreements", "tokens_emitted"}
    assert int(stats["tokens_emitted"]) == B * GEN


def test_ttft_returns_first_token():
    cfg, key, params, batch = _setup("dense")
    engine = GenerationEngine(cfg, gen=GEN)
    tok = engine.ttft(params, batch)
    full, _ = engine.generate(params, batch)
    np.testing.assert_array_equal(np.asarray(tok[:, 0]),
                                  np.asarray(full[:, 0]))
    tmr_engine = GenerationEngine(cfg, Tmr("parallel"), gen=GEN)
    store, _ = tmr_engine.prepare(params)
    np.testing.assert_array_equal(np.asarray(tmr_engine.ttft(store, batch)),
                                  np.asarray(tok))


def test_make_eval_hook_in_train_loop(tmp_path):
    """The engine-backed eval hook fires every eval_every steps with
    device-resident tokens from the loop's current params."""
    from repro.checkpoint import Checkpointer
    from repro.runtime import LoopConfig, TrainLoop

    cfg, key, params, batch = _setup("dense")
    engine = GenerationEngine(cfg, gen=3)

    def train_step(state, b):
        return state, {"loss": jnp.zeros(())}

    loop = TrainLoop(train_step, {"params": params},
                     lambda s: jnp.zeros((2,)),
                     LoopConfig(total_steps=6, checkpoint_every=0,
                                log_every=0, eval_every=3),
                     ckpt=Checkpointer(str(tmp_path), async_save=False),
                     log=lambda *_: None,
                     eval_fn=make_eval_hook(engine, batch))
    loop.run()
    assert [e["step"] for e in loop.eval_history] == [3, 6]
    ref, _ = engine.generate(params, batch)
    for e in loop.eval_history:
        assert isinstance(e["tokens"], jax.Array)
        np.testing.assert_array_equal(np.asarray(e["tokens"]),
                                      np.asarray(ref))


def test_engine_rejects_unknown_execution():
    cfg = get_config("phi3-mini-3.8b").smoke()
    with pytest.raises(ValueError, match="scan"):
        GenerationEngine(cfg, gen=4, execution="turbo")


def test_engine_rejects_silent_vote_noops():
    """Every vote-flag combination that would silently do nothing must
    raise: no copy axis, loop execution, cache votes without vote points,
    and the serial discipline (copies never run concurrently)."""
    cfg = get_config("phi3-mini-3.8b").smoke()
    with pytest.raises(ValueError, match="copy axis"):
        GenerationEngine(cfg, gen=4, vote_every=2)
    with pytest.raises(ValueError, match="scan"):
        GenerationEngine(cfg, Tmr("parallel"), gen=4, vote_every=2,
                         execution="loop")
    with pytest.raises(ValueError, match="vote_every"):
        GenerationEngine(cfg, Tmr("parallel"), gen=4, vote_cache=True)
    with pytest.raises(ValueError, match="serial"):
        GenerationEngine(cfg, Tmr("serial"), gen=4, vote_every=2)
    GenerationEngine(cfg, Tmr("serial"), gen=4)          # vote-at-end: fine


@pytest.fixture
def donating(monkeypatch):
    """Donation on the CPU too, so a read of a donated buffer fails here
    as it would on an accelerator."""
    import repro.launch.engine as engine_mod
    monkeypatch.setattr(engine_mod, "DONATE_ON_CPU", True)


@pytest.mark.parametrize("spec", ["ecc", "ecc+tmr-parallel"])
def test_prepare_donates_params_only_when_asked(donating, spec):
    """`prepare` leaves every params leaf alive unless the caller passes
    donate=True, and the store does not depend on it."""
    cfg, key, params, _ = _setup("dense")
    engine = GenerationEngine(cfg, parse_scheme(spec), gen=GEN)
    fault = TransientBitFlips(1e-3)
    kept, _ = engine.prepare(params, key=key, fault=fault)
    assert not any(x.is_deleted() for x in jax.tree.leaves(params))
    taken, _ = engine.prepare(params, key=key, fault=fault, donate=True)
    assert all(x.is_deleted() for x in jax.tree.leaves(params))
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(taken)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
