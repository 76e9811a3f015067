"""Fault subsystem: model determinism, campaign statistics, consumer wiring
(DESIGN.md §10)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import analytics as A
from repro.core.crossbar import Crossbar, ErrorModel
from repro.core.reliability import encode_words
from repro.core.stateful_logic import g_nor
from repro.faults import (CampaignConfig, CompositeFault, RetentionDrift,
                          StuckAtFaults, TransientBitFlips,
                          TransientGateFaults, inject_bit_flips,
                          run_campaign, sweep, wilson_interval)
from repro.kernels.inject_scrub import inject_scrub
from repro.runtime import LoopConfig, TrainLoop


# --- FaultModel determinism ---------------------------------------------------

@pytest.mark.parametrize("model", [
    TransientBitFlips(0.05), TransientGateFaults(0.05),
    StuckAtFaults(0.03, 0.03), RetentionDrift(0.05),
    CompositeFault((TransientBitFlips(0.02), StuckAtFaults(0.02, 0.02))),
], ids=lambda m: type(m).__name__)
def test_same_key_same_mask(model, key):
    words = jax.random.bits(key, (128,), jnp.uint32)
    m1 = model.corrupt_words(words, jax.random.fold_in(key, 7))
    m2 = model.corrupt_words(words, jax.random.fold_in(key, 7))
    assert (np.asarray(m1) == np.asarray(m2)).all()


def test_disjoint_keys_independent_draws(key):
    """Masks from fold_in(key, i) are pairwise distinct and uncorrelated:
    the overlap of flipped-bit sets matches the p^2 product rate."""
    model = TransientBitFlips(0.25)
    zeros = jnp.zeros((512,), jnp.uint32)
    masks = [np.asarray(model.word_mask(jax.random.fold_in(key, i), zeros))
             for i in range(4)]
    n_bits = 512 * 32
    for i in range(4):
        for j in range(i + 1, 4):
            assert (masks[i] != masks[j]).any(), (i, j)
            both = np.bitwise_and(masks[i], masks[j])
            overlap = sum(bin(x).count("1") for x in both) / n_bits
            # E[overlap] = 0.0625; 4-sigma band for n_bits draws
            assert abs(overlap - 0.0625) < 4 * np.sqrt(0.0625 / n_bits) + 0.01


def test_models_vmap_over_keys(key):
    model = TransientBitFlips(0.1)
    keys = jax.random.split(key, 8)
    masks = jax.vmap(lambda k: model.word_mask(k, jnp.zeros(32, jnp.uint32)))(keys)
    assert masks.shape == (8, 32)
    single = model.word_mask(keys[3], jnp.zeros(32, jnp.uint32))
    assert (np.asarray(masks[3]) == np.asarray(single)).all()


def test_stuck_at_permanent_and_idempotent(key):
    sa = StuckAtFaults(0.05, 0.05)
    words = jax.random.bits(key, (256,), jnp.uint32)
    once = sa.corrupt_words(words, key)
    twice = sa.corrupt_words(once, key)
    assert (np.asarray(once) == np.asarray(twice)).all()
    # dt-invariant: a defect map is not an exposure process
    long_dt = sa.corrupt_words(words, key, dt=1e6)
    assert (np.asarray(once) == np.asarray(long_dt)).all()
    sa0, sa1 = sa.stuck_masks(key, (256, 32))
    assert not bool((sa0 & sa1).any())


def test_transient_dt_scaling(key):
    p, dt = 0.01, 16.0
    model = RetentionDrift(p)
    flips = model.bit_flips(key, (100_000,), dt=dt)
    want = -np.expm1(dt * np.log1p(-p))          # 1 - (1-p)^dt ~ 0.149
    got = float(jnp.mean(flips))
    assert abs(got - want) < 4 * np.sqrt(want * (1 - want) / 100_000)


def test_inject_bit_flips_rate_and_determinism(key):
    params = {"w": jax.random.normal(key, (4096,), jnp.float32)}
    bad = inject_bit_flips(params, key, 1e-3)
    bad2 = inject_bit_flips(params, key, 1e-3)
    # compare bit patterns: a flip can mint NaNs, and NaN != NaN
    u32 = lambda x: np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint32))
    assert (u32(bad["w"]) == u32(bad2["w"])).all()
    xor = u32(bad["w"]) ^ u32(params["w"])
    rate = sum(bin(x).count("1") for x in xor) / (4096 * 32)
    assert 3e-4 < rate < 3e-3


def test_deprecated_reexport_is_same_object():
    from repro.core import reliability
    from repro.faults import models
    assert reliability.inject_bit_flips is models.inject_bit_flips


# --- campaign statistics ------------------------------------------------------

def test_wilson_interval_basics():
    lo, hi = wilson_interval(0, 0)
    assert (lo, hi) == (0.0, 1.0)
    lo, hi = wilson_interval(0, 100)
    assert lo == 0.0 and 0.0 < hi < 0.06      # rare-event: no zero-width lie
    lo, hi = wilson_interval(50, 100)
    assert lo < 0.5 < hi and hi - lo < 0.25
    wide = wilson_interval(50, 100, z=3.0)
    assert wide[0] < lo and wide[1] > hi


def test_campaign_recovers_known_probability(key):
    res = run_campaign(lambda k: jax.random.bernoulli(k, 0.3), key,
                       CampaignConfig(batch_size=512, max_trials=2048))
    assert res.n_trials == 2048
    lo, hi = res.ci
    assert lo < 0.3 < hi


def test_campaign_early_stop_and_extras(key):
    def trial(k):
        fail = jax.random.bernoulli(k, 0.5)
        return fail, {"weight": jnp.float32(2.0)}

    cfg = CampaignConfig(batch_size=128, max_trials=1 << 20,
                         min_trials=128, ci_halfwidth=0.1)
    res = run_campaign(trial, key, cfg)
    assert res.n_trials < 1 << 20              # stopped on CI width
    assert res.ci_halfwidth <= 0.1
    assert res.extras["weight"] == pytest.approx(2.0 * res.n_trials)


def test_campaign_batched_mode_matches_vmap(key):
    p = 0.2

    def batch_fn(k, n):
        return jax.random.bernoulli(k, p, (n,))

    res = run_campaign(batch_fn, key, CampaignConfig(batch_size=256,
                                                     max_trials=1024),
                       batched=True)
    assert res.n_trials == 1024
    lo, hi = res.ci
    assert lo < p < hi


def test_sweep_grid(key):
    rows = sweep(lambda p: (lambda k: jax.random.bernoulli(k, p)),
                 [{"p": 0.1}, {"p": 0.6}], jax.random.fold_in(key, 17),
                 CampaignConfig(batch_size=512, max_trials=2048, z=2.576))
    assert len(rows) == 2
    for pt, res in rows:
        assert res.contains(pt["p"]), res.describe()
    assert rows[0][1].p_hat < rows[1][1].p_hat


# --- empirical ECC statistics vs the closed forms ----------------------------

def test_single_flip_correction_rate_matches_analytics(key):
    """One scrub interval at small p: the block-failure rate matches
    weight_corruption_ecc(p, T=1, m=32) and the corrected-block rate
    matches the exactly-one-flip term, both within the Wilson interval."""
    p = 2e-4
    model = TransientBitFlips(p)

    def batch(k, n):
        kb, ki = jax.random.split(k)
        buf = jax.random.bits(kb, (n * 32,), jnp.uint32)
        par = encode_words(buf)
        mask = model.word_mask(ki, buf)
        fixed, _, counts = inject_scrub(buf, par, mask)
        fail = (fixed.reshape(n, 32) != buf.reshape(n, 32)).any(axis=-1)
        return fail, {"corrected": counts[1]}

    res = run_campaign(batch, key,
                       CampaignConfig(batch_size=2048, max_trials=8192,
                                      z=2.576), batched=True)
    p_model = float(A.weight_corruption_ecc(p, np.array([1]), m=32)[0])
    assert res.contains(p_model), (res.describe(), p_model)
    # exactly-one-flip rate: n_bits * p * (1-p)^(n_bits-1)
    n_bits = 32 * 32
    p1 = n_bits * p * (1 - p) ** (n_bits - 1)
    lo, hi = wilson_interval(int(res.extras["corrected"]), res.n_trials,
                             z=2.576)
    assert lo <= p1 <= hi, (lo, p1, hi)


# --- consumer wiring ----------------------------------------------------------

def test_error_model_float_and_model_paths_identical(key):
    rng = np.random.default_rng(3)
    state = rng.integers(0, 2, (64, 8))
    a = Crossbar.from_array(state, errors=ErrorModel(p_input=0.1))
    b = Crossbar.from_array(state,
                            errors=ErrorModel(input=TransientBitFlips(0.1)))
    oa = a.row_gate("nor", [0, 1], 5, key=key)
    ob = b.row_gate("nor", [0, 1], 5, key=key)
    assert (np.asarray(oa.state) == np.asarray(ob.state)).all()


def test_crossbar_stuck_at_inputs(key):
    """A stuck-at input model pins cells: corrupting twice with the same
    key changes nothing further (unlike transient flips)."""
    rng = np.random.default_rng(4)
    xb = Crossbar.from_array(rng.integers(0, 2, (128, 4)),
                             errors=ErrorModel(input=StuckAtFaults(0.2, 0.2)))
    once = xb.row_gate("nor", [0, 1], 3, key=key)
    again = once.row_gate("nor", [0, 1], 3, key=key)
    assert (np.asarray(again.state[:, :2]) == np.asarray(once.state[:, :2])).all()


def test_maybe_flip_accepts_fault_model(key):
    a = jax.random.bernoulli(key, 0.5, (4096,))
    b = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (4096,))
    # NOTE: the corruption key must be independent of the keys that drew the
    # inputs — bernoulli(key) and the stuck mask share uniforms otherwise
    out = g_nor(a, b, key=jax.random.fold_in(key, 2),
                p_gate=StuckAtFaults(0.5, 0.0))
    want = ~(a | b)
    # half the output cells are stuck at 0
    stuck_frac = float((out < want).mean())     # 1 -> 0 transitions
    assert 0.4 < stuck_frac / max(float(want.mean()), 1e-9) < 0.6


def test_train_loop_fault_model_hook(key):
    params = {"w": jax.random.normal(key, (256,), jnp.float32)}
    cfg = LoopConfig(inject_seed=5, fault_model=TransientBitFlips(1e-2))
    loop = TrainLoop(None, {"params": params}, None, cfg, log=lambda *_: None)
    c1 = loop._corrupt(params)
    c2 = loop._corrupt(params)
    assert (np.asarray(c1["w"]) == np.asarray(c2["w"])).all()  # keyed by step
    assert (np.asarray(c1["w"]) != np.asarray(params["w"])).any()
    loop.total_restores = 1    # post-restore replays must draw fresh flips
    c3 = loop._corrupt(params)
    assert (np.asarray(c3["w"]) != np.asarray(c1["w"])).any()


def test_train_loop_permanent_faults_use_stable_key(key):
    """A stuck-at model keeps the SAME defect map across steps and restores
    (a defect is a device property, not an exposure process)."""
    params = {"w": jax.random.normal(key, (256,), jnp.float32)}
    cfg = LoopConfig(inject_seed=5, fault_model=StuckAtFaults(0.01, 0.01))
    loop = TrainLoop(None, {"params": params}, None, cfg, log=lambda *_: None)
    c1 = loop._corrupt(params)
    loop.step = 7
    loop.total_restores = 2
    c2 = loop._corrupt(params)
    assert (np.asarray(c1["w"]) == np.asarray(c2["w"])).all()
    # corrupting the already-corrupted params is a no-op (idempotent defects)
    c3 = loop._corrupt(c1)
    assert (np.asarray(c3["w"]) == np.asarray(c1["w"])).all()


# --- row-offset draws (slab-wise corruption of large leaves) -----------------

@pytest.mark.parametrize("offset", [0, 37, 4096])
def test_uniform_row_offset_matches_whole_draw(key, offset):
    """Rows offset.. of one large draw, made on their own, are bit-equal to
    the same rows of the whole draw."""
    from repro.faults.models import uniform
    whole = jax.random.uniform(key, (5000, 32))
    part = jax.jit(uniform, static_argnums=1)(key, (512, 32),
                                              jnp.int32(offset))
    np.testing.assert_array_equal(np.asarray(part),
                                  np.asarray(whole[offset:offset + 512]))


@pytest.mark.parametrize("model", [
    TransientBitFlips(2e-3), RetentionDrift(2e-3), StuckAtFaults(1e-3, 1e-3),
    CompositeFault((TransientBitFlips(1e-3), StuckAtFaults(1e-3, 0.0))),
], ids=lambda m: type(m).__name__)
def test_slab_word_masks_match_whole_buffer(model, key):
    """A buffer corrupted slab by slab (word offsets) is corrupted exactly
    as in one call — the contract the per-leaf serving prepare relies
    on."""
    words = jax.random.bits(key, (3 * 1024,), jnp.uint32)
    whole = model.corrupt_words(words, key)
    slabs = [model.corrupt_words(words[i:i + 1024], key, offset=i)
             for i in range(0, words.shape[0], 1024)]
    np.testing.assert_array_equal(np.asarray(jnp.concatenate(slabs)),
                                  np.asarray(whole))
    assert int((whole != words).sum()) > 0
