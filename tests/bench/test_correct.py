"""What decides `correct`, at a size the CPU holds.

The float32 reference against the serving program's own prefill and
paged decode; the whole run of a cell (`bench.run.run`) with the timed
path intact and with it broken underneath; and the control, the
reference in float8 in the program's place, which must read far above
what the served bfloat16 program reads."""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, weights as W
from bench.reference import model as R
from bench.run import run

ROOT = Path(__file__).resolve().parents[2]
PEAKS = json.loads((ROOT / "bench/peaks.json").read_text())["TPU v5 lite"]
SMALL = dict(name="small", n_layers=2, d_model=128, n_heads=4, n_kv=2,
             d_ff=256, vocab=500, norm_eps=1e-5, rope_theta=1e4,
             dtype="bfloat16", check={"sample": 6, "gap_limit": 0.05})
SEED = 2 ** 31 + 11


def _program_cfg(dtype):
    from bench.serve import program_config
    return program_config(dict(SMALL, dtype=dtype))


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4),
                                       ("bfloat16", 0.1)])
def test_reference_matches_program_prefill_and_decode(dtype, tol):
    from repro.models.steps import make_decode_step, make_prefill_step
    cfg = _program_cfg(dtype)
    params = W.program_params(SMALL, SEED, cfg.cdtype)
    toks = np.random.default_rng(0).integers(0, 500, 24).astype(np.int32)
    ref = R.logits(SMALL, SEED, R.hidden(SMALL, SEED, toks,
                                          cfg.cdtype)[15:23], cfg.cdtype)
    _, lg, cache = make_prefill_step(cfg, cache_len=32)(
        params, {"tokens": jnp.asarray(toks[None, :16])})
    err = [float(jnp.abs(lg[0, -1] - ref[0]).max())]
    decode = make_decode_step(cfg)
    for i in range(7):
        _, lg, cache = decode(params, jnp.asarray(toks[None, 16 + i:17 + i]),
                              cache)
        err.append(float(jnp.abs(lg[0, -1] - ref[i + 1]).max()))
    assert max(err) < tol, err


def _loaded(mix_name, **mix_kw):
    mix = json.loads((ROOT / "bench/traffic" / f"{mix_name}.json")
                     .read_text())
    mix.update(slots=2, clients=2, chunk=4, prompt_buckets=[16],
               gen={"dist": "pareto", "min": 8, "max": 40, "alpha": 1.0},
               gen_cap=40)
    mix.update(mix_kw)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {"cell": {"chips": 1}, "conf": dict(SMALL), "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"]
                           if m["name"] in ("tokens_per_s", "setup_s")],
            "per_layer": []}


def _run(loaded, seed=SEED, control=False):
    return run(loaded, seed, 1.5, False, jax.devices()[:1], PEAKS,
               setup_start=time.perf_counter(), control=control)


def test_served_float32_tokens_are_the_references_first_choices():
    loaded = _loaded("decode-off")
    loaded["conf"]["dtype"] = "float32"
    res = _run(loaded)
    assert res["correct"] and res["failed"] == 0
    assert res["checks"]["worst_gap"]["value"] < 1e-4


def _broken_tick(monkeypatch, alter):
    from repro.launch.batching import ContinuousBatcher
    orig = ContinuousBatcher._tick_program

    def broken(self):
        fn = orig(self)
        return lambda *a: alter(a, fn(*a))

    monkeypatch.setattr(ContinuousBatcher, "_tick_program", broken)


def _token_altered(args, out):
    """Each slot's first token of the chunk, plus one, in the output."""
    tok, ring, *rest = out
    off = args[-1]
    rows = jnp.arange(ring.shape[0])
    ring = ring.at[rows, off].set((ring[rows, off] + 1) % 500)
    return (tok, ring, *rest)


def _state_unchanged(args, out):
    """The tick's KV pool returned as it came in."""
    tok, ring, pk, pv, *rest = out
    return (tok, ring, args[3], args[4], *rest)


@pytest.mark.parametrize("alter", [_token_altered, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, alter):
    from bench import run as RUN
    _broken_tick(monkeypatch, alter)
    res = _run(_loaded("decode-off"))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", [3, 2 ** 32 + 9, 77])
def test_the_control_fails_the_limit_the_program_meets(seed):
    # at this size the program read at most 0.018 and the control at
    # least 0.098 over five seeds; the limit 0.05 lies between
    res = _run(_loaded("decode-off"), seed=seed, control=True)
    limit = res["checks"]["worst_gap"]["limit"]
    assert res["program_gap"] <= limit
    assert res["checks"]["worst_gap"]["value"] > limit
    assert not res["correct"]
