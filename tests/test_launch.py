"""Launch-layer units: HLO collective parsing, shape registry, policies."""
import pytest

from repro.configs import get_config, get_train_policy, list_archs
from repro.launch.hlo_stats import parse_collectives
from repro.launch.specs import SHAPES, applicable, arch_rules, skip_reason

SAMPLE_HLO = """
  %all-reduce.1 = f32[2,32768,8192]{2,1,0} all-reduce(%x), channel_id=17, replica_groups=[16,16]<=[256], to_apply=%add
  %ag = bf16[8,5120,16384]{2,0,1} all-gather(%w), dims={1}, replica_groups={{0,1,2,3},{4,5,6,7}}
  %rs = (f32[128]{0}, f32[128]{0}) reduce-scatter(%a, %b), replica_groups=[2,8]<=[16]
  %cp = bf16[1,4096]{1,0} collective-permute(%y), source_target_pairs={{0,1}}
  %a2a = f32[64,64]{1,0} all-to-all(%z), replica_groups=[4,4]<=[16]
  %ard = f32[9]{0} all-reduce-done(%start)
"""


def test_parse_collectives_bytes_and_groups():
    st = parse_collectives(SAMPLE_HLO)
    assert st.per_op_count["all-reduce"] == 1       # -done skipped
    assert st.per_op_bytes["all-reduce"] == 2 * 32768 * 8192 * 4
    assert st.per_op_bytes["all-gather"] == 8 * 5120 * 16384 * 2
    assert st.per_op_bytes["reduce-scatter"] == 2 * 128 * 4
    assert st.per_op_group["all-gather"] == 4       # explicit groups
    assert st.per_op_group["all-reduce"] == 16      # iota groups [rows,cols]
    assert st.link_traffic_bytes() > 0


def test_ring_model_all_reduce_factor():
    st = parse_collectives(
        "%ar = f32[100]{0} all-reduce(%x), replica_groups=[1,4]<=[4]")
    # 2*(n-1)/n with n=4 -> 1.5x result bytes
    assert st.link_traffic_bytes() == pytest.approx(400 * 1.5)


def test_shape_applicability():
    assert skip_reason(get_config("deepseek-67b"), SHAPES["long_500k"])
    assert applicable(get_config("mamba2-130m"), SHAPES["long_500k"])
    assert applicable(get_config("recurrentgemma-2b"), SHAPES["long_500k"])
    for arch in list_archs():
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert applicable(get_config(arch), SHAPES[s])


def test_train_policies_resolve():
    for arch in list_archs():
        p = get_train_policy(arch)
        assert set(p) >= {"microbatches", "param_dtype", "opt_dtype", "grad_dtype"}
    assert get_train_policy("llama4-maverick-400b-a17b")["param_dtype"] == "bfloat16"


def test_serve_rules_override_only_in_serve_mode():
    base = arch_rules("llama4-maverick-400b-a17b", serve=False)
    serve = arch_rules("llama4-maverick-400b-a17b", serve=True)
    assert base.axes_for("expert") == ("model",)
    assert serve.axes_for("expert") == ("data",)
    assert serve.axes_for("model_dim") == ()


def test_roofline_param_counts_sane():
    from benchmarks.roofline import param_count
    n = param_count(get_config("deepseek-67b"))
    assert 6.2e10 < n["total"] < 7.2e10              # ~67B
    m = param_count(get_config("llama4-maverick-400b-a17b"))
    assert 3.5e11 < m["total"] < 4.6e11              # ~400B
    assert 1.4e10 < m["active"] < 2.2e10             # ~17B active
    s = param_count(get_config("mamba2-130m"))
    assert 0.8e8 < s["total"] < 2.0e8


_CACHE_SCRIPT = """
import os
import jax, jax.numpy as jnp
from repro import compile_cache
print("DIR", compile_cache.enable(), jax.config.jax_compilation_cache_dir)
print("TPU_LOG_DIR", os.environ["TPU_LOG_DIR"])
if %r:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(4.0)).block_until_ready()
"""


def _cache_run(env_dir, compile_):
    import os
    import subprocess
    import sys
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "TPU_LOG_DIR")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=src)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT % compile_],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    # the TPU runtime's logs stay off, not in a fixed path under /tmp
    assert "TPU_LOG_DIR disabled" in out.stdout.splitlines()
    return [l.split()[1:] for l in out.stdout.splitlines()
            if l.startswith("DIR")][0]


def test_compile_cache_env_dir_is_the_only_cache(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there and the
    in-repo default is not touched."""
    from repro.compile_cache import REPO_CACHE
    before = sorted(REPO_CACHE.iterdir()) if REPO_CACHE.exists() else None
    used, configured = _cache_run(tmp_path / "cc", compile_=True)
    assert used == configured == str(tmp_path / "cc")
    assert any((tmp_path / "cc").iterdir())
    after = sorted(REPO_CACHE.iterdir()) if REPO_CACHE.exists() else None
    assert after == before


def test_compile_cache_default_is_fixed_and_ignored():
    """Without the variable the cache is the fixed, git-ignored directory
    at the root of the checkout."""
    from pathlib import Path
    from repro.compile_cache import REPO_CACHE
    root = Path(__file__).resolve().parents[1]
    assert REPO_CACHE == root / ".jax_cache"
    used, configured = _cache_run(None, compile_=False)
    assert used == configured == str(REPO_CACHE)
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


SERVE_ARGS = ["--arch", "phi3-mini-3.8b", "--smoke", "--prompt-len", "8",
              "--gen", "4", "--inject-p-bit", "1e-4"]


@pytest.mark.parametrize("mode", [
    ["--batch", "2", "--chunk", "2", "--scheme", "hsiao"],
    ["--server", "--requests", "3", "--slots", "2", "--rate", "1000",
     "--chunk", "2", "--page-tokens", "4", "--scheme", "hsiao-wb"]],
    ids=["engine", "server"])
def test_serve_reads_nothing_it_donated(monkeypatch, mode):
    """`serve` hands its weights to the store: with donation on the CPU
    too, a later read of them (or of any donated tick buffer) would fail
    here as it fails on an accelerator."""
    import repro.launch.engine as engine_mod
    from repro.launch import serve
    monkeypatch.setattr(engine_mod, "DONATE_ON_CPU", True)
    out = serve.main(SERVE_ARGS + mode)
    assert int(out["stats"]["ecc_uncorrectable"]) == 0


def test_server_trace_carries_the_batchers_spans(tmp_path):
    """`serve --server --trace/--metrics`: the Chrome trace holds the
    scheduler's spans and the server record its slowest ticks, split by
    child span."""
    import json
    from repro.launch import serve
    trace, metrics = tmp_path / "t.json", tmp_path / "m.jsonl"
    out = serve.main(SERVE_ARGS + [
        "--server", "--requests", "3", "--slots", "2", "--rate", "1000",
        "--chunk", "2", "--page-tokens", "4", "--scheme", "hsiao-wb",
        "--trace", str(trace), "--metrics", str(metrics)])
    events = json.loads(trace.read_text())["traceEvents"]
    names = {e["name"] for e in events}
    assert {"batcher.tick", "tick.launch", "tick.wait", "tick.finish",
            "batcher.admit", "admit.launch", "admit.wait",
            "request", "serve"} <= names
    served = {e["rid"] for e in events if e["name"] == "request"}
    assert set(range(3)) <= served
    (rec,) = [json.loads(ln) for ln in metrics.read_text().splitlines()
              if json.loads(ln)["kind"] == "server"]
    slow = rec["slowest_ticks"]
    assert 0 < len(slow) <= 5
    assert [t["ms"] for t in slow] == sorted((t["ms"] for t in slow),
                                             reverse=True)
    for t in slow:
        assert set(t["children_ms"]) <= {"tick.launch", "tick.wait",
                                         "tick.finish", "tick.scrub",
                                         "tick.scrub_fetch"}
        assert t["ms"] >= sum(t["children_ms"].values())
    assert out["batcher"].ticks >= len(slow)
