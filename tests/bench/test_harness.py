"""The benchmark's harness without a chip: formulas, peaks, the window's
token count, the result of a run that finds no TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import flops as F
from bench.context import Context, reader
from bench.serve import Sent, Window

ROOT = Path(__file__).resolve().parents[2]
PHI3 = json.loads((ROOT / "bench/configs/phi3-mini-3.8b.json").read_text())
DS = json.loads((ROOT / "bench/configs/deepseek-67b-l4.json").read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_parameter_counts_match_the_published_sizes():
    # phi3-mini: 3.82 B parameters with the embedding (arXiv:2404.14219)
    total = F.matmul_params(PHI3) + 32128 * 3072
    assert total == pytest.approx(3.82e9, rel=0.01)
    # deepseek 4 layers: 4.45 B with embedding and head
    total = F.matmul_params(DS) + 102400 * 8192
    assert total == pytest.approx(4.45e9, rel=0.01)
    assert F.kv_bytes_per_token(PHI3, 2) == 384 * 1024
    assert F.kv_bytes_per_token(DS, 2) == 16 * 1024


def test_token_and_prefill_flops_by_hand():
    c = dict(n_layers=2, d_model=8, n_heads=2, n_kv=1, d_ff=16, vocab=100)
    hd = 4
    layer = 8 * 8 + 8 * 2 * 4 + 8 * 8 + 8 * 32 + 16 * 8
    head = 8 * 128
    assert F.layer_params(c) == layer
    assert F.token_flops(c, 10) == 2 * (2 * layer + head) \
        + 4 * 2 * 2 * hd * 10
    assert F.prefill_flops(c, 3) == 2 * 2 * layer * 3 \
        + 4 * 2 * 2 * hd * 6 + 2 * head


def test_peaks_are_keyed_by_device_kind():
    p = F.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        F.peaks("TPU v4")


def _sent(rid, marks, prompt_len=4, gen=None, due=0.0):
    import numpy as np
    s = Sent(rid=rid, prompt=np.zeros(prompt_len, np.int32),
             gen=gen or sum(n for _, n in marks), due=due)
    s.marks = marks
    s.sent = due
    s.admit_launch = marks[0][0] - 0.1
    s.tokens = np.zeros(s.gen, np.int32)
    return s


def test_window_counts_tokens_marked_between_its_tick_boundaries():
    w = Window(t0=10.0, t1=20.0)
    # admitted before the window: its first mark and first tick fall out
    w.requests.append(_sent(0, [(9.0, 1), (10.0, 8), (12.0, 8), (21.0, 3)]))
    # admitted inside, still in flight at the close
    w.requests.append(_sent(1, [(15.0, 1), (16.0, 8), (20.0, 8),
                                (22.0, 8)]))
    assert w.tokens_in_window() == 8 + 1 + 8 + 8
    assert reader("tokens_per_s")(Context(
        conf=PHI3, mix={}, window=w, peaks={}, setup_s=1.0)) == 25 / 10.0


class _FakeTrace:
    def __init__(self, window_ns):
        self.window_ns = window_ns

    def chips(self):
        return [0]


def test_mfu_decode_for_a_known_token_count():
    w = Window(t0=0.0, t1=2.0)
    # one request decoding 16 tokens in the window after a 128 prompt
    w.requests.append(_sent(0, [(-1.0, 1), (1.0, 8), (2.0, 8)],
                            prompt_len=128))
    # one admitted inside the window: its prefill is not decode work
    w.requests.append(_sent(1, [(1.5, 1)], prompt_len=128))
    peaks = F.peaks("TPU v5 lite")
    ctx = Context(conf=PHI3, mix={"chunk": 8}, window=w, peaks=peaks,
                  setup_s=0.0, trace=_FakeTrace(2e9))
    want = sum(F.token_flops(PHI3, 128 + 1 + i) for i in range(16))
    got = reader("mfu.decode")(ctx)
    assert got == pytest.approx(100 * want / 2.0 / 197e12)
    # about 7.6 GFLOP a token: 16 tokens in 2 s is 0.03% of the peak
    assert 0.02 < got < 0.04
    both = want + F.prefill_flops(PHI3, 128)
    assert reader("mfu.prefill")(ctx) == pytest.approx(
        100 * both / 2.0 / 197e12)


def test_every_metric_has_a_reader_and_every_cell_its_files():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(reader(m["name"]))
    for w in BENCH["workloads"]:
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").is_file()
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_every_cell_serves_within_its_models_published_context():
    configs = {c["name"]: json.loads((ROOT / c["file"]).read_text())
               for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        mix = json.loads((ROOT / "bench/traffic" / f"{w['traffic']}.json")
                         .read_text())
        longest = max(mix["prompt_buckets"]) + mix["gen_cap"]
        assert longest <= configs[w["config"]]["max_position_embeddings"]


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi3-decode-off",
         "--seed", str(2 ** 33 + 5), "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = _run(ROOT, env)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_a_run_with_only_the_benchmark_files_fails(tmp_path):
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = _run(tmp_path, env)
    assert r.returncode != 0 and r.stdout.strip() == ""
