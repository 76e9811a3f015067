"""The trace reduction, on a small trace recorded on a TPU v5e chip.

`bench/data/trace_sample.xplane.pb.gz` is `bench/record_trace.py`'s
output: the serving program at `.smoke()` widths under `hsiao-wb`, three
turns of admit + tick inside a ``bench.window`` annotation."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace as TR
from bench.context import Context, reader
from bench.serve import Window

SAMPLE = Path(__file__).resolve().parents[2] / "bench/data/" \
    "trace_sample.xplane.pb.gz"


@pytest.fixture(scope="module")
def tr():
    return TR.load(SAMPLE)


def test_the_window_and_the_programs_in_it(tr):
    assert tr.chips() == [0]
    assert tr.window_ns > 0
    ticks = tr.program_runs("tick")
    assert len(ticks) == 3 and min(ticks) > 0
    assert len(tr.program_runs("admit")) >= 1
    assert tr.program_runs("no_such_program") == []


def test_busy_time_is_a_union_inside_the_window(tr):
    iv = tr.busy_intervals(0)
    assert (iv[:, 1] > iv[:, 0]).all()
    assert (iv[1:, 0] > iv[:-1, 1]).all()          # disjoint, sorted
    assert iv[0, 0] >= tr.window[0] and iv[-1, 1] <= tr.window[1]
    busy = tr.busy_ns(0)
    assert sum(tr.program_runs("tick")) <= busy <= tr.window_ns


def test_idle_gaps_add_up_to_the_idle_time(tr):
    gaps = tr.idle_gaps(100)
    idle = (tr.window_ns - tr.busy_ns(0)) * 1e-9
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6)
    assert {n for n, _ in gaps} <= {"bench.admit", "bench.tick", "host"}


def test_top_ops_are_sorted_and_bounded(tr):
    top = tr.top_ops(10)
    assert 0 < len(top) <= 10
    secs = [s for _, s in top]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= tr.window_ns * 1e-9 * 1.5


def test_hlo_bytes_counts_results_and_operands_once():
    text = ('%scrub_hsiao_kernel.3 = (u32[128,128]{1,0:T(8,128)S(1)}, '
            'u32[7,512]{1,0:T(8,128)}, s32[8,128]{1,0}) custom-call('
            'u32[128,128]{1,0} %a, u32[7,512]{1,0} %b), '
            'custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={u32[128,128]{1,0}}')
    assert TR.hlo_bytes(text) == 2 * 128 * 128 * 4 + 2 * 7 * 512 * 4 \
        + 8 * 128 * 4


def test_readers_on_the_sample(tr):
    ctx = Context(conf={}, mix={"chunk": 4}, window=Window(t0=0.0, t1=1.0),
                  peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
                  setup_s=0.0, trace=tr)
    tick = reader("tick_ms.decode")(ctx)
    assert tick == pytest.approx(np.mean(tr.program_runs("tick")) * 1e-6)
    idle = reader("idle_share.decode")(ctx)
    assert 0 < idle < 100
    roof = reader("hsiao_secded_roofline.decode")(ctx)
    assert 0 < roof <= 105
    # nothing decoded in this window: no operations, no share
    assert reader("mfu.decode")(ctx) is None
