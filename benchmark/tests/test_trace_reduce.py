"""The reduction from a trace to busy time, own time and labelled gaps."""

import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_trace.xplane.pb")


def test_union_and_clip():
    merged = tr.union([(0, 10, "a"), (5, 12, "b"), (20, 30, "c")])
    assert merged == [(0, 12), (20, 30)]
    assert tr._length(merged) == 22
    assert tr._clip(merged, 8, 25) == [(8, 12), (20, 25)]


def test_own_time_leaves_out_what_children_cover():
    # a loop of 100 ns encloses two ops of 30 and 20; a later op stands alone
    own = tr.own_time_by_name([(0, 100, "while"), (10, 40, "fusion"),
                               (50, 70, "copy"), (200, 260, "fusion")])
    assert own["while"] == pytest.approx(50e-9)
    assert own["fusion"] == pytest.approx(90e-9)
    assert own["copy"] == pytest.approx(20e-9)


def test_op_label_keeps_name_and_shape():
    assert tr.op_label(
        "%concatenate.7 = f32[29360128,15]{0,1:T(8,128)} concatenate(f32[2"
    ) == "concatenate.7 f32[29360128,15]"
    assert tr.op_label(
        "%pbtpu_binned_merge_acc.1 = f32[3670016,128]{1,0:T(8,128)} custom-"
    ) == "pbtpu_binned_merge_acc.1 f32[3670016,128]"
    assert tr.op_label("%fusion.3 = (f32[8,1]{0,1}, f32[8,1]) fusion(")         == "fusion.3 f32[8,1]"
    assert tr.op_label("dot_general.1") == "dot_general.1"


def test_program_name_drops_the_run_id():
    assert tr.program_name("jit_step_flat(1234567)") == "jit_step_flat"
    assert tr.program_name("jit_apply") == "jit_apply"


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_recorded_trace_reduces():
    r = tr.reduce(RECORDED)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    names = {name for _, _, name in r["spans"]}
    assert {"load", "begin_pass", "train_pass", "end_pass"} <= names
    assert any(p.startswith("jit_") for p in r["by_program"])
    idle = sum(s for _, s in r["gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    b = tr.breakdown(r)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
