"""The reduction from a trace to busy time, own time and labelled gaps."""

import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_trace.xplane.pb")
# one traced pass cycle of a rehearsal on a v5e chip (PR 26), with the
# program's ``pbtpu/`` spans beside the harness's ``bench/`` ones
RECORDED_SPANS = os.path.join(HERE, "recorded_trace_spans.xplane.pb")


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


def test_segments_go_to_the_innermost_span():
    # a pass of 0..100 holds a boundary 10..40 with a combine 20..30 in it
    # (which ends a rounding error late: cut to its parent) and a close
    segs = tr.innermost_segments([
        (0, 100, "train_pass"), (10, 40, "boundary"),
        (20, 41, "boundary/combine"), (90, 100, "pass_close"),
        (200, 210, "load")])
    assert segs == [
        (0, 10, "train_pass"), (10, 20, "boundary"),
        (20, 40, "boundary/combine"), (40, 90, "train_pass"),
        (90, 100, "pass_close"), (200, 210, "load")]


def test_gaps_are_cut_at_span_edges_and_add_up():
    segs = [(0, 10, "train_pass"), (10, 20, "boundary"),
            (20, 40, "boundary/combine"), (40, 90, "train_pass")]
    # busy 5..15 and 30..35 inside a window of -5..120
    gaps = tr.label_gaps([(5, 15), (30, 35)], -5, 120, segs)
    assert gaps == [
        ("outside", 5e-9), ("train_pass", 5e-9), ("boundary", 5e-9),
        ("boundary/combine", 10e-9), ("boundary/combine", 5e-9),
        ("train_pass", 50e-9), ("outside", 30e-9)]
    assert sum(s for _, s in gaps) == pytest.approx((125 - 10 - 5) * 1e-9)
    assert tr.label_gaps([], 0, 10, []) == [("outside", 10e-9)]
    assert tr.label_gaps([(0, 10)], 0, 10, segs) == []


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


@pytest.mark.skipif(not os.path.exists(RECORDED_SPANS),
                    reason="no recorded trace in this checkout")
def test_recorded_gaps_carry_the_programs_span_names():
    r = tr.reduce(RECORDED_SPANS)
    assert r["devices"] == 1
    # the window is the harness's own spans', whatever the program's add
    harness = [s for s in r["spans"]
               if s[2] in ("load", "begin_pass", "end_pass")]
    assert r["window_s"] == pytest.approx(
        (max(b for _, b, _ in harness) - min(a for a, _, _ in harness)) / 1e9)
    assert r["window_s"] == pytest.approx(0.165005572)
    assert r["busy_s"] == pytest.approx(0.007714326)
    idle = {}
    for name, s in r["gaps"]:
        idle[name] = idle.get(name, 0.0) + s
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert {"ingest", "unique_keys", "boundary/writeback", "stage/read",
            "pass_close/read", "pass_close/rebind"} <= set(idle)
    # under the harness's own spans stays only what no span of the program
    # covers
    assert idle.get("load", 0.0) < 0.05 * idle["ingest"]
    assert idle["train_pass"] < 0.2 * sum(idle.values())
    names = [n for n, _ in tr.breakdown(r)["idle_gaps"]]
    assert names[:2] == ["pass_close/rebind", "ingest"]
