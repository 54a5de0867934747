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


# ---- the route's reader: by name, and by the running cell's own shapes ---

def _route_record(cell_config, by_op, steps=2):
    import json
    from benchmark import work
    with open(os.path.join(HERE, "..", "configs", cell_config + ".json")) as f:
        cfg = json.load(f)
    return {"passes": [{"steps": steps}],
            "peaks": work.peaks("TPU v5 lite"),
            "work": {"flops": work.step_flops(cfg)},
            "trace": {"devices": 1, "by_op": by_op}}


def test_the_route_is_the_whole_chunk_and_the_rungs_that_ran():
    from benchmark.metrics import moe_route_ms_per_step as reader
    by_op = {
        "sort.3 s32[24576]": 0.002, "topk.1 f32[4096,6]": 0.001,
        "ragged-dot-metadata.2 s32[16]": 0.001,
        "fusion.9 s32[24576]": 0.010,               # the whole chunk
        "fusion.10 s32[17]": 0.003,           # the 16 held experts' sizes
        "fusion.13 f32[17]": 0.600, "fusion.14 s32[17,2]": 0.600,   # not them
        "select_add_fusion.4 bf16[9856,2560]": 0.020,    # a rung that ran
        "fusion.11 f32[6144]": 0.004,                    # another
        "fusion.12 pred[15488,1]": 0.500,           # a rung no product ran at
        "ragged-dot-none.7 f32[9856,768]": 0.300,   # the grouped products':
        "ragged-dot-none.8 f32[6144,2560]": 0.200,  # another metric's
        "ragged-dot-none.9 f32[16,2560,768]": 0.100,
        "pbtpu_attention_fwd.1 bf16[2,28,8192,128]": 0.400,
        "fusion.1 f32[2560,37984]": 0.700}
    record = _route_record("smallthinker_21b_ep4", by_op)
    assert reader.read(record) == pytest.approx(
        1e3 * (0.002 + 0.001 + 0.001 + 0.010 + 0.003 + 0.020 + 0.004) / 2)
    # the same trace under the hybrid's work: its rungs are other rows
    hybrid = _route_record("nemotron3_nano_ep16", by_op)
    assert reader.read(hybrid) == pytest.approx(
        1e3 * (0.002 + 0.001 + 0.001 + 0.010 + 0.004) / 2)
    # a rung's rows that the model has elsewhere (the hybrid's convolution
    # is 6144 wide) are not the route's while no chunk took that rung
    del by_op["ragged-dot-none.8 f32[6144,2560]"]
    assert reader.read(_route_record("nemotron3_nano_ep16", by_op)) \
        == pytest.approx(1e3 * (0.002 + 0.001 + 0.001 + 0.010) / 2)
    # no cell's work, no trace, or nothing of the route: nothing to read
    assert reader.read({**record, "work": {"flops": 1.0}}) is None
    assert reader.read({**record, "trace": None}) is None
    assert reader.read(_route_record("smallthinker_21b_ep4", {
        "fusion.1 f32[2560,37984]": 0.7})) is None


@pytest.mark.parametrize("metric,cell_config,seconds,share", [
    ("attention", "smallthinker_21b_ep4", 0.1759, 27.1),
    ("expert_gmm", "smallthinker_21b_ep4", 0.1107, 16.0),
    ("attention", "nemotron3_nano_ep16", 0.0136, 30.8),
    ("expert_gmm", "nemotron3_nano_ep16", 0.0660, 5.7)])
def test_a_kernels_share_comes_from_the_running_cells_reference(
        metric, cell_config, seconds, share):
    import importlib
    op = {"attention": "pbtpu_attention_dkv.1 f32[2,32,4096,128]",
          "expert_gmm": "ragged-dot-none.3 f32[3072,1856]"}[metric]
    record = _route_record(cell_config, {op: seconds, "fusion.1 f32[8]": 9.0},
                           steps=1)
    ms, pct = (importlib.import_module(f"benchmark.metrics.{metric}_{kind}")
               for kind in ("ms_per_step", "roofline_pct"))
    assert ms.read(record) == pytest.approx(1e3 * seconds)
    assert round(pct.read(record), 1) == share
    # a record of no listed cell reads the time and no share
    other = {**record, "work": {"flops": 1.0}}
    assert ms.read(other) == pytest.approx(1e3 * seconds)
    assert pct.read(other) is None


RECORDED_BY_OP = {
    # cell: (file, the rows of the whole chunk and of the rungs its traced
    # pass took, read off the file's grouped products by hand)
    "smallthinker_21b_ep4": ("by_op.smallthinker_21b_ep4.seq8k.json",
                             (24576, 6144, 9856, 15488)),
    "nemotron3_nano_ep16": ("by_op.nemotron3_nano_ep16.seq4k.json",
                            (24576, 3072))}


@pytest.mark.parametrize("cell_config", sorted(RECORDED_BY_OP))
def test_the_route_read_from_a_recorded_pass_counts_the_rungs(cell_config):
    """The ``by_op`` table of one traced pass on the chip (PR 35; the
    operations under a hundredth of a millisecond a step left out): the
    reader gives what a sum by hand over the rung shapes gives, and none
    of the grouped products' time."""
    import json
    import re
    from benchmark.metrics import expert_gmm_ms_per_step as gmm
    from benchmark.metrics import moe_route_ms_per_step as reader
    file, rows = RECORDED_BY_OP[cell_config]
    with open(os.path.join(HERE, "fixtures", file)) as f:
        recorded = json.load(f)
    record = _route_record(cell_config, recorded["by_op"], recorded["steps"])
    shaped = re.compile(r" \w+\[(%s)[,\]]| s32\[%d\]$"
                        % ("|".join(map(str, rows)), recorded["bins"]))
    named = re.compile(r"^[\w.-]*(sort|topk|ragged-dot-metadata)")
    by_hand = with_products = 0.0
    for label, s in recorded["by_op"].items():
        if shaped.search(label) or named.match(label):
            with_products += s
            if not label.startswith("ragged-dot-none"):
                by_hand += s
    route = reader.read(record)
    assert route == pytest.approx(1e3 * by_hand / recorded["steps"])
    assert route == pytest.approx(recorded["moe_route_ms_per_step"])
    assert 1e3 * with_products / recorded["steps"] > 1.5 * route
    # the parent's reader knew the whole chunk's rows alone
    whole = sum(s for label, s in recorded["by_op"].items()
                if named.match(label) or (
                    re.search(r" \w+\[24576[,\]]", label)
                    and not label.startswith("ragged-dot-none")))
    assert 1e3 * whole / recorded["steps"] < 0.6 * route
    assert gmm.read(record) == pytest.approx(
        recorded["expert_gmm_ms_per_step"])
