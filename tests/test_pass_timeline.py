"""The pass timeline from inside the program (ISSUE 24): every
``monitor.span`` and stage scope is a ``pbtpu/<name>`` annotation on
the profiler's clock; one pass of the training thread nests whole under
``pbtpu/train_pass``; the new stage totals reach ``Trainer.timers`` and
the flight record; ``python -m paddlebox_tpu.monitor.trace --device``
reads a capture back; ``flags.trace_device`` captures on any backend,
says why when it cannot, and stops on ``abort_pass``."""

from __future__ import annotations

import json
import time
import warnings

import numpy as np
import pytest

from paddlebox_tpu import monitor
from paddlebox_tpu.config import flags
from paddlebox_tpu.monitor import critical_path as cp_lib
from paddlebox_tpu.monitor import names
from paddlebox_tpu.monitor import trace as trace_lib
from paddlebox_tpu.monitor.registry import STATS

from test_monitor import _tiny_trainer

TRACE_FLAGS = ("trace", "trace_sample_passes", "trace_device",
               "trace_device_dir")

# what one incremental pass of the training thread holds, by parent
HEAD_AND_LOOP = ("unique_keys", "boundary", "preplan", "stage/read",
                 "h2d_stage", "train_step", "auc_update", "pass_close")
BOUNDARY_CHILDREN = ("boundary/diff", "boundary/wait_feed",
                     "boundary/fetch", "boundary/h2d",
                     "boundary/writeback", "boundary/combine",
                     "boundary/land")


@pytest.fixture(autouse=True)
def _clean():
    saved = {k: flags.get(k) for k in TRACE_FLAGS}
    h = monitor.hub()
    h.disable()
    h.abort_pass(reason="test setup")
    yield
    h.abort_pass(reason="test teardown")
    h.disable()
    for k, v in saved.items():
        flags.set(k, v)


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """Two passes of the tiny trainer through BoxPS, the second (an
    incremental boundary) under a ``jax.profiler`` capture on the CPU."""
    import jax
    from paddlebox_tpu.fleet import BoxPS
    tmp = tmp_path_factory.mktemp("timeline")
    tr, ds = _tiny_trainer(tmp)
    box = BoxPS(tr.store)
    logdir = str(tmp / "capture")
    records = []
    for k in range(2):
        if k == 1:
            jax.profiler.start_trace(logdir)
        try:
            box.begin_pass()
            tr.train_pass(ds, metrics=box.metrics)
            records.append(box.end_pass()["flight_record"])
        finally:
            if k == 1:
                jax.profiler.stop_trace()
    return {"logdir": logdir, "trainer": tr, "flights": records}


def _host_threads(logdir):
    """Per host thread, the ``pbtpu/`` events with their stats."""
    import jax
    data = jax.profiler.ProfileData.from_file(trace_lib.find_xplane(logdir))
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for ln in plane.lines:
                evs = [(e.name[len("pbtpu/"):], dict(e.stats))
                       for e in ln.events if e.name.startswith("pbtpu/")]
                if evs:
                    out.append(evs)
    return out


# ---------------------------------------------------------------------------
# the bridge and the spans
# ---------------------------------------------------------------------------

def test_capture_nests_the_pass_under_train_pass(captured):
    capture = trace_lib.read_capture(
        trace_lib.find_xplane(captured["logdir"]))
    nested = [trace_lib.nest_spans(t)[0] for t in capture["threads"]]
    main = [recs for recs in nested
            if any(r["name"] == "train_pass" for r in recs)]
    assert len(main) == 1, "one training thread holds the root"
    paths = {r["path"] for r in main[0]}
    for name in HEAD_AND_LOOP:
        assert ("train_pass", name) in paths, name
    for name in BOUNDARY_CHILDREN:
        assert ("train_pass", "boundary", name) in paths, name
    assert ("train_pass", "train_step", "push_apply") in paths
    for name in ("pass_close/rebind", "pass_close/end_pass", "stage/drain",
                 "pass_close/read"):
        assert ("train_pass", "pass_close", name) in paths, name
    # the BoxPS lifecycle calls are the root's siblings, not its children
    assert ("box_begin_pass",) in paths and ("box_end_pass",) in paths
    # the pack thread's work is on its own line, outside the root (the
    # device-scope table's builder starts after the pack thread has ended
    # and may be given its thread id, so its spans its line: ISSUE 38)
    pack = [recs for recs in nested
            if any(r["name"] == "stage/translate" for r in recs)]
    assert pack and all(r["path"] in (("stage/translate",),
                                      ("device_scopes",))
                        for recs in pack for r in recs)
    assert main[0] is not pack[0]
    built = [r for recs in nested for r in recs
             if r["name"] == "device_scopes"]
    assert built and all(r["path"] == ("device_scopes",) for r in built)
    assert ("train_pass", "pass_close", "pass_close/device_scopes") in paths


def test_every_annotation_carries_the_pass_and_is_registered(captured):
    threads = _host_threads(captured["logdir"])
    seen = set()
    for evs in threads:
        for name, stats in evs:
            seen.add(name)
            if name != "box_begin_pass":    # opens before the pass does
                assert int(stats["pass_id"]) == 2, (name, stats)
                assert "step" in stats, name
    assert {"train_pass", "stage/translate", "boundary/land"} <= seen
    assert seen <= set(names.SPAN_NAMES), seen - set(names.SPAN_NAMES)


def test_stage_scope_with_annotation_costs_microseconds():
    """No capture, hub off: a stage scope — its total, an inert
    annotation, one flag check — stays under 5 us (the span scope's twin
    is tests/test_monitor.py::test_disabled_path_call_cost)."""
    from paddlebox_tpu.monitor.timers import StageTimers
    timers = StageTimers(["read"])
    with timers("read"):                       # lazy imports, once
        pass
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with timers("read"):
            pass
    cost = (time.perf_counter() - t0) / n
    assert cost < 5e-6, f"stage scope costs {cost:.2e}s"
    assert timers.count["read"] == n + 1


def test_stage_that_is_also_a_span_is_one_scope():
    """``timers(stage, span=name)``: the total goes to the stage, the one
    event and the one annotation carry the span's name — the interval is
    never emitted twice."""
    from paddlebox_tpu.monitor.timers import StageTimers
    timers = StageTimers(["train", "read"])
    ms = monitor.MemorySink()
    monitor.hub().enable(ms)
    with timers("train", span="train_step"):
        with timers("read"):
            time.sleep(0.002)
    spans = [r["name"] for r in ms.records if r["type"] == "span"]
    assert spans == ["stage/read", "train_step"]
    assert timers.count == {"train": 1, "read": 1}
    assert timers.total["train"] >= timers.total["read"] >= 0.002


# ---------------------------------------------------------------------------
# stage totals
# ---------------------------------------------------------------------------

def test_timers_and_flight_record_carry_the_new_stages(captured):
    tr = captured["trainer"]
    total, count = tr.timers.total, tr.timers.count
    for stage in ("unique_keys", "preplan", "h2d", "head", "close",
                  "read", "translate", "train", "auc", "drain"):
        assert stage in total, stage
    steps = 2                                  # 16 examples, batch 8
    # the loop's stages count what they counted before: one read per
    # batch and one for the end of the stream, one train and auc per step
    assert count["read"] == 2 * (steps + 1)
    assert count["train"] == count["auc"] == count["h2d"] == 2 * steps
    assert count["drain"] == count["head"] == count["close"] == 2
    assert count["unique_keys"] == count["preplan"] == 2
    assert total["head"] >= total["unique_keys"] + total["preplan"]
    assert total["close"] >= total["drain"]
    for fr in captured["flights"]:
        st = fr["stage_seconds"]
        assert {"unique_keys", "preplan", "h2d", "head", "close"} <= set(st)
        assert st["head"] >= st["unique_keys"] + st["preplan"]
        att = cp_lib.attribute_pass(fr)
        # head and close enclose other stages: beside the composition,
        # never in it, so the composition still sums to the wall
        assert set(att["nested"]) == {"head", "close"}
        assert not set(att["stages"]) & {"head", "close", "translate"}
        assert sum(att["stages"].values()) + att["unattributed_seconds"] \
            == pytest.approx(att["wall_seconds"], abs=1e-4)
        assert 0 < att["coverage"] <= 1.0


def test_pass_that_raises_closes_and_a_retry_inside_except_finishes(
        tmp_path):
    """A pass aborted in its loop still closes ``pass_close`` (the
    rebind), marks itself aborted and reads nothing; the retry from
    inside the ``except`` block — the recovery flow — runs through."""
    tr, ds = _tiny_trainer(tmp_path)
    calls = []

    def dead_peer():
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("peer lost")

    tr.peer_check = dead_peer
    try:
        tr.train_pass(ds)
        raise AssertionError("the second step's peer check raises")
    except RuntimeError:
        assert tr._pass_aborted and tr.last_pass_steps == 1
        assert tr.timers.count["close"] == 1
        assert tr.timers.count["drain"] == 0
        tr.peer_check = None
        out = tr.train_pass(ds)        # an exception is being handled
    assert out["steps"] == 2 and not tr._pass_aborted
    assert tr.timers.count["close"] == 2 and tr.timers.count["drain"] == 1


def test_public_handles_on_the_live_state(captured, tmp_path):
    tr = captured["trainer"]
    tr.block_until_ready()
    eng = tr.engines()
    assert set(eng) == {"table_layout", "pull_engine", "push_engine",
                        "exchange_wire", "push_overlap", "host_plan",
                        "table_shape", "plane_shapes"}
    assert eng["push_engine"] == tr.resolved_push_engine(tr._last_ws)
    assert eng["table_shape"] == list(tr._last_ws.table.shape)
    assert eng["plane_shapes"] == [eng["table_shape"]]    # one array
    assert eng["push_overlap"] is bool(tr.push_overlap)
    assert captured["flights"][-1]["extra"]["push_engine"] == \
        eng["push_engine"]
    fresh, _ = _tiny_trainer(tmp_path)
    fresh.block_until_ready()                  # before any pass: no table
    assert fresh.engines()["table_shape"] is None
    assert fresh.engines()["plane_shapes"] is None
    assert fresh.engines()["push_engine"] is None


# ---------------------------------------------------------------------------
# the reader
# ---------------------------------------------------------------------------

def test_self_time_is_duration_minus_children():
    spans = [(0.0, 10.0, "train_pass"), (1.0, 4.0, "boundary"),
             (1.5, 2.5, "boundary/diff"), (3.0, 4.0, "boundary/land"),
             (6.0, 9.0, "train_step"), (6.5, 7.0, "push_apply"),
             (9.5, 10.000001, "pass_close")]    # ends a rounding late
    recs, segs = trace_lib.nest_spans(spans)
    by = {r["path"]: r for r in recs}
    assert by[("train_pass",)]["self_s"] == pytest.approx(3.5)
    assert by[("train_pass", "boundary")]["self_s"] == pytest.approx(1.0)
    assert by[("train_pass", "train_step", "push_apply")]["self_s"] == \
        pytest.approx(0.5)
    assert by[("train_pass", "pass_close")]["end"] == 10.0
    # the segments partition the root under the innermost span
    assert sum(b - a for a, b, _ in segs) == pytest.approx(10.0)
    assert [p for a, b, p in segs if a <= 3.5 < b] == \
        [("train_pass", "boundary", "boundary/land")]
    assert [p for a, b, p in segs if a <= 5.0 < b] == [("train_pass",)]


def test_idle_gaps_go_to_the_innermost_span():
    main = [(0.0, 10.0, "train_pass"), (0.0, 2.0, "unique_keys"),
            (2.0, 4.0, "boundary"), (3.0, 4.0, "boundary/land"),
            (4.0, 9.0, "train_step")]
    pack = [(2.0, 6.0, "stage/translate")]
    device = [(-1.0, 0.5), (4.5, 6.0), (5.0, 8.0), (12.0, 13.0)]
    rep = trace_lib.reduce_capture([pack, main], device)
    tp = rep["train_pass"]
    assert tp["device_busy_s"] == pytest.approx(0.5 + 3.5)
    assert tp["device_idle_s"] == pytest.approx(6.0)
    assert tp["self_s"] == pytest.approx(1.0)
    assert tp["longest_hole_s"] == pytest.approx(1.0)
    assert rep["idle_by_span"] == pytest.approx({
        "train_pass > unique_keys": 1.5,
        "train_pass > boundary": 1.0,
        "train_pass > boundary > boundary/land": 1.0,
        "train_pass > train_step": 1.5,
        "train_pass": 1.0})
    head = rep["longest_gaps"][0]
    assert head["seconds"] == pytest.approx(4.0)
    assert head["at_s"] == pytest.approx(0.5)
    assert head["spans"][0] == ["train_pass > unique_keys",
                                pytest.approx(1.5)]
    # the pack thread counts under the spans, never under the gaps
    assert rep["spans"]["stage/translate"]["seconds"] == pytest.approx(4.0)
    assert rep["spans"]["boundary"]["self_s"] == pytest.approx(1.0)
    # no root, no attribution — but the spans still add up
    assert "train_pass" not in trace_lib.reduce_capture([pack], device)


def test_kernel_launches_count_a_kernel_by_its_instructions_own_name():
    """An ``XLA Ops`` event is a whole HLO instruction: the forward pass's
    call under differentiation (``jvp_<name>_``) and a recomputation's
    (the plain name) are the same kernel, an instruction that only reads a
    kernel's output is not that kernel, and ``dq`` is not ``dkv``."""
    fwd = "(bf16[2,32,8192,64]{3,2,1,0}, f32[2,32,8192,128]{3,2,1,0})"
    events = [
        (f"%pbtpu_attention_fwd.1 = {fwd} custom-call(%a, %b, %c)", 0.014),
        (f"%jvp_pbtpu_attention_fwd_.1 = {fwd} custom-call(%a, %b)", 0.0139),
        ("%get-tuple-element.7 = bf16[2,32,8192,64]{3,2,1,0} "
         "get-tuple-element(%pbtpu_attention_fwd.1), index=0", 0.5),
        ("%pbtpu_attention_dq.1 = bf16[2,32,8192,64]{3,2,1,0} "
         "custom-call(%q, %pbtpu_attention_fwd.1)", 0.013),
        ("%pbtpu_attention_dkv.1 = (f32[2,32,8192,64]{3,2,1,0}) "
         "custom-call(%q)", 0.017),
        ("%fusion.12 = f32[16384,2048]{1,0} fusion(%p)", 0.2)]
    got = trace_lib.kernel_launches(events + events[:1])
    assert got == {
        "pbtpu_attention_fwd": {"launches": 3,
                                "seconds": pytest.approx(0.0419)},
        "pbtpu_attention_dq": {"launches": 1, "seconds": 0.013},
        "pbtpu_attention_dkv": {"launches": 1, "seconds": 0.017}}
    assert set(got) <= set(trace_lib.KERNEL_NAMES)
    text = trace_lib.render_capture_text({
        "device_source": "/device:TPU:0 XLA Ops", "spans": {},
        "kernels": got})
    assert [ln.split()[:2] for ln in text.splitlines()[2:6]] == [
        ["kernel", "launches"], ["pbtpu_attention_fwd", "3"],
        ["pbtpu_attention_dkv", "1"], ["pbtpu_attention_dq", "1"]]


def test_reader_cli_on_the_capture(captured, capsys):
    assert trace_lib.main(["--device", captured["logdir"], "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    tp = rep["train_pass"]
    assert tp["passes"] == 1 and rep["devices"] == 0
    assert tp["device_busy_s"] > 0        # the CPU's own hlo_op events
    assert tp["device_busy_s"] + tp["device_idle_s"] == \
        pytest.approx(tp["seconds"])
    assert sum(rep["idle_by_span"].values()) == \
        pytest.approx(tp["device_idle_s"])
    assert all(path.startswith("train_pass")
               for path in rep["idle_by_span"])
    assert rep["spans"]["train_step"]["count"] == 2
    b = rep["spans"]["boundary"]
    assert b["self_s"] < b["seconds"]
    assert rep["longest_gaps"] and rep["longest_gaps"][0]["spans"]
    # no device plane on the CPU: both reports say what was read instead,
    # and no kernel ran under its name
    assert rep["device_source"] == trace_lib.NO_DEVICE_PLANE
    assert rep["kernels"] == {}
    assert trace_lib.main(["--device", captured["logdir"]]) == 0
    text = capsys.readouterr().out
    assert "device idle by innermost span" in text
    assert f"device intervals read from: {trace_lib.NO_DEVICE_PLANE}" \
        in text


def test_reader_cli_refuses_what_it_cannot_read(tmp_path, capsys):
    assert trace_lib.main(["--device", str(tmp_path)]) == 2
    assert "no .xplane.pb" in capsys.readouterr().err
    assert trace_lib.main(["--device"]) == 2


# ---------------------------------------------------------------------------
# flags.trace_device
# ---------------------------------------------------------------------------

def _trace_device_on(tmp_path):
    flags.set("trace", True)
    flags.set("trace_sample_passes", 1)
    flags.set("trace_device", True)
    flags.set("trace_device_dir", str(tmp_path / "dev"))
    h = monitor.hub()
    h.enable(monitor.MemorySink())
    return h


def test_trace_device_captures_on_the_cpu_and_stops_on_abort(tmp_path):
    h = _trace_device_on(tmp_path)
    h.begin_pass(7)
    assert trace_lib._device_dir is not None
    with monitor.span("train_pass"):
        with monitor.span("train_step"):
            time.sleep(0.01)
    h.abort_pass(reason="the pass raised")
    assert trace_lib._device_dir is None, "abort_pass ends the capture"
    capture = trace_lib.read_capture(
        trace_lib.find_xplane(str(tmp_path / "dev" / "pass-00007")))
    rep = trace_lib.reduce_capture(capture["threads"],
                                   capture["device_ops"])
    assert rep["spans"]["train_step"]["count"] == 1
    assert rep["train_pass"]["self_s"] < rep["train_pass"]["seconds"]
    # a second window opens cleanly after the aborted one
    h.begin_pass(8)
    assert trace_lib._device_dir is not None
    h.end_pass()
    assert trace_lib._device_dir is None


def test_failing_start_trace_warns_once_counts_and_training_goes_on(
        tmp_path, monkeypatch):
    import jax

    def refuse(*a, **kw):
        raise RuntimeError("profiler says no")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    monkeypatch.setattr(trace_lib, "_device_warned", False)
    tr, ds = _tiny_trainer(tmp_path)
    _trace_device_on(tmp_path)
    errors0 = STATS.get("trace.device_capture_errors")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        outs = [tr.train_pass(ds) for _ in range(2)]
    said = [w for w in caught if "trace_device" in str(w.message)]
    assert len(said) == 1, [str(w.message) for w in caught]
    assert "profiler says no" in str(said[0].message)
    assert STATS.get("trace.device_capture_errors") - errors0 == 2
    assert all(o["steps"] == 2 for o in outs)
    assert trace_lib._device_dir is None


def test_eval_pass_takes_its_keys_under_the_train_pass_stage(tmp_path):
    """ISSUE 31: an eval pass reads its key set under the same
    ``unique_keys`` stage and span a train pass does, and from the same
    set (the records have not changed between the two)."""
    tr, ds = _tiny_trainer(tmp_path)
    tr.train_pass(ds)
    sink = monitor.MemorySink()
    monitor.hub().enable(sink)
    count0 = tr.timers.count["unique_keys"]
    s0 = STATS.snapshot()
    tr.eval_pass(ds)
    s1 = STATS.snapshot()
    assert tr.timers.count["unique_keys"] == count0 + 1
    assert [r["name"] for r in sink.records
            if r["type"] == "span"].count("unique_keys") == 1
    assert s1.get("dataset.key_set_reused", 0) \
        - s0.get("dataset.key_set_reused", 0) == 1
    assert s1.get("dataset.key_set_rebuilt", 0) \
        == s0.get("dataset.key_set_rebuilt", 0)


def test_flight_record_says_the_loads_key_set_answered(tmp_path):
    """``dataset.key_runs`` is counted by the load, before the pass opens
    (monitor.STATS); the pass's own ``stats_delta`` holds one reuse and no
    rebuild, and a rebuild once the records were rebound."""
    from paddlebox_tpu.fleet import BoxPS
    runs0 = STATS.get("dataset.key_runs")
    tr, ds = _tiny_trainer(tmp_path)
    assert STATS.get("dataset.key_runs") - runs0 == 1     # one file
    box = BoxPS(tr.store)
    deltas = []
    for _ in range(2):
        box.begin_pass()
        tr.train_pass(ds, metrics=box.metrics)
        deltas.append(box.end_pass()["flight_record"]["stats_delta"])
        ds.records = ds.records.select(np.arange(ds.records.num)[::-1])
    assert deltas[0]["dataset.key_set_reused"] == 1
    assert "dataset.key_set_rebuilt" not in deltas[0]
    assert "dataset.key_runs" not in deltas[0]
    assert deltas[1]["dataset.key_set_rebuilt"] == 1
    assert deltas[1]["dataset.key_runs"] == 3     # one run a sparse column
    assert "dataset.key_set_reused" not in deltas[1]


def test_an_eval_pass_is_a_root_of_its_own(tmp_path):
    """ISSUE 38: past its ``unique_keys`` an eval pass had no span; it is
    a root now, and the stages it shares with a train pass nest in it
    under the names they have there. ``preload_pass`` spans the hand-over
    of the next pass's keys."""
    import jax
    tr, ds = _tiny_trainer(tmp_path)
    tr.train_pass(ds)
    logdir = str(tmp_path / "capture")
    jax.profiler.start_trace(logdir)
    try:
        out = tr.eval_pass(ds)
        tr.preload_pass(ds.unique_keys())
        tr.wait_feed_pass_done()
    finally:
        jax.profiler.stop_trace()
    assert 0.0 <= out["auc"] <= 1.0
    capture = trace_lib.read_capture(trace_lib.find_xplane(logdir))
    paths = {r["path"] for t in capture["threads"]
             for r in trace_lib.nest_spans(t)[0]}
    for name in ("unique_keys", "boundary", "preplan", "stage/read",
                 "h2d_stage", "auc_update", "pass_close/read"):
        assert ("eval_pass", name) in paths, (name, sorted(paths))
    assert ("preload_pass",) in paths
    assert not any(p[0] == "train_pass" for p in paths)
    assert {"eval_pass", "preload_pass", "midpass_save"} <= set(
        names.SPAN_NAMES)
