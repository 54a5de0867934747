"""Telemetry hub: context propagation, per-pass deltas, sink isolation,
Prometheus exposition, flight-record schema against a real 2-pass run,
and the disabled-path cost contract."""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np
import pytest

from paddlebox_tpu import monitor
from paddlebox_tpu.monitor import context as mon_ctx
from paddlebox_tpu.monitor import flight
from paddlebox_tpu.monitor.registry import STATS


@pytest.fixture(autouse=True)
def _clean_hub():
    """Every test starts with a disabled hub and no open pass, and leaves
    it that way (the hub is a process singleton — leaks poison the suite
    exactly like leaked threads)."""
    h = monitor.hub()
    h.disable()
    h.abort_pass(reason="test setup")
    yield
    h.abort_pass(reason="test teardown")
    h.disable()


# ---------------------------------------------------------------------------
# context propagation
# ---------------------------------------------------------------------------

def test_context_propagates_into_spawned_threads():
    h = monitor.hub()
    ms = monitor.MemorySink()
    h.enable(ms)
    try:
        h.begin_pass(11, phase=1)
        mon_ctx.set_step(3)

        def worker():
            monitor.event("from_worker", x=1)

        t = mon_ctx.spawn(worker, name="ctx-worker")
        t.start(); t.join()
        # a plainly-created thread resolves the pass too (global fallback)
        t2 = threading.Thread(target=worker)
        t2.start(); t2.join()
        # step advanced AFTER the threads were created must be visible to
        # a thread spawned earlier (the context object is shared, mutable)
        seen = []
        start = threading.Event()
        go = threading.Event()

        def late_reader():
            start.set()
            go.wait(5)
            seen.append(mon_ctx.current().tags())

        t3 = mon_ctx.spawn(late_reader, name="late-reader")
        t3.start(); start.wait(5)
        mon_ctx.set_step(99)
        go.set(); t3.join()
        h.end_pass()
    finally:
        h.disable()
    evs = ms.find("from_worker")
    assert len(evs) == 2
    for e in evs:
        assert e["pass_id"] == 11 and e["step"] == 3 and e["phase"] == 1
        assert e["thread"] != "MainThread"
    assert seen == [{"pass_id": 11, "step": 99, "phase": 1}]
    # scope closed: events outside a pass carry nulls
    ms2 = monitor.MemorySink()
    h.enable(ms2)
    monitor.event("outside")
    h.disable()
    assert ms2.records[-1]["pass_id"] is None


def test_nested_scope_restores_outer():
    h = monitor.hub()
    h.begin_pass(1)
    handle = mon_ctx.enter_pass(2)
    assert mon_ctx.current().pass_id == 2
    mon_ctx.exit_pass(handle)
    assert mon_ctx.current().pass_id == 1
    h.end_pass()
    assert mon_ctx.current().pass_id is None


# ---------------------------------------------------------------------------
# per-pass counter deltas vs cumulative STATS
# ---------------------------------------------------------------------------

def test_flight_record_stats_delta_vs_cumulative():
    h = monitor.hub()
    monitor.counter_add("t.mon.delta", 10)       # before the pass
    h.begin_pass(21)
    monitor.counter_add("t.mon.delta", 3)
    monitor.counter_add("t.mon.fresh", 2)
    rec = h.end_pass()
    assert rec["pass_id"] == 21
    # delta since pass start, NOT the cumulative value
    assert rec["stats_delta"]["t.mon.delta"] == 3
    assert rec["stats_delta"]["t.mon.fresh"] == 2
    assert STATS.get("t.mon.delta") == 13        # cumulative untouched
    # untouched counters don't clutter the record
    assert "t.mon.delta" in rec["stats_delta"]
    h.begin_pass(22)
    rec2 = h.end_pass()
    assert "t.mon.delta" not in rec2["stats_delta"]


def test_record_train_accumulates_across_trainers():
    """Phased programs run several train_passes inside one box pass; the
    flight record must carry the sum."""
    h = monitor.hub()
    h.begin_pass(31)
    h.record_train(stage_seconds={"train": 1.0}, steps=4, examples=64,
                   seconds=2.0)
    h.record_train(stage_seconds={"train": 0.5, "auc": 0.25}, steps=2,
                   examples=32, seconds=1.0)
    rec = h.end_pass()
    assert rec["steps"] == 6 and rec["examples"] == 96
    assert rec["stage_seconds"]["train"] == pytest.approx(1.5)
    assert rec["stage_seconds"]["auc"] == pytest.approx(0.25)
    assert rec["train_seconds"] == pytest.approx(3.0)


# ---------------------------------------------------------------------------
# sink error isolation
# ---------------------------------------------------------------------------

class _BoomSink(monitor.Sink):
    def __init__(self):
        self.calls = 0

    def emit(self, rec):
        self.calls += 1
        raise RuntimeError("sink boom")


def test_failing_sink_never_kills_training_and_is_detached():
    h = monitor.hub()
    boom = _BoomSink()
    ms = monitor.MemorySink()
    h.enable(boom, ms)
    try:
        for i in range(10):
            monitor.event("tick", i=i)   # must never raise
    finally:
        # read health BEFORE disable so both live and detached states
        # are visible (disable moves live sinks to "closed")
        health = h.summary()["sinks"]
        h.disable()
    assert boom.calls == 3               # detached after 3 failures
    assert len(ms.find("tick")) == 10    # healthy sink got everything
    assert h.sink_errors >= 3
    # satellite: the detached sink is VISIBLE in the summary with its
    # strike count — not a mysteriously short stream
    detached = [s for s in health if s["state"] == "detached"]
    assert detached and detached[0]["type"] == "_BoomSink"
    assert detached[0]["strikes"] == 3
    assert any(s["state"] == "attached" and s["type"] == "MemorySink"
               for s in health)


def test_jsonl_sink_bad_path_never_blocks(tmp_path):
    """A JSONL sink whose file cannot open must swallow events (recording
    the error) without blocking or raising into the emitting thread."""
    bad = tmp_path / "iam_a_dir"
    bad.mkdir()
    sink = monitor.JsonlSink(str(bad), queue_size=32)  # open() will fail
    h = monitor.hub()
    h.enable(sink)
    try:
        t0 = time.perf_counter()
        for i in range(5000):
            monitor.event("flood", i=i)
        elapsed = time.perf_counter() - t0
    finally:
        h.disable()                      # joins the writer thread
    assert elapsed < 5.0                 # never blocked on the dead writer
    assert sink.error is not None


def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    sink = monitor.JsonlSink(path)
    h = monitor.hub()
    h.enable(sink)
    h.begin_pass(5)
    monitor.event("alpha", k=1)
    h.end_pass()
    h.disable()
    res = flight.validate_events_file(path)
    assert res["errors"] == []
    assert res["events"] >= 3            # pass_begin, alpha, flight record
    assert len(res["flight_records"]) == 1
    assert sink.error is None and sink.written >= 3


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

def test_prometheus_exposition_format():
    h = monitor.hub()
    monitor.counter_add("t.prom/count:er", 7)
    monitor.gauge_set("t.prom.gauge", 2.5)
    text = h.prometheus_text()
    lines = text.splitlines()
    # sanitized names, one TYPE line per metric, counter vs gauge kinds
    assert "# TYPE pbtpu_t_prom_count:er counter" in lines
    assert "pbtpu_t_prom_count:er 7" in lines
    assert "# TYPE pbtpu_t_prom_gauge gauge" in lines
    assert "pbtpu_t_prom_gauge 2.5" in lines
    # the doctor's alert series are ALWAYS exported (zero-filled when
    # untouched) so training/serving /metrics never gain or lose series
    assert "# TYPE pbtpu_exchange_overflow_retries counter" in lines
    assert "# TYPE pbtpu_tiering_hot_rows gauge" in lines
    assert "# TYPE pbtpu_tiering_hot_hit_rate gauge" in lines
    for line in lines:
        if not line or line.startswith("#"):
            continue
        name, val = line.rsplit(" ", 1)
        float(val)                       # every sample parses
        assert " " not in name


def test_training_metrics_endpoint_scrapes_alert_series():
    """The training-side /metrics twin of the serving endpoint: the
    doctor's alert series are scrapeable from a bare training process."""
    import urllib.request

    srv = monitor.start_metrics_endpoint(port=0)
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            body = r.read().decode()
        assert "pbtpu_exchange_overflow_retries" in body
        assert "pbtpu_tiering_hot_hit_rate" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=10)
    finally:
        srv.shutdown()
        srv.server_close()
        srv._pbtpu_thread.join(timeout=10)


# ---------------------------------------------------------------------------
# disabled-path cost (acceptance: no-op fast path)
# ---------------------------------------------------------------------------

def test_disabled_path_call_cost():
    h = monitor.hub()
    assert not h.enabled
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        monitor.event("noop", x=1)
    event_cost = (time.perf_counter() - t0) / n
    with monitor.span("noop"):        # the scope's lazy imports, once
        pass
    t0 = time.perf_counter()
    for _ in range(n):
        with monitor.span("noop"):
            pass
    span_cost = (time.perf_counter() - t0) / n
    # generous bounds (CI noise): the disabled event is one flag check,
    # the disabled span two and an inert profiler annotation (ISSUE 24:
    # about 1.2 us together) — micro-seconds, not tens of them
    assert event_cost < 5e-6, f"disabled event() costs {event_cost:.2e}s"
    assert span_cost < 5e-6, f"disabled span() costs {span_cost:.2e}s"


# ---------------------------------------------------------------------------
# profiler ring buffer (satellite: bounded span store)
# ---------------------------------------------------------------------------

def test_profiler_ring_buffer_caps_and_counts_drops():
    from paddlebox_tpu.config import flags, set_flags
    from paddlebox_tpu.utils import profiler as prof

    old = flags.profiler_max_events
    set_flags(profiler_max_events=16)
    try:
        prof.enable_profiler()
        for i in range(50):
            with prof.RecordEvent(f"s{i}"):
                pass
        evs = prof.profiler_events()
        assert len(evs) == 16
        assert prof.dropped_spans() == 34
        # oldest dropped, newest kept
        assert evs[-1]["name"] == "s49" and evs[0]["name"] == "s34"
    finally:
        prof.disable_profiler()
        set_flags(profiler_max_events=old)


def test_chrome_trace_has_pass_markers_and_tagged_spans(tmp_path):
    from paddlebox_tpu.utils import profiler as prof

    h = monitor.hub()
    prof.enable_profiler()
    try:
        h.begin_pass(77)
        mon_ctx.set_step(5)
        with monitor.span("tagged_work"):
            pass
        h.end_pass()
    finally:
        prof.disable_profiler()
    path = str(tmp_path / "trace.json")
    prof.export_chrome_trace(path)
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    instants = {e["name"] for e in evs if e["ph"] == "i"}
    assert {"pass_begin", "pass_end"} <= instants
    span = next(e for e in evs if e["name"] == "tagged_work")
    assert span["args"] == {"pass_id": 77, "step": 5}


# ---------------------------------------------------------------------------
# fs / faultpoint routing (satellite)
# ---------------------------------------------------------------------------

def test_commandfs_failure_routes_through_hub_counters():
    from paddlebox_tpu.utils.fs import CommandFS

    h = monitor.hub()
    ms = monitor.MemorySink()
    h.enable(ms)
    before_ex = STATS.get("fs.rm.exhausted")
    before_rt = STATS.get("fs.rm.retries")
    fs = CommandFS(rm="false {path}", retries=3, retry_backoff=0.0)
    try:
        with pytest.raises(RuntimeError, match="after 3 attempts"):
            fs.rm("/nonexistent/x")
    finally:
        h.disable()
    assert STATS.get("fs.rm.exhausted") == before_ex + 1
    assert STATS.get("fs.rm.retries") == before_rt + 2
    ev = ms.find("fs_exhausted")
    assert ev and ev[0]["fields"]["op"] == "rm"
    assert ev[0]["fields"]["attempts"] == 3


def test_faultpoint_trip_routes_through_hub():
    from paddlebox_tpu.utils import faultpoint

    h = monitor.hub()
    ms = monitor.MemorySink()
    h.enable(ms)
    before = STATS.get("faultpoint.trips")
    try:
        faultpoint.arm("pass_ckpt.pre_manifest", action="ioerror")
        with pytest.raises(faultpoint.FaultInjected):
            faultpoint.hit("pass_ckpt.pre_manifest")
    finally:
        faultpoint.disarm()
        h.disable()
    assert STATS.get("faultpoint.trips") == before + 1
    ev = ms.find("faultpoint_trip")
    assert ev and ev[0]["fields"]["point"] == "pass_ckpt.pre_manifest"
    assert ms.find("faultpoint_armed")


# ---------------------------------------------------------------------------
# nan guard (satellite: flags.check_nan_inf wiring)
# ---------------------------------------------------------------------------

def _tiny_trainer(tmp_path, nan_dump_dir=None, inject_inf=False):
    from paddlebox_tpu.data import DataFeedSchema
    from paddlebox_tpu.data.dataset import SlotDataset
    from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
    from paddlebox_tpu.models import DNNCTRModel
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.train import Trainer, TrainerConfig

    schema = DataFeedSchema.ctr(num_sparse=3, num_float=1, batch_size=8,
                                max_len=2)
    rng = np.random.default_rng(0)
    ds = SlotDataset(schema)
    lines = []
    for i in range(16):
        dense = "inf" if (inject_inf and i == 9) else f"{rng.random():.3f}"
        parts = [f"1 {int(rng.random() < 0.4)}", f"1 {dense}"]
        for s in range(3):
            parts.append(
                f"2 {rng.integers(1, 1000)} {rng.integers(1, 1000)}")
        lines.append(" ".join(parts))
    f = tmp_path / "part-0"
    f.write_text("\n".join(lines) + "\n")
    ds.set_filelist([str(f)])
    ds.load_into_memory(global_shuffle=False)
    store = HostEmbeddingStore(EmbeddingConfig(dim=4))
    model = DNNCTRModel(num_slots=3, emb_dim=4, dense_dim=1, hidden=(8,))
    tr = Trainer(model, store, schema, make_mesh(1),
                 TrainerConfig(global_batch_size=8, auc_buckets=1 << 8,
                               nan_dump_dir=nan_dump_dir))
    return tr, ds


def test_flags_check_nan_inf_trips_with_telemetry(tmp_path):
    from paddlebox_tpu.config import set_flags

    tr, ds = _tiny_trainer(tmp_path, nan_dump_dir=str(tmp_path / "dump"),
                           inject_inf=True)
    h = monitor.hub()
    ms = monitor.MemorySink()
    h.enable(ms)
    set_flags(check_nan_inf=True)
    try:
        with pytest.raises(FloatingPointError, match="non-finite leaves"):
            tr.train_pass(ds)
    finally:
        set_flags(check_nan_inf=False)
        h.disable()
    ev = ms.find("nan_guard")
    assert ev, "nan trip must emit a telemetry event"
    assert ev[0]["fields"]["n_bad"] >= 1
    assert any("loss" in p or "dense" in p or "labels" in p
               for p in ev[0]["fields"]["paths"])
    # the aborted pass closed its scope (no leak into the next pass)
    assert mon_ctx.current().pass_id is None
    # scope dump landed next to the error
    dumps = os.listdir(tmp_path / "dump")
    assert any(d.startswith("nan_step") for d in dumps)
    assert STATS.get("trainer.nan_trips") >= 1


# ---------------------------------------------------------------------------
# flight-record schema against a REAL 2-pass train on CPU (acceptance)
# ---------------------------------------------------------------------------

def test_two_pass_train_flight_records_and_schema(tmp_path):
    from paddlebox_tpu.fleet import BoxPS

    tr, ds = _tiny_trainer(tmp_path)
    box = BoxPS(tr.store)
    box.init_metric("auc", method="plain")
    h = monitor.hub()
    ms = monitor.MemorySink()
    jl = monitor.JsonlSink(str(tmp_path / "events.jsonl"))
    h.enable(ms, jl)
    try:
        for _ in range(2):
            box.begin_pass()
            out = tr.train_pass(ds, metrics=box.metrics)
            info = box.end_pass()
            assert info["flight_record"] is not None
    finally:
        h.disable()

    res = flight.validate_events_file(str(tmp_path / "events.jsonl"))
    assert res["errors"] == [], res["errors"][:10]
    flights = res["flight_records"]
    assert [f["pass_id"] for f in flights] == [1, 2]
    for fr in flights:
        assert fr["steps"] == 2 and fr["examples"] == 16
        assert fr["examples_per_sec"] > 0
        # stage split covers the trainer's stages
        assert {"read", "translate", "train", "auc",
                "drain"} <= set(fr["stage_seconds"])
        # per-pass sparse telemetry deltas
        assert fr["stats_delta"].get("trainer.tokens") == 2 * 8 * 6
        assert fr["stats_delta"].get("trainer.pull_bytes", 0) > 0
        # metric snapshot came from the registry
        assert "auc" in fr["metrics"] and "auc" in fr["metrics"]["auc"]
        assert fr["extra"]["loss_mean"] == pytest.approx(
            out["loss_mean"], abs=1.0)   # same field, last pass exact
        # pass-boundary account (ISSUE 12): wall + component split, the
        # critical-path attributor's input
        assert fr["extra"]["boundary_seconds"] >= 0
        split = fr["extra"]["boundary_split"]
        assert set(split) == {"build", "h2d", "spill_fault_in"}
        assert all(v >= 0 for v in split.values())
        assert split["build"] + split["h2d"] > 0
    # every event in the stream carries the tag keys; events emitted
    # while a pass was open carry its id
    with open(tmp_path / "events.jsonl") as f:
        recs = [json.loads(line) for line in f]
    in_pass = [r for r in recs if r.get("type") in ("span", "event")
               and r["name"] not in ("eval_pass",)]
    assert in_pass
    assert all(r["pass_id"] in (1, 2) for r in in_pass), (
        sorted({r["name"] for r in in_pass if r["pass_id"] is None}))
    # background threads contributed tagged events (the pack producer at
    # minimum — prefetch is on by default)
    assert any(t != "MainThread" for t in res["threads"]), res["threads"]


def test_flight_validator_rejects_bad_exchange_extras():
    """ISSUE 16: the adaptive-exchange identity extras are closed
    vocabularies — an off-vocabulary wire or topology is a schema error,
    not a silent dashboard mystery."""
    base = {"ts": 1.0, "type": "flight_record", "name": "pass",
            "pass_id": 1, "step": None, "phase": None, "thread": "t",
            "seconds": 1.0, "steps": 1, "examples": 1,
            "examples_per_sec": 1.0, "stage_seconds": {},
            "stats_delta": {}, "metrics": {}}
    for k, bad in (("exchange_wire", "fp64"),
                   ("exchange_wire_next", 8),
                   ("exchange_topology", "ring")):
        errs = flight.validate_flight_record(dict(base, extra={k: bad}))
        assert any(k in e for e in errs), (k, errs)
    ok = dict(base, extra={"exchange_wire": "f32",
                           "exchange_wire_next": "bf16",
                           "exchange_topology": "hier"})
    assert flight.validate_flight_record(ok) == []


def test_kernel_names_are_the_pallas_calls_own():
    """``names.KERNEL_NAMES`` is closed over the ``name=`` of every
    ``pallas_call`` in ``paddlebox_tpu/ops``: a device trace shows a
    kernel by that name and the benchmark's readers look for it."""
    import re
    from paddlebox_tpu.monitor import names
    ops = os.path.join(os.path.dirname(os.path.abspath(monitor.__file__)),
                       "..", "ops")
    found = set()
    for fname in os.listdir(ops):
        if fname.endswith(".py"):
            with open(os.path.join(ops, fname)) as f:
                found |= set(re.findall(r'name="(pbtpu_\w+)"', f.read()))
    assert found == set(names.KERNEL_NAMES)
    assert len(set(names.KERNEL_NAMES)) == len(names.KERNEL_NAMES)
    assert {"pbtpu_short_conv_fwd", "pbtpu_short_conv_bwd"} <= found


def test_device_scope_names_are_the_scopes_the_code_opens():
    """``names.DEVICE_SCOPE_NAMES`` is closed over every literal
    ``device_scope("...")`` in the package, as ``KERNEL_NAMES`` is over
    the kernels: the capture reader's and the benchmark's by-scope rows
    are those names and no other."""
    import re
    from paddlebox_tpu.monitor import names
    root = os.path.join(os.path.dirname(os.path.abspath(monitor.__file__)),
                        "..")
    opened = set()
    for dirpath, _, files in os.walk(root):
        for fname in files:
            if fname.endswith(".py"):
                with open(os.path.join(dirpath, fname)) as f:
                    opened |= set(re.findall(
                        r'device_scope\(\s*"(\w+)"', f.read()))
    assert opened == set(names.DEVICE_SCOPE_NAMES)
    assert len(set(names.DEVICE_SCOPE_NAMES)) == len(names.DEVICE_SCOPE_NAMES)
    assert names.DEVICE_SCOPE_PREFIX + "x" != names.ANNOTATION_PREFIX + "x"
