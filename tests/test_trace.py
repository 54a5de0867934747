"""World trace (ISSUE 15): cross-rank distributed tracing with a
clock-corrected merged timeline.

The acceptance bar: a merged Perfetto trace from a 2-rank run shows
ALIGNED timelines (injected skew recovered within tolerance) with
causal flow edges across the exchange and from the end_pass publish to
the serving swap — proven here — and tracing disabled costs one
enabled-check per scope (micro-test, same contract as the hub's
disabled event path). Every record the write side emits passes
``flight.validate_event``.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from paddlebox_tpu import monitor
from paddlebox_tpu.config import flags
from paddlebox_tpu.monitor import critical_path as cp_lib
from paddlebox_tpu.monitor import flight, names
from paddlebox_tpu.monitor import trace as trace_lib
from paddlebox_tpu.monitor.aggregate import EVIDENCE_EVENTS

TRACE_FLAGS = ("trace", "trace_sample_passes", "trace_run_id",
               "trace_device", "trace_device_dir")


@pytest.fixture(autouse=True)
def _clean(tmp_path):
    saved = {k: flags.get(k) for k in TRACE_FLAGS}
    h = monitor.hub()
    h.disable()
    h.abort_pass(reason="test setup")
    trace_lib.on_end_pass()
    trace_lib._SAW_PASS = False     # each test is its own "process"
    yield
    trace_lib.on_end_pass()
    trace_lib._SAW_PASS = False
    h.abort_pass(reason="test teardown")
    h.disable()
    for k, v in saved.items():
        flags.set(k, v)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _emit_rank_stream(dirpath, pass_id=1, steps=2):
    """One traced pass emitted through the REAL pipeline: JsonlSink +
    begin_pass + spans + exchange flow points + end_pass."""
    flags.set("trace", True)
    h = monitor.hub()
    h.enable(monitor.JsonlSink(os.path.join(dirpath, "events.jsonl")))
    h.begin_pass(pass_id, phase=1)
    assert trace_lib.active()
    for s in range(steps):
        monitor.context.set_step(s)
        with monitor.span("h2d_stage"):
            pass
        trace_lib.flow("exchange", f"p{pass_id}.s{s}",
                       wire="f32", tokens=64, bytes_bound=4096)
        with monitor.span("train_step"):
            time.sleep(0.001)
    h.record_train(stage_seconds={"read": 0.01}, steps=steps,
                   examples=steps * 64, seconds=0.01)
    h.end_pass()
    h.disable()
    return os.path.join(dirpath, "events.jsonl")


def _shift_stream(src_file, dst_dir, shift_s):
    """A second 'rank' = the first stream with every wall clock shifted
    (the injected skew): same records, skewed host."""
    os.makedirs(dst_dir, exist_ok=True)
    out = os.path.join(dst_dir, "events.jsonl")
    with open(src_file) as f, open(out, "w") as g:
        for line in f:
            rec = json.loads(line)
            if isinstance(rec.get("ts"), (int, float)):
                rec["ts"] = rec["ts"] + shift_s
            g.write(json.dumps(rec) + "\n")
    return out


def _append_probe(path, observer, peer, offset_s, rtt_s=0.01):
    rec = {"ts": time.time(), "type": "event",
           "name": "trace.clock_probe", "pass_id": None, "step": None,
           "phase": None, "thread": "hb",
           "fields": {"observer": observer, "peer": peer,
                      "offset_s": offset_s, "rtt_s": rtt_s}}
    assert flight.validate_event(rec) == []
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _pass_slices(trace, pid):
    return [e for e in trace["traceEvents"]
            if e.get("ph") == "X" and e.get("pid") == pid
            and str(e.get("name", "")).startswith("pass ")]


# ---------------------------------------------------------------------------
# the acceptance tests
# ---------------------------------------------------------------------------

def test_two_rank_merge_recovers_injected_skew(tmp_path):
    """2-rank merge: rank1 is rank0's stream with +5s of injected wall
    skew; a clock probe recovers the offset and the merged timelines
    ALIGN within tolerance (they are ~5s apart uncorrected)."""
    d0 = str(tmp_path / "rank0")
    os.makedirs(d0)
    f0 = _emit_rank_stream(d0)
    skew = 5.0
    _shift_stream(f0, str(tmp_path / "rank1"), skew)
    _append_probe(f0, observer=0, peer=1, offset_s=skew)

    merged = trace_lib.merge_roots([d0, str(tmp_path / "rank1")])
    summary = trace_lib.summarize(merged)
    assert summary["ranks"] == [0, 1]
    # the injected skew is recovered ~exactly (a single exact probe)
    assert abs(summary["clock_offsets_s"]["1"] - skew) < 1e-6
    assert summary["clock_corrected_ranks"] == [0, 1]
    p0, p1 = _pass_slices(merged, 0), _pass_slices(merged, 1)
    assert p0 and p1
    assert abs(p0[0]["ts"] - p1[0]["ts"]) < 0.05 * 1e6   # aligned

    # exchange flow edges present, cross-rank, ~zero latency corrected
    ex = [e for e in summary["flow_edges"] if e["kind"] == "exchange"]
    assert len(ex) == 2                      # one per step
    for e in ex:
        assert {e["src_rank"], e["dst_rank"]} == {0, 1}
        assert abs(e["latency_s"]) < 0.05
    # the chrome flow events pair s/f on shared ids
    s_ids = {e["id"] for e in merged["traceEvents"] if e.get("ph") == "s"}
    f_ids = {e["id"] for e in merged["traceEvents"] if e.get("ph") == "f"}
    assert s_ids and s_ids == f_ids

    # WITHOUT the probe, the same merge is ~5s misaligned — the
    # correction is real, not an artifact of the fixture
    raw = trace_lib.read_trace_records(d0)
    raw["clock_probes"] = []
    other = trace_lib.read_trace_records(str(tmp_path / "rank1"))
    uncorrected = trace_lib.merge_streams([raw, other], [0, 1])
    q0, q1 = _pass_slices(uncorrected, 0), _pass_slices(uncorrected, 1)
    assert abs(q0[0]["ts"] - q1[0]["ts"]) > 4.0 * 1e6


def test_every_emitted_record_passes_validate_event(tmp_path):
    d0 = str(tmp_path / "rank0")
    os.makedirs(d0)
    f0 = _emit_rank_stream(d0)
    out = flight.validate_events_file(f0)
    assert out["errors"] == []
    assert out["events"] > 0 and out["flight_records"]


def test_trace_ids_and_parent_links(tmp_path):
    """Span records carry their own span_id with a parent chain rooted
    at the pass; event records point at their enclosing span."""
    flags.set("trace", True)
    ms = monitor.MemorySink()
    h = monitor.hub()
    h.enable(ms)
    h.begin_pass(3)
    with monitor.span("train_pass"):
        with monitor.span("train_step"):
            monitor.event("nan_guard", n_bad=0)
    h.end_pass()
    by_name = {r["name"]: r for r in ms.records}
    outer, inner = by_name["train_pass"], by_name["train_step"]
    ev, fr = by_name["nan_guard"], by_name["pass"]
    tid = outer["trace_id"]
    assert tid and tid.endswith(":3")
    assert all(r.get("trace_id") == tid for r in (inner, ev, fr))
    assert inner["parent_span_id"] == outer["span_id"]
    assert ev["parent_span_id"] == inner["span_id"]
    assert fr["span_id"] == outer["parent_span_id"]  # the pass root
    assert fr["parent_span_id"] is None
    for r in ms.records:
        assert flight.validate_event(r) == []


def test_sampling_gates_whole_passes(tmp_path):
    flags.set("trace", True)
    flags.set("trace_sample_passes", 2)
    h = monitor.hub()
    ms = monitor.MemorySink()
    h.enable(ms)
    h.begin_pass(1)                  # 1 % 2 != 0 -> unsampled
    assert not trace_lib.active()
    with monitor.span("h2d_stage"):
        pass
    h.end_pass()
    h.begin_pass(2)                  # sampled
    assert trace_lib.active()
    with monitor.span("h2d_stage"):
        pass
    h.end_pass()
    spans = [r for r in ms.records if r["name"] == "h2d_stage"]
    assert len(spans) == 2
    assert "trace_id" not in spans[0]      # unsampled: no trace plane
    assert spans[1]["trace_id"].endswith(":2")


def test_disabled_cost_is_one_check():
    """Tracing off: flow() and the hub-record stamp cost one module-flag
    check — the same micro-contract as the hub's disabled event path."""
    assert not trace_lib.active()
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        trace_lib.flow("exchange", "p0.s0", wire="f32")
    cost = (time.perf_counter() - t0) / n
    assert cost < 5e-6, f"disabled flow() costs {cost:.2e}s"


# ---------------------------------------------------------------------------
# heartbeat clock probes (the real round trip, skew injected)
# ---------------------------------------------------------------------------

def test_heartbeat_roundtrip_emits_clock_probe_with_skew(tmp_path):
    from paddlebox_tpu.distributed.resilience import HeartbeatMonitor
    from paddlebox_tpu.distributed.store import FileStore
    st = FileStore(str(tmp_path), timeout_s=1.0)
    h = monitor.hub()
    ms = monitor.MemorySink()
    h.enable(ms)
    hb0 = HeartbeatMonitor(st, 0, 2, run_id="r", watch=False, start=False)
    hb1 = HeartbeatMonitor(st, 1, 2, run_id="r", watch=False, start=False)
    skew = 5.0
    hb1._wall = lambda: time.time() + skew     # rank1's host runs fast
    try:
        hb0.publish()                  # t0 leaves rank0
        hb1.scan()                     # rank1 observes it (t1, skewed)
        hb1.publish()                  # echo + t2 leave rank1
        hb0.scan()                     # rank0 closes the loop (t3)
    finally:
        hb0.close()
        hb1.close()
    probes = ms.find("trace.clock_probe")
    mine = [p for p in probes if (p["fields"] or {}).get("observer") == 0]
    assert mine, f"no probe from rank0 in {probes}"
    f = mine[-1]["fields"]
    assert f["peer"] == 1
    # the estimate recovers the injected skew within the store rtt
    assert abs(f["offset_s"] - skew) < 0.5
    assert f["rtt_s"] >= 0
    assert flight.validate_event(mine[-1]) == []


# ---------------------------------------------------------------------------
# publish -> serving swap (cross-process propagation through the donefile)
# ---------------------------------------------------------------------------

def test_publish_to_swap_flow_edge(tmp_path):
    """The full loop: a traced end_pass publishes (trace ids stamped
    into the donefile entry + a publish/src flow point), a serving
    process swaps it in (publish/dst flow point carrying the parent
    link), and the merged world trace shows the causal edge."""
    from test_train_e2e import synth_dataset, NUM_SLOTS
    from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
    from paddlebox_tpu.fleet import BoxPS, FleetUtil
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.serving import (DONEFILE, ServingPublisher,
                                       ServingServer)
    from paddlebox_tpu.train import Trainer, TrainerConfig

    flags.set("trace", True)
    ds, schema = synth_dataset(128)
    store = HostEmbeddingStore(EmbeddingConfig(dim=8, learning_rate=0.15))
    model = DeepFMModel(num_slots=NUM_SLOTS, emb_dim=8, dense_dim=1,
                        hidden=(16,))
    tr = Trainer(model, store, schema, make_mesh(1),
                 TrainerConfig(global_batch_size=64, dense_lr=3e-3))
    box = BoxPS(store)
    root = str(tmp_path / "serve")
    pub = ServingPublisher(root, model, schema, quant="f32", hot_top_k=8)

    d_train = str(tmp_path / "rank0")
    h = monitor.hub()
    h.enable(monitor.JsonlSink(os.path.join(d_train, "events.jsonl")))
    box.begin_pass()
    tr.train_pass(ds)
    out = box.end_pass(trainer=tr, publisher=pub)
    assert out["publish"]["announced"]
    h.disable()

    # the donefile entry carries the publish span's trace context
    entry = FleetUtil(root).latest(DONEFILE)
    assert isinstance(entry.get("trace"), dict)
    assert entry["trace"]["trace_id"] and entry["trace"]["span_id"]

    # serving side: its own telemetry stream (a second "rank")
    d_serve = str(tmp_path / "rank1")
    h.enable(monitor.JsonlSink(os.path.join(d_serve, "events.jsonl")))
    srv = ServingServer(root, poll_s=0.05)
    assert srv.poll_once() == 1
    h.disable()

    merged = trace_lib.merge_roots([d_train, d_serve])
    summary = trace_lib.summarize(merged)
    pub_edges = [e for e in summary["flow_edges"]
                 if e["kind"] == "publish"]
    assert pub_edges, f"no publish edge in {summary['flow_edges']}"
    e = pub_edges[0]
    assert e["key"] == "v1"
    assert e["src_rank"] == 0 and e["dst_rank"] == 1
    assert e["latency_s"] >= 0
    # the swap-side point carries the explicit parent link back to the
    # publish span that produced the version
    assert e["fields"]["parent_span_id"] == entry["trace"]["span_id"]
    assert e["fields"]["parent_trace_id"] == entry["trace"]["trace_id"]
    # both streams stay schema-clean end to end
    for d in (d_train, d_serve):
        out = flight.validate_events_file(os.path.join(d, "events.jsonl"))
        assert out["errors"] == []


def test_request_spans_parent_linked_across_processes(tmp_path):
    """ISSUE 19 acceptance: a REAL serving process (subprocess, own hub
    + JsonlSink + standing serving scope) serves a version this process
    published under a traced pass; the merged world trace contains
    request-level ``serve/score`` spans parent-linked — through the
    donefile-carried publish ids — to the publish span, across the
    process boundary. One timeline: train pass -> publish -> swap ->
    requests."""
    import subprocess
    import sys

    from test_train_e2e import synth_dataset, NUM_SLOTS
    from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
    from paddlebox_tpu.fleet import BoxPS, FleetUtil
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.serving import DONEFILE, ServingPublisher
    from paddlebox_tpu.train import Trainer, TrainerConfig

    flags.set("trace", True)
    ds, schema = synth_dataset(128)
    store = HostEmbeddingStore(EmbeddingConfig(dim=8, learning_rate=0.15))
    model = DeepFMModel(num_slots=NUM_SLOTS, emb_dim=8, dense_dim=1,
                        hidden=(16,))
    tr = Trainer(model, store, schema, make_mesh(1),
                 TrainerConfig(global_batch_size=64, dense_lr=3e-3))
    box = BoxPS(store)
    root = str(tmp_path / "serve")
    pub = ServingPublisher(root, model, schema, quant="f32", hot_top_k=8)

    d_train = str(tmp_path / "rank0")
    h = monitor.hub()
    h.enable(monitor.JsonlSink(os.path.join(d_train, "events.jsonl")))
    box.begin_pass()
    tr.train_pass(ds)
    assert box.end_pass(trainer=tr, publisher=pub)["publish"]["announced"]
    h.disable()
    entry = FleetUtil(root).latest(DONEFILE)
    assert isinstance(entry.get("trace"), dict)

    # the serving process: fresh interpreter, request tracing sampled at
    # every batch, serving telemetry to its own "rank" directory
    d_serve = str(tmp_path / "rank1")
    os.makedirs(d_serve)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PBTPU_TRACE="1",
               PBTPU_SERVING_TRACE_SAMPLE="1")
    env.pop("PBTPU_FAULTPOINT", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "tests", "serving_obs_worker.py"),
         root, d_serve, "--requests", "16"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["version"] == 1 and out["served"] >= 16

    # the serving stream carries sampled request spans whose payload
    # parent ids are EXACTLY the donefile-carried publish ids
    records = [json.loads(ln) for ln in
               open(os.path.join(d_serve, "events.jsonl"))]
    score_spans = [r for r in records if r.get("name") == "serve/score"]
    assert score_spans, "no sampled serve/score span in the stream"
    for r in score_spans:
        assert r["fields"]["parent_span_id"] == entry["trace"]["span_id"]
        assert r["fields"]["parent_trace_id"] == entry["trace"]["trace_id"]
    assert any(r.get("name") == "serve/wait" for r in records)
    assert any(r.get("type") == "serving_record" for r in records)

    # the merged world trace draws the cross-process parent link: the
    # publish span lives in the TRAINER's stream, the request spans in
    # the serving process's — linked via the propagated ids
    merged = trace_lib.merge_roots([d_train, d_serve])
    summary = trace_lib.summarize(merged)
    assert summary["linked_spans"] >= 1
    assert summary["linked_edges"] >= 1
    pub_edges = [e for e in summary["flow_edges"]
                 if e["kind"] == "publish"]
    assert pub_edges and pub_edges[0]["dst_rank"] == 1
    # both streams schema-clean end to end (the serving record included)
    for d in (d_train, d_serve):
        res = flight.validate_events_file(os.path.join(d, "events.jsonl"))
        assert res["errors"] == []


# ---------------------------------------------------------------------------
# CLI + doctor integration
# ---------------------------------------------------------------------------

def test_trace_cli_writes_perfetto_json(tmp_path, capsys):
    d0 = str(tmp_path / "rank0")
    os.makedirs(d0)
    f0 = _emit_rank_stream(d0)
    _shift_stream(f0, str(tmp_path / "rank1"), 2.0)
    _append_probe(f0, observer=0, peer=1, offset_s=2.0)
    out = str(tmp_path / "world_trace.json")
    rc = trace_lib.main([d0, str(tmp_path / "rank1"), "-o", out,
                         "--json"])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["out"] == out
    assert abs(summary["clock_offsets_s"]["1"] - 2.0) < 1e-6
    with open(out) as f:
        trace = json.load(f)
    phs = {e.get("ph") for e in trace["traceEvents"]}
    assert {"X", "M", "s", "f"} <= phs


def test_trace_cli_refuses_empty_inputs(tmp_path, capsys):
    d = tmp_path / "empty"
    d.mkdir()
    (d / "events.jsonl").write_text("")
    assert trace_lib.main([str(d)]) == 2
    assert trace_lib.main([]) == 2


def _write_doctor_world(tmp_path, latency_s):
    """Two synthetic rank streams whose publish flow edge takes
    ``latency_s`` against a 10s pass wall."""
    t = time.time()
    fr = {"ts": t, "type": "flight_record", "name": "pass", "pass_id": 1,
          "step": None, "phase": 1, "thread": "Main", "seconds": 10.0,
          "train_seconds": 6.0, "steps": 8, "examples": 1024,
          "examples_per_sec": 102.4,
          "stage_seconds": {"train": 6.0}, "stats_delta": {},
          "metrics": {}, "owner": "box"}
    assert flight.validate_flight_record(fr) == []

    def flow_rec(ts, role):
        return {"ts": ts, "type": "flow", "name": "trace.flow",
                "pass_id": 1, "step": None, "phase": None, "thread": "M",
                "fields": {"kind": "publish", "key": "v9", "role": role}}
    d0, d1 = tmp_path / "rank0", tmp_path / "rank1"
    d0.mkdir(), d1.mkdir()
    (d0 / "events.jsonl").write_text(
        json.dumps(fr) + "\n" + json.dumps(flow_rec(t, "src")) + "\n")
    (d1 / "events.jsonl").write_text(
        json.dumps(flow_rec(t + latency_s, "dst")) + "\n")
    return str(d0), str(d1)


def test_doctor_cli_reports_cross_rank_flow(tmp_path, capsys):
    from paddlebox_tpu.monitor import doctor
    d0, d1 = _write_doctor_world(tmp_path, latency_s=4.0)  # 40% of wall
    assert doctor.main([d0, d1, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["world_trace"]["flow_edges"]
    status = {r["rule"]: r["status"] for r in rep["rules"]}
    assert status["cross-rank-flow"] == "fired"
    f = next(f for f in rep["findings"] if f["rule"] == "cross-rank-flow")
    assert f["evidence"]["longest_edge"]["kind"] == "publish"
    assert f["evidence"]["longest_edge"]["latency_s"] == pytest.approx(
        4.0, abs=0.01)
    # --fail-on: the CI gate exits 1 on a warn-or-worse finding
    assert doctor.main([d0, d1, "--json", "--fail-on", "warn"]) == 1
    capsys.readouterr()
    assert doctor.main([d0, d1, "--json", "--fail-on", "critical"]) == 0
    capsys.readouterr()
    assert doctor.main(["--fail-on", "bogus", d0]) == 2


def test_doctor_cli_quiet_without_trace_records(tmp_path, capsys):
    """A stream with no trace plane: the rule is no-data, never an
    error, and the report has no world_trace key."""
    from paddlebox_tpu.monitor import doctor
    d0, _ = _write_doctor_world(tmp_path, latency_s=0.0)
    # strip the flow records: keep only the flight record
    p = os.path.join(d0, "events.jsonl")
    lines = [ln for ln in open(p) if "trace.flow" not in ln]
    open(p, "w").writelines(lines)
    assert doctor.main([d0, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert "world_trace" not in rep
    status = {r["rule"]: r["status"] for r in rep["rules"]}
    assert status["cross-rank-flow"] == "no-data"


# ---------------------------------------------------------------------------
# attribution + satellites
# ---------------------------------------------------------------------------

def test_attribute_flow_edges_names_longest():
    edges = [
        {"kind": "exchange", "key": "p1.s0", "src_rank": 0,
         "dst_rank": 1, "latency_s": 0.2},
        {"kind": "publish", "key": "v3", "src_rank": 0, "dst_rank": 2,
         "latency_s": 3.0},
        {"kind": "exchange", "key": "p1.s1", "src_rank": 1,
         "dst_rank": 0, "latency_s": -0.01},
    ]
    fa = cp_lib.attribute_flow_edges(edges, wall_seconds_mean=10.0)
    assert fa["edges"] == 3
    assert fa["longest"]["kind"] == "publish"
    assert fa["longest"]["dst_rank"] == 2
    assert fa["longest_share_of_wall"] == pytest.approx(0.3)
    assert fa["by_kind"]["exchange"]["count"] == 2
    assert fa["negative_edges"] == 1
    assert cp_lib.attribute_flow_edges([]) == {
        "edges": 0, "longest": None, "by_kind": {}}


def test_exchange_flow_fields_shape():
    from paddlebox_tpu.embedding import EmbeddingConfig
    from paddlebox_tpu.embedding import exchange
    f = exchange.flow_fields(EmbeddingConfig(dim=8), "bf16", 128)
    assert f["wire"] == "bf16" and f["tokens"] == 128
    assert isinstance(f["bytes_bound"], int) and f["bytes_bound"] > 0


def test_prometheus_exports_sink_health_gauges():
    h = monitor.hub()
    # zero-filled even with no sinks: an alert on the series is defined
    text = h.prometheus_text()
    assert "pbtpu_monitor_sinks_attached 0" in text
    assert "# TYPE pbtpu_monitor_sinks_unhealthy gauge" in text
    ms = monitor.MemorySink()
    h.enable(ms)
    ms.dropped = 7
    text = h.prometheus_text()
    assert "pbtpu_monitor_sinks_attached 1" in text
    assert "pbtpu_monitor_sink_dropped_events 7" in text
    assert "pbtpu_monitor_sinks_unhealthy 1" in text


def test_event_name_registry_is_closed_and_consistent():
    assert len(set(names.EVENT_NAMES)) == len(names.EVENT_NAMES)
    assert len(set(names.SPAN_NAMES)) == len(names.SPAN_NAMES)
    # every evidence event the aggregator retains is a registered name
    assert set(EVIDENCE_EVENTS) <= set(names.EVENT_NAMES)
    for n in ("trace.flow", "trace.clock_probe", "trace.device_capture",
              "serving_swap", "pass_begin"):
        assert names.is_registered(n)
    for n in ("h2d_stage", "train_step", "publish"):
        assert n in names.SPAN_NAMES
    assert not names.is_registered("totally_made_up")


def test_ensure_service_never_clobbers_a_training_process():
    """Co-located publisher+server: once a process has opened ANY pass
    scope, the pass lifecycle owns the trace window — a serving poll
    must not re-activate tracing inside an unsampled pass or between
    passes (the review-found sampling-clobber hazard)."""
    flags.set("trace", True)
    flags.set("trace_sample_passes", 2)
    h = monitor.hub()
    h.enable(monitor.MemorySink())
    h.begin_pass(1)                       # unsampled (1 % 2 != 0)
    assert not trace_lib.active()
    assert trace_lib.ensure_service("serving") is False
    assert not trace_lib.active()         # sampling decision intact
    h.end_pass()
    assert trace_lib.ensure_service("serving") is False
    assert not trace_lib.active()         # between passes too
    # a fresh pass-less process (fixture resets the latch) activates
    trace_lib._SAW_PASS = False
    assert trace_lib.ensure_service("serving") is True
    assert trace_lib.active()


def test_flow_propagated_pairs_under_producer_run(tmp_path):
    """A serving host with DEFAULT flags (no local trace scope, no
    matching trace_run_id) still lands the publish->swap edge: the
    donefile-carried parent ids activate the dst point and the merger
    pairs it under the PRODUCER's run prefix."""
    h = monitor.hub()
    ms = monitor.MemorySink()
    h.enable(ms)
    # producer side: traced pass under run id "jobA"
    flags.set("trace", True)
    flags.set("trace_run_id", "jobA")
    h.begin_pass(5)
    trace_lib.flow("publish", "v7", role="src")
    h.end_pass()
    # consumer side: tracing OFF locally, only the propagated parent
    flags.set("trace", False)
    trace_lib._SAW_PASS = False
    assert not trace_lib.active()
    trace_lib.flow_propagated("publish", "v7", "dst",
                              {"trace_id": "jobA:5", "span_id": "s-9"},
                              swap_pause_ms=0.1)
    # no parent + no local scope -> no-op (an untraced run stays silent)
    trace_lib.flow_propagated("publish", "v8", "dst", None)
    h.disable()
    flows = [r for r in ms.records if r.get("name") == "trace.flow"]
    assert len(flows) == 2                 # v8 never emitted
    stream = trace_lib.records_to_stream(ms.records)
    summary = trace_lib.summarize(trace_lib.merge_streams([stream], [0]))
    edges = [e for e in summary["flow_edges"] if e["kind"] == "publish"]
    assert len(edges) == 1 and edges[0]["key"] == "v7"
    assert edges[0]["fields"]["parent_span_id"] == "s-9"


def test_ntp_offset_math():
    # observer clock = 0-based; peer clock = observer + 3; delay 0.1 each way
    t0 = 100.0
    t1 = (t0 + 0.1) + 3.0        # peer reads after 0.1s, peer clock
    t2 = t1 + 0.05               # peer publishes echo 0.05s later
    t3 = (t2 - 3.0) + 0.1        # observer reads 0.1s after, its clock
    off, rtt = trace_lib.ntp_offset(t0, t1, t2, t3)
    assert off == pytest.approx(3.0, abs=1e-9)
    assert rtt == pytest.approx(0.2, abs=1e-9)
