"""Run doctor (ISSUE 12): every rule has a fire + quiet fixture, the
2-rank aggregation golden (skew + straggler naming), critical-path
attribution, JSONL rotation (schema-clean segments picked up in order),
the telemetry.rotate.pre fault window, live mode, and the CLI."""

from __future__ import annotations

import json
import os
import time

import pytest

from paddlebox_tpu import monitor
from paddlebox_tpu.monitor import aggregate as agg_lib
from paddlebox_tpu.monitor import critical_path as cp_lib
from paddlebox_tpu.monitor import doctor, flight
from paddlebox_tpu.monitor.registry import STATS
from paddlebox_tpu.utils import faultpoint


@pytest.fixture(autouse=True)
def _clean_hub():
    h = monitor.hub()
    h.disable()
    h.abort_pass(reason="test setup")
    yield
    h.abort_pass(reason="test teardown")
    h.disable()


# ---------------------------------------------------------------------------
# synthetic flight records
# ---------------------------------------------------------------------------

def make_flight(pass_id, seconds=10.0, train=6.0, read=0.5, auc=0.2,
                drain=0.1, boundary=0.5, split=None, stats=None,
                **extra):
    rec = {
        "ts": time.time(), "type": "flight_record", "name": "pass",
        "pass_id": pass_id, "step": None, "phase": 1, "thread": "Main",
        "seconds": seconds, "train_seconds": train, "steps": 8,
        "examples": 1024, "examples_per_sec": 1024 / seconds,
        "stage_seconds": {"read": read, "train": train, "auc": auc,
                          "drain": drain, "translate": 0.3},
        "stats_delta": dict(stats or {}),
        "metrics": {}, "owner": "box",
        "extra": dict({"boundary_seconds": boundary,
                       "boundary_split": split or
                       {"build": boundary * 0.6, "h2d": boundary * 0.4,
                        "spill_fault_in": 0.0}}, **extra),
    }
    assert flight.validate_flight_record(rec) == []
    return rec


def make_serving_window(ts, requests=100, failures=0, swaps=0,
                        version_lag=0, slo_ms=50.0, p50_ms=3.0,
                        p99_ms=8.0, versions=None, **extra):
    """One schema-valid serving window record (ISSUE 19) — the serving
    plane's make_flight. The doctor flattens ``fields``; fixtures pass
    full records so every synthetic window also exercises the schema."""
    rec = {
        "ts": float(ts), "type": "serving_record",
        "name": "serving_window", "pass_id": None, "step": None,
        "phase": -1, "thread": "serving",
        "fields": dict({"window_s": 30.0, "requests": requests,
                        "failures": failures, "swaps": swaps,
                        "version_lag": version_lag, "slo_ms": slo_ms,
                        "p50_ms": p50_ms, "p99_ms": p99_ms,
                        "versions": versions or {}}, **extra),
    }
    assert flight.validate_serving_record(rec) == []
    return rec


def make_fleet_window(ts, replicas=2, healthy=2, quarantined=0,
                      requests=500, sheds=0, retries=0, hedges=0,
                      hedges_won=0, restarts=0, promote_holds=0,
                      p50_ms=2.0, p99_ms=8.0, **extra):
    """One schema-valid fleet window record (ISSUE 20) — the replica
    fleet's make_serving_window."""
    rec = {
        "ts": float(ts), "type": "fleet_record", "name": "fleet_window",
        "pass_id": None, "step": None, "phase": -1, "thread": "fleet",
        "fields": dict({"window_s": 10.0, "replicas": replicas,
                        "healthy": healthy, "quarantined": quarantined,
                        "requests": requests, "sheds": sheds,
                        "retries": retries, "hedges": hedges,
                        "hedges_won": hedges_won, "restarts": restarts,
                        "promote_holds": promote_holds, "p50_ms": p50_ms,
                        "p99_ms": p99_ms}, **extra),
    }
    assert flight.validate_fleet_record(rec) == []
    return rec


# Per-rule (fire_kwargs, quiet_kwargs) for doctor.diagnose — the
# closed-registry discipline: a new rule cannot ship without BOTH a
# firing and a quiet synthetic fixture registered here (the coverage
# test below parametrizes over doctor.ALL_RULES).
RULE_FIXTURES: dict = {
    "boundary-wall": (
        dict(flights=[make_flight(1, seconds=10.0, train=4.0,
                                  boundary=4.0)]),
        dict(flights=[make_flight(1, seconds=10.0, train=8.0,
                                  boundary=0.5)]),
    ),
    "exchange-overflow": (
        dict(flights=[
            make_flight(1, stats={"exchange.tokens": 1000,
                                  "exchange.overflow_retries": 2}),
            make_flight(2, stats={"exchange.tokens": 1000,
                                  "exchange.overflow_retries": 3,
                                  "exchange.overflow_dropped": 40})]),
        dict(flights=[make_flight(1, stats={"exchange.tokens": 1000}),
                      make_flight(2, stats={"exchange.tokens": 1000})]),
    ),
    "spill-thrash": (
        dict(flights=[
            make_flight(1, stats={"spill.cache_hits": 900,
                                  "spill.cache_misses": 100}),
            make_flight(2, stats={"spill.cache_hits": 200,
                                  "spill.cache_misses": 800,
                                  "tiering.admitted": 500,
                                  "tiering.evicted": 490})]),
        dict(flights=[
            make_flight(1, stats={"spill.cache_hits": 900,
                                  "spill.cache_misses": 100}),
            make_flight(2, stats={"spill.cache_hits": 880,
                                  "spill.cache_misses": 120,
                                  "tiering.admitted": 50,
                                  "tiering.evicted": 5})]),
    ),
    "dedup-drift": (
        dict(flights=[
            make_flight(1, stats={"exchange.tokens": 1000,
                                  "exchange.unique_lanes": 800}),
            make_flight(2, stats={"exchange.tokens": 1000,
                                  "exchange.unique_lanes": 400})]),
        dict(flights=[
            make_flight(1, stats={"exchange.tokens": 1000,
                                  "exchange.unique_lanes": 800}),
            make_flight(2, stats={"exchange.tokens": 1000,
                                  "exchange.unique_lanes": 780})]),
    ),
    "nan-guard": (
        dict(flights=[make_flight(1, stats={"trainer.nan_trips": 1})],
             evidence={"nan_guard": [{
                 "name": "nan_guard", "pass_id": 1, "step": 7,
                 "fields": {"n_bad": 2, "paths": ["loss"]}}]}),
        dict(flights=[make_flight(1)]),
    ),
    "serving-staleness": (
        dict(flights=[make_flight(
            1, stats={"serving.publishes": 1,
                      "serving.publish_failures": 1})]),
        dict(flights=[make_flight(
            1, stats={"serving.publishes": 1, "serving.pass_lag": 0})]),
    ),
    "heartbeat-gap": (
        dict(counters={"resilience.peer_lost": 1},
             evidence={"peer_lost": [{
                 "name": "peer_lost",
                 "fields": {"rank": 3, "observer": 0,
                            "after_s": 30.0}}]}),
        dict(counters={"resilience.peer_lost": 0}),
    ),
    "sink-health": (
        dict(sink_health=[{"type": "JsonlSink", "state": "detached",
                           "strikes": 3, "dropped": 120,
                           "error": "OSError(28, 'No space left')"}]),
        dict(sink_health=[{"type": "JsonlSink", "state": "attached",
                           "strikes": 0, "dropped": 0, "written": 99}]),
    ),
    "cross-rank-flow": (
        # longest edge 4s against a 10s mean pass wall = 40% — fired;
        # quiet: the same edge at 0.1s (1%)
        dict(flights=[make_flight(1, seconds=10.0)],
             detail={"world_trace": {
                 "flow_edges": [
                     {"kind": "exchange", "key": "p1.s3",
                      "src_rank": 0, "dst_rank": 1, "latency_s": 4.0,
                      "fields": {"wire": "bf16"}},
                     {"kind": "publish", "key": "v7", "src_rank": 0,
                      "dst_rank": 2, "latency_s": 0.5, "fields": {}}],
                 "clock_offsets_s": {"0": 0.0, "1": 1.25}}}),
        dict(flights=[make_flight(1, seconds=10.0)],
             detail={"world_trace": {
                 "flow_edges": [
                     {"kind": "exchange", "key": "p1.s3",
                      "src_rank": 0, "dst_rank": 1, "latency_s": 0.1,
                      "fields": {}}],
                 "clock_offsets_s": {"0": 0.0, "1": 0.0}}}),
    ),
    "version-regression": (
        # candidate AUC 0.58 against stable 0.74 — far past the 0.005
        # margin; quiet: identical versions score identically
        dict(servings=[make_serving_window(
            100.0,
            versions={"1": {"role": "stable", "requests": 80,
                            "auc": 0.74, "score_mean": 0.21},
                      "2": {"role": "candidate", "requests": 80,
                            "auc": 0.58, "score_mean": 0.34,
                            "score_kl": 0.8}})]),
        dict(servings=[make_serving_window(
            100.0,
            versions={"1": {"role": "stable", "requests": 80,
                            "auc": 0.74, "score_mean": 0.21},
                      "2": {"role": "candidate", "requests": 80,
                            "auc": 0.74, "score_mean": 0.21,
                            "score_kl": 0.01}})]),
    ),
    "p99-burn": (
        # 3 of 4 recent windows (incl. the latest) breach the 50ms SLO;
        # quiet: same traffic, p99 comfortably under
        dict(servings=[
            make_serving_window(100.0, p99_ms=12.0),
            make_serving_window(130.0, p99_ms=72.0),
            make_serving_window(160.0, p99_ms=65.0),
            make_serving_window(190.0, p99_ms=80.0)]),
        dict(servings=[
            make_serving_window(100.0, p99_ms=12.0),
            make_serving_window(130.0, p99_ms=72.0),
            make_serving_window(160.0, p99_ms=11.0),
            make_serving_window(190.0, p99_ms=13.0)]),
    ),
    "swap-regression": (
        # the swap window's p99 steps 6ms -> 40ms (> 1.5x and > +1ms);
        # quiet: a swap whose window holds the pre-swap latency
        dict(servings=[
            make_serving_window(100.0, p99_ms=6.0),
            make_serving_window(130.0, p99_ms=40.0, swaps=1,
                                active_version=7)]),
        dict(servings=[
            make_serving_window(100.0, p99_ms=6.0),
            make_serving_window(130.0, p99_ms=6.5, swaps=1,
                                active_version=7)]),
    ),
    "fleet-degraded": (
        # one replica out of rotation after a crash-loop quarantine;
        # quiet: full fleet, no sheds, no promotion holds
        dict(fleets=[make_fleet_window(
            100.0, healthy=1, quarantined=1, restarts=4, retries=3)]),
        dict(fleets=[make_fleet_window(100.0)]),
    ),
}


@pytest.mark.parametrize("rule_cls", doctor.ALL_RULES,
                         ids=[r.id for r in doctor.ALL_RULES])
def test_every_rule_fires_and_stays_quiet(rule_cls):
    assert rule_cls.id in RULE_FIXTURES, (
        f"rule {rule_cls.id!r} shipped without fire+quiet fixtures — "
        "register them in RULE_FIXTURES")
    assert rule_cls.incident, "every rule must cite its prior incident"
    fire_kw, quiet_kw = RULE_FIXTURES[rule_cls.id]

    rep = doctor.diagnose(**fire_kw)
    assert doctor.validate_report(rep) == []
    status = {r["rule"]: r["status"] for r in rep["rules"]}
    assert status[rule_cls.id] == "fired", (rule_cls.id, status)
    finding = next(f for f in rep["findings"]
                   if f["rule"] == rule_cls.id)
    # a finding is NAMED and carries evidence + a suggestion — never a
    # bare boolean
    assert finding["severity"] in ("critical", "warn", "info")
    assert finding["summary"] and finding["suggestion"]
    assert isinstance(finding["evidence"], dict) and finding["evidence"]

    rep_q = doctor.diagnose(**quiet_kw)
    status_q = {r["rule"]: r["status"] for r in rep_q["rules"]}
    assert status_q[rule_cls.id] == "quiet", (rule_cls.id, status_q)
    assert all(f["rule"] != rule_cls.id for f in rep_q["findings"])


def test_quarantined_rule_downgrades_to_info_and_is_surfaced():
    """ISSUE 20 satellite (remediation-history feedback): a rule whose
    applied remediation the parity guard reverted still REPORTS its
    symptom, but as info with the discredited suggestion suppressed —
    and the report names the quarantined rule ids."""
    fire_kw, _ = RULE_FIXTURES["fleet-degraded"]
    rep = doctor.diagnose(**fire_kw,
                          quarantined_rules=["fleet-degraded"])
    assert doctor.validate_report(rep) == []
    assert rep["quarantined_rules"] == ["fleet-degraded"]
    f = next(f for f in rep["findings"] if f["rule"] == "fleet-degraded")
    assert f["severity"] == "info"              # symptom stays visible,
    assert "suggestion suppressed" in f["suggestion"]   # advice doesn't
    assert "original:" in f["suggestion"]       # ...but stays auditable
    # the SAME evidence un-quarantined is actionable (warn)
    rep2 = doctor.diagnose(**fire_kw)
    f2 = next(f for f in rep2["findings"]
              if f["rule"] == "fleet-degraded")
    assert f2["severity"] == "warn"
    assert "quarantined_rules" not in rep2


@pytest.mark.parametrize("window,severity", [
    (dict(promote_holds=1), "warn"),            # a full fleet that held
    (dict(requests=90, sheds=10), "warn"),      # shed rate over 1 %
    (dict(healthy=0), "critical"),              # nobody left to route to
])
def test_fleet_degraded_fires_on_each_kind_of_evidence(window, severity):
    rep = doctor.diagnose(fleets=[make_fleet_window(100.0, **window)])
    assert doctor.validate_report(rep) == []
    f = next(f for f in rep["findings"] if f["rule"] == "fleet-degraded")
    assert f["severity"] == severity
    for key, value in window.items():
        assert f["evidence"][key] == value


def test_doctor_report_verdict_and_severity_order():
    rep = doctor.diagnose(**RULE_FIXTURES["nan-guard"][0])
    assert rep["verdict"] == "findings:1"
    # critical findings sort first when several fire
    fire = dict(RULE_FIXTURES["boundary-wall"][0])
    fire["evidence"] = RULE_FIXTURES["heartbeat-gap"][0]["evidence"]
    fire["counters"] = RULE_FIXTURES["heartbeat-gap"][0]["counters"]
    rep2 = doctor.diagnose(**fire)
    assert [f["severity"] for f in rep2["findings"]] == \
        sorted([f["severity"] for f in rep2["findings"]],
               key=lambda s: {"critical": 0, "warn": 1}.get(s, 9))
    assert rep2["findings"][0]["rule"] == "heartbeat-gap"


def test_serving_staleness_does_not_double_count_failures():
    """The CLI hands diagnose() counters that ARE the summed per-pass
    deltas — seeding from the counter and adding the deltas again would
    report every failure twice (review finding)."""
    flights = [make_flight(
        1, stats={"serving.publishes": 1, "serving.publish_failures": 1})]
    rep = doctor.diagnose(
        flights=flights,
        counters={"serving.publishes": 1, "serving.publish_failures": 1})
    f = next(f for f in rep["findings"]
             if f["rule"] == "serving-staleness")
    assert f["evidence"]["publish_failures"] == 1
    assert "1 failed publish(es)" in f["summary"]


def test_serving_staleness_fires_on_gradual_gauge_growth():
    """pass_lag grows by 1 every pass: the per-pass DELTAS are all 1.0,
    but the absolute gauge after 4 passes is 4 — the rule must
    reconstruct the running value, not max the deltas (review
    finding: gradual staleness could never fire)."""
    flights = [make_flight(p, stats={"serving.publishes": 1,
                                     "serving.pass_lag": 1.0})
               for p in range(1, 5)]
    rep = doctor.diagnose(flights=flights)
    f = next(f for f in rep["findings"]
             if f["rule"] == "serving-staleness")
    assert f["evidence"]["pass_lag"] == 4.0


def test_record_train_accumulates_boundary_across_phases():
    """Phased programs run several train_passes per pass: the boundary
    account must SUM like stage_seconds (review finding: last-write-wins
    extras kept only the cheap second-phase rebuild)."""
    h = monitor.hub()
    h.begin_pass(41)
    h.record_train(steps=1, examples=8, seconds=1.0,
                   boundary_seconds=40.0,
                   boundary_split={"build": 30.0, "h2d": 10.0,
                                   "spill_fault_in": 0.0})
    h.record_train(steps=1, examples=8, seconds=1.0,
                   boundary_seconds=0.2,
                   boundary_split={"build": 0.1, "h2d": 0.1,
                                   "spill_fault_in": 0.0})
    rec = h.end_pass()
    assert rec["extra"]["boundary_seconds"] == pytest.approx(40.2)
    assert rec["extra"]["boundary_split"]["build"] == pytest.approx(30.1)
    assert flight.validate_flight_record(rec) == []


def test_world_view_reads_push_bytes_counter(tmp_path):
    """The exchange push-traffic counter is exchange.push_bytes —
    the world view must surface its imbalance (review finding: a
    mis-spelled key silently dropped the distribution)."""
    r0 = make_flight(1, stats={"exchange.tokens": 100,
                               "exchange.push_bytes": 1000})
    r1 = make_flight(1, seconds=12.0,
                     stats={"exchange.tokens": 100,
                            "exchange.push_bytes": 9000})
    _write_stream(str(tmp_path / "rank0"), [r0])
    _write_stream(str(tmp_path / "rank1"), [r1])
    world = agg_lib.aggregate([str(tmp_path / "rank0"),
                               str(tmp_path / "rank1")])
    dist = world["passes"][0]["exchange"]["push_bytes"]
    assert dist["max_rank"] == 1 and dist["max"] == 9000.0


def test_rule_verdicts_are_rank_order_independent():
    """pass_deltas sums across merged ranks' records per pass — a
    last-wins collapse made spill-thrash/dedup-drift depend on the
    order the rank roots were listed in (review finding)."""
    healthy = [make_flight(1, stats={"spill.cache_hits": 900,
                                     "spill.cache_misses": 100}),
               make_flight(2, stats={"spill.cache_hits": 900,
                                     "spill.cache_misses": 100})]
    collapsed = [make_flight(1, stats={"spill.cache_hits": 900,
                                       "spill.cache_misses": 100}),
                 make_flight(2, stats={"spill.cache_hits": 100,
                                       "spill.cache_misses": 900,
                                       "tiering.admitted": 500,
                                       "tiering.evicted": 490})]
    verdicts = set()
    for order in (healthy + collapsed, collapsed + healthy):
        rep = doctor.diagnose(flights=order)
        verdicts.add({r["rule"]: r["status"]
                      for r in rep["rules"]}["spill-thrash"])
    assert len(verdicts) == 1, verdicts


def test_heartbeat_rule_no_data_without_resilience_plane():
    """A single-host run with no heartbeat plane must read no-data, not
    'heartbeats checked, all healthy' (the no-data contract)."""
    rep = doctor.diagnose(flights=[make_flight(1)])
    status = {r["rule"]: r["status"] for r in rep["rules"]}
    assert status["heartbeat-gap"] == "no-data"


def test_sink_health_does_not_latch_on_cumulative_counter():
    """A recovered transient emit error leaves the process-cumulative
    monitor.sink_errors nonzero forever; the rule must stay quiet when
    this session's sinks are healthy (review finding)."""
    healthy = [{"type": "JsonlSink", "state": "attached", "strikes": 0,
                "dropped": 0, "written": 10}]
    rep = doctor.diagnose(counters={"monitor.sink_errors": 3},
                          sink_health=healthy)
    status = {r["rule"]: r["status"] for r in rep["rules"]}
    assert status["sink-health"] == "quiet"


# ---------------------------------------------------------------------------
# critical-path attribution
# ---------------------------------------------------------------------------

def test_attribution_limiter_trend_and_headroom():
    flights = [
        make_flight(1, seconds=10.0, train=6.0, boundary=2.0),
        make_flight(2, seconds=10.0, train=4.0, boundary=5.0),
    ]
    out = cp_lib.attribute_records(flights)
    p1, p2 = out["passes"]
    assert p1["limiter"] == "train" and p2["limiter"] == "boundary"
    assert p1["stages"]["boundary"] == 2.0
    assert p2["boundary_share"] == 0.5
    # headroom: the boundary can hide under train, bounded by both
    assert p1["overlap_headroom_seconds"] == 2.0
    assert p2["overlap_headroom_seconds"] == 4.0
    assert p1["boundary_split"]["build"] == pytest.approx(1.2)
    # translate is overlapped, never charged to the wall
    assert "translate" not in p1["stages"]
    assert p1["overlapped"]["translate"] == pytest.approx(0.3)
    s = out["summary"]
    assert s["limiter"] in ("train", "boundary")
    assert s["boundary_share_trend"] == "rising"
    assert s["boundary_share_per_pass"] == [0.2, 0.5]
    # coverage accounts the attributable stages against the wall
    assert 0.8 <= p1["coverage"] <= 1.0


def test_attribution_over_merged_ranks_is_order_independent():
    """Several ranks' records for one pass: the STRAGGLER's record is
    attributed regardless of listing order (review finding — last-wins
    made the report depend on CLI argument order)."""
    fast = make_flight(1, seconds=8.0, train=5.0)
    slow = make_flight(1, seconds=14.0, train=10.0)
    for order in ([fast, slow], [slow, fast]):
        out = cp_lib.attribute_records(order)
        assert out["passes"][0]["wall_seconds"] == 14.0
        assert out["passes"][0]["stages"]["train"] == 10.0


# ---------------------------------------------------------------------------
# 2-rank aggregation golden: skew + straggler naming
# ---------------------------------------------------------------------------

def _write_stream(dirpath, records):
    os.makedirs(dirpath, exist_ok=True)
    with open(os.path.join(dirpath, "events.jsonl"), "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def _golden_world(tmp_path, names=("rank0", "rank1")):
    r0 = make_flight(1, seconds=8.0, train=5.0, boundary=1.0,
                     stats={"exchange.tokens": 1000,
                            "exchange.unique_lanes": 700,
                            "exchange.pull_bytes": 4000})
    r0b = make_flight(2, seconds=8.2, train=5.1, boundary=1.1,
                      stats={"exchange.tokens": 1000,
                             "exchange.unique_lanes": 690,
                             "exchange.pull_bytes": 4100})
    # rank 1 is the straggler: 2x train time, more exchange traffic
    r1 = make_flight(1, seconds=14.0, train=10.0, boundary=1.2,
                     stats={"exchange.tokens": 1000,
                            "exchange.unique_lanes": 710,
                            "exchange.pull_bytes": 9000})
    _write_stream(str(tmp_path / names[0]), [r0, r0b])
    _write_stream(str(tmp_path / names[1]), [r1])   # pass 2 missing
    return [str(tmp_path / names[0]), str(tmp_path / names[1])]


def test_two_rank_aggregation_golden(tmp_path):
    roots = _golden_world(tmp_path)
    world = agg_lib.aggregate(roots)
    assert world["world_size"] == 2
    assert [r["rank"] for r in world["ranks"]] == [0, 1]
    p1, p2 = world["passes"]
    assert p1["pass_id"] == 1 and p1["ranks_reporting"] == 2
    assert p1["missing_ranks"] == []
    # straggler NAMED: rank 1 set the pass wall
    assert p1["straggler"] == 1
    assert p1["seconds"]["max_rank"] == 1
    assert p1["seconds"]["max"] == 14.0 and p1["seconds"]["min"] == 8.0
    assert p1["stage_skew"]["train"]["max_rank"] == 1
    assert p1["stage_skew"]["train"]["skew"] == pytest.approx(
        10.0 / 7.5, rel=1e-3)
    # exchange imbalance across shards is visible per pass
    assert p1["exchange"]["pull_bytes"]["max_rank"] == 1
    assert 0 < p1["exchange"]["dedup_ratio"]["mean"] < 1
    # a rank that never committed pass 2 is named missing — the
    # aggregation-level straggler/lost-rank signal
    assert p2["pass_id"] == 2 and p2["missing_ranks"] == [1]
    # cumulative counter view sums the deltas
    assert world["counters"]["exchange.pull_bytes"] == 4000 + 4100 + 9000


def test_aggregation_rank_names_follow_heartbeat_naming(tmp_path):
    """rank_names maps dense position -> ORIGINAL launcher rank, the
    HeartbeatMonitor convention — the straggler carries the original
    id."""
    roots = _golden_world(tmp_path, names=("a", "b"))
    world = agg_lib.aggregate(roots, rank_names=[4, 7])
    assert [r["rank"] for r in world["ranks"]] == [4, 7]
    assert world["passes"][0]["straggler"] == 7
    assert world["passes"][1]["missing_ranks"] == [7]
    # without rank_names, rankN dir basenames name the rank
    world2 = agg_lib.aggregate(_golden_world(tmp_path))
    assert world2["passes"][0]["straggler"] == 1


def test_doctor_over_world_names_straggler(tmp_path):
    roots = _golden_world(tmp_path)
    world = agg_lib.aggregate(roots)
    rep = doctor.diagnose(flights=world["flight_records"],
                          counters=world["counters"],
                          evidence=world["evidence"], world=world)
    assert doctor.validate_report(rep) == []
    assert rep["world"]["world_size"] == 2


def test_aggregation_reads_remote_roots(tmp_path):
    """hdfs://-schemed telemetry roots (the PR-5 remote layout) read
    through the registered CommandFS, segments and all."""
    from mockfs import register_mockfs

    root = tmp_path / "mock_root"
    (root / "rank0").mkdir(parents=True)
    _write_stream(str(root / "rank0"), [make_flight(1)])
    register_mockfs(str(root), scheme="mockdoc")
    st = agg_lib.read_stream("mockdoc://rank0")
    assert len(st["flight_records"]) == 1
    world = agg_lib.aggregate(["mockdoc://rank0"])
    assert world["passes"][0]["pass_id"] == 1


# ---------------------------------------------------------------------------
# JSONL rotation (satellite 1)
# ---------------------------------------------------------------------------

def test_jsonl_rotation_segments_schema_clean_and_ordered(tmp_path):
    path = str(tmp_path / "events.jsonl")
    sink = monitor.JsonlSink(path, rotate_mb=0.01)     # ~10KB segments
    h = monitor.hub()
    h.enable(sink)
    try:
        h.begin_pass(1)
        for i in range(120):
            monitor.event("tick", i=i, pad="x" * 200)
        h.end_pass()
    finally:
        h.disable()
    assert sink.error is None
    assert sink.rotations >= 2 and len(sink.segments) >= 3
    # every segment independently schema-clean, whole lines only
    total = 0
    for seg in sink.segments:
        res = flight.validate_events_file(seg)
        assert res["errors"] == [], (seg, res["errors"][:5])
        total += res["events"]
    assert total >= 120
    # the old segment's tail names its successor
    with open(sink.segments[0]) as f:
        last = json.loads(f.read().splitlines()[-1])
    assert last["name"] == "sink_rotated"
    assert last["fields"]["next"] == os.path.basename(sink.segments[1])
    # aggregate discovers the segments in write order and sees every
    # event exactly once (incl. the flight record)
    files = agg_lib.discover_stream_files(str(tmp_path))
    assert files == sink.segments
    st = agg_lib.read_stream(str(tmp_path))
    assert st["events"] >= 120
    assert len(st["flight_records"]) == 1
    # ordering survives a shuffled listing
    assert agg_lib.order_segments(list(reversed(files))) == files


def test_rotation_fault_latches_error_not_training(tmp_path):
    """telemetry.rotate.pre: a failed rotation latches the sink error;
    the emitting thread never sees an exception and every
    already-written segment stays parseable."""
    path = str(tmp_path / "events.jsonl")
    sink = monitor.JsonlSink(path, rotate_mb=0.01)
    h = monitor.hub()
    h.enable(sink)
    faultpoint.arm("telemetry.rotate.pre", action="ioerror")
    try:
        for i in range(200):
            monitor.event("tick", i=i, pad="y" * 200)   # must never raise
    finally:
        # join the writer FIRST: disarming before the drain reaches the
        # rotation point would un-inject the fault under it
        h.disable()
        faultpoint.disarm()
    assert isinstance(sink.error, faultpoint.FaultInjected)
    assert len(sink.segments) == 1          # the rotation never landed
    res = flight.validate_events_file(path)
    assert res["errors"] == []
    # the latched error is visible through sink health (satellite 2)
    health = [s for s in h.summary()["sinks"]
              if s["type"] == "JsonlSink"]
    assert health and "FaultInjected" in health[0]["error"]
    # ...and the doctor's sink-health rule fires on exactly this
    rep = doctor.diagnose(sink_health=health)
    assert {r["rule"]: r["status"] for r in rep["rules"]}[
        "sink-health"] == "fired"


# ---------------------------------------------------------------------------
# live mode (flags.doctor_live)
# ---------------------------------------------------------------------------

def test_doctor_live_emits_findings_at_end_pass():
    from paddlebox_tpu.config import set_flags

    h = monitor.hub()
    ms = monitor.MemorySink()
    h.enable(ms)
    before = STATS.get("doctor.findings")
    set_flags(doctor_live=True)
    try:
        h.begin_pass(31)
        # a boundary far above the (tiny) pass wall -> boundary-wall
        h.record_train(steps=1, examples=8, seconds=0.01,
                       boundary_seconds=5.0,
                       boundary_split={"build": 3.0, "h2d": 2.0,
                                       "spill_fault_in": 0.0})
        h.end_pass()
        findings = h.last_doctor_findings
    finally:
        set_flags(doctor_live=False)
        h.disable()
    # live mode reads the CUMULATIVE registry, so rules fed by earlier
    # tests' counters may fire too — the boundary-wall finding must be
    # among them (assert membership, not position)
    assert findings
    assert any(f["rule"] == "boundary-wall" for f in findings)
    evs = ms.find("doctor.finding")
    assert evs, "live mode must emit doctor.finding events"
    bw = next(e for e in evs if e["fields"]["rule"] == "boundary-wall")
    # emitted inside the pass scope: the finding carries the pass tag
    assert bw["pass_id"] == 31
    assert bw["fields"]["suggestion"]
    assert STATS.get("doctor.findings") > before


def test_boxps_end_pass_returns_doctor_findings(tmp_path):
    from paddlebox_tpu.config import set_flags
    from paddlebox_tpu.fleet import BoxPS
    from test_monitor import _tiny_trainer

    tr, ds = _tiny_trainer(tmp_path)
    box = BoxPS(tr.store)
    h = monitor.hub()
    set_flags(doctor_live=True)
    try:
        box.begin_pass()
        tr.train_pass(ds)
        info = box.end_pass()
    finally:
        set_flags(doctor_live=False)
        h.disable()
    # live doctor ran; a tiny CPU pass is boundary-heavy, so findings
    # (if any) surface through the end_pass dict — both shapes are
    # legal, but the hub must have recorded the evaluation
    assert h.last_doctor_findings is not None or "doctor" not in info \
        or info["doctor"]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_json_over_synthetic_stream(tmp_path, capsys):
    _write_stream(str(tmp_path / "rank0"),
                  [make_flight(1, seconds=10.0, train=4.0, boundary=4.0),
                   make_flight(2, seconds=10.0, train=4.0, boundary=4.5)])
    rc = doctor.main([str(tmp_path / "rank0"), "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    rep = json.loads(out)
    assert doctor.validate_report(rep) == []
    assert rep["verdict"].startswith("findings")
    assert [p["pass_id"] for p in rep["critical_path"]["passes"]] == [1, 2]
    assert any(f["rule"] == "boundary-wall" for f in rep["findings"])
    # human rendering carries the same facts
    rc2 = doctor.main([str(tmp_path / "rank0")])
    text = capsys.readouterr().out
    assert rc2 == 0
    assert "boundary-wall" in text and "suggestion:" in text


def test_cli_two_rank_world(tmp_path, capsys):
    roots = _golden_world(tmp_path)
    rc = doctor.main(roots + ["--json", "--rank-names", "4,7"])
    out = capsys.readouterr().out
    assert rc == 0
    rep = json.loads(out)
    assert rep["world"]["ranks"] == [4, 7]
    assert rep["world"]["passes"][0]["straggler"] == 7


def test_cli_fail_on_gates_serving_rules_from_stream(tmp_path, capsys):
    """ISSUE 19 CI gate: serving window records in a telemetry stream
    reach the serving rules through the CLI — --fail-on warn exits 1 on
    a version regression read off disk, 0 when the split looks clean."""
    bad = [make_flight(1),
           make_serving_window(
               100.0,
               versions={"1": {"role": "stable", "auc": 0.74},
                         "2": {"role": "candidate", "auc": 0.58}})]
    _write_stream(str(tmp_path / "bad"), bad)
    rc = doctor.main([str(tmp_path / "bad"), "--json",
                      "--fail-on", "warn"])
    out = capsys.readouterr().out
    assert rc == 1
    rep = json.loads(out)
    status = {r["rule"]: r["status"] for r in rep["rules"]}
    assert status["version-regression"] == "fired"

    good = [make_flight(1),
            make_serving_window(
                100.0,
                versions={"1": {"role": "stable", "auc": 0.74},
                          "2": {"role": "candidate", "auc": 0.74,
                                "score_kl": 0.02}})]
    _write_stream(str(tmp_path / "good"), good)
    assert doctor.main([str(tmp_path / "good"), "--json",
                        "--fail-on", "warn"]) == 0
    capsys.readouterr()


def test_cli_refuses_empty_inputs(tmp_path, capsys):
    assert doctor.main([]) == 2
    empty = tmp_path / "nothing"
    empty.mkdir()
    assert doctor.main([str(empty)]) == 2
    capsys.readouterr()


# ---------------------------------------------------------------------------
# registry guards
# ---------------------------------------------------------------------------

def test_monitor_faultpoints_registered():
    """telemetry.rotate.pre lives in the closed registry and in the
    MONITOR_POINTS category the kill matrices exclude (same shape as
    ELASTIC/SERVING/EXCHANGE_POINTS)."""
    assert set(faultpoint.MONITOR_POINTS) <= set(faultpoint.POINTS)
    assert "telemetry.rotate.pre" in faultpoint.MONITOR_POINTS


def test_exchange_rules_name_adaptive_exchange_knobs():
    """ISSUE 16: the exchange rules' suggestions name the CONCRETE
    adaptive-exchange knobs — overflow points at the hierarchical
    topology, dedup drift at the per-pass wire controller, and the
    cross-rank exchange edge at both — never a bare 'tune the wire'."""
    rep = doctor.diagnose(**RULE_FIXTURES["exchange-overflow"][0])
    f = next(f for f in rep["findings"] if f["rule"] == "exchange-overflow")
    assert "flags.exchange_topology='hier'" in f["suggestion"]

    rep = doctor.diagnose(**RULE_FIXTURES["dedup-drift"][0])
    f = next(f for f in rep["findings"] if f["rule"] == "dedup-drift")
    assert "flags.exchange_adaptive" in f["suggestion"]

    rep = doctor.diagnose(**RULE_FIXTURES["cross-rank-flow"][0])
    f = next(f for f in rep["findings"] if f["rule"] == "cross-rank-flow")
    assert f["evidence"]["longest_edge"]["kind"] == "exchange"
    assert "flags.exchange_adaptive" in f["suggestion"]
    assert "flags.exchange_topology='hier'" in f["suggestion"]
    assert "note_flow_attribution" in f["suggestion"]
