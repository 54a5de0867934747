"""Bench regression gate (BENCH_BEST.json) + the --dryrun tier-1 smoke.

Round 5 shipped a reproducible 1.87x headline regression inside a green
artifact. The gate makes that class of failure impossible: every recorded
number is compared against the best recorded value per metric, a >10%
unwaived regression fails audit_ok and the exit code, and the CPU dryrun
exercises the gate + stage-attribution + push-floor code paths on every
PR instead of only on-chip.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_PY = os.path.join(REPO, "bench.py")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("_bench_mod", BENCH_PY)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gate_trips_on_unwaived_regression(bench):
    best = {"device_kind": None, "threshold": 0.10,
            "metrics": {"headline_eps": 1000.0, "matrix.a": 500.0}}
    g = bench.apply_regression_gate(
        {"headline_eps": 850.0, "matrix.a": 510.0}, best, "cpu")
    assert not g["ok"]
    assert g["regressed"] == ["headline_eps"]
    assert g["lines"]["headline_eps"].startswith("REGRESS(")
    assert g["lines"]["matrix.a"].startswith("ok(")


def test_gate_honors_waiver_note(bench):
    best = {"device_kind": None,
            "metrics": {"headline_eps": 1000.0},
            "waivers": {"headline_eps": "known host-load variance"}}
    g = bench.apply_regression_gate({"headline_eps": 500.0}, best, "cpu")
    assert g["ok"]
    assert "waived: known host-load variance" in \
        g["lines"]["headline_eps"]


def test_gate_within_threshold_passes(bench):
    best = {"device_kind": None, "metrics": {"headline_eps": 1000.0}}
    g = bench.apply_regression_gate({"headline_eps": 905.0}, best, "cpu")
    assert g["ok"]


def test_gate_skips_foreign_hardware_and_missing_best(bench):
    best = {"device_kind": "TPU v5 lite",
            "metrics": {"headline_eps": 1000.0}}
    g = bench.apply_regression_gate({"headline_eps": 1.0}, best, "cpu")
    assert g["ok"] and "skipped" in g
    assert bench.apply_regression_gate({}, None, "cpu")["ok"]


def test_gate_reports_missing_and_new_metrics(bench):
    best = {"device_kind": None, "metrics": {"gone_metric": 10.0}}
    g = bench.apply_regression_gate({"new_metric": 5.0}, best, "cpu")
    assert g["ok"]
    assert "missing" in g["lines"]["gone_metric"]
    assert "new" in g["lines"]["new_metric"]


def test_collect_gate_metrics_namespace(bench):
    detail = {
        "matrix": {"kstep_f32": {"examples_per_sec_per_chip": 7.0},
                   "broken": {"error": "boom"}},
        "e2e": {"examples_per_sec_per_chip": 3.0},
        "host": {"derived_max_feed_eps_per_chip": 9.0},
    }
    m = bench.collect_gate_metrics(11.0, detail)
    assert m == {"headline_eps": 11.0, "matrix.kstep_f32": 7.0,
                 "e2e_eps": 3.0, "host.derived_max_feed_eps": 9.0}


def test_collect_gate_metrics_serving_points(bench):
    """The serving drill's publish/swap/latency numbers land in the gate
    namespace (ISSUE 7); a failed drill ({'error': …}) contributes
    nothing instead of poisoning the namespace."""
    detail = {"matrix": {"serving": {
        "publish_seconds": 0.8, "swap_pause_ms": 0.02, "p99_ms": 12.5,
        "p50_ms": 4.0, "serve_eps": 900.0}}}
    m = bench.collect_gate_metrics(1.0, detail)
    assert m["serving.publish_seconds"] == 0.8
    assert m["serving.swap_pause_ms"] == 0.02
    assert m["serving.p99_ms"] == 12.5
    assert "serving.p50_ms" not in m      # only the three gated points
    m2 = bench.collect_gate_metrics(1.0,
                                    {"matrix": {"serving":
                                                {"error": "boom"}}})
    assert not any(k.startswith("serving.") for k in m2)


def test_collect_gate_metrics_serving_split_point(bench):
    """The version-split drill gates exactly shadow_p99_ms (ISSUE 19) —
    the AUC/KL attribution rides the artifact, not the gate; a failed
    drill contributes nothing."""
    detail = {"matrix": {"serving_split": {
        "shadow_p99_ms": 9.5, "shadow_p50_ms": 3.0, "stable_auc": 0.8,
        "candidate_auc": 0.79, "score_kl": 0.01, "requests": 256}}}
    m = bench.collect_gate_metrics(1.0, detail)
    assert m["serving_split.shadow_p99_ms"] == 9.5
    assert not any(k.startswith("serving_split.")
                   for k in m if k != "serving_split.shadow_p99_ms")
    m2 = bench.collect_gate_metrics(
        1.0, {"matrix": {"serving_split": {"error": "boom"}}})
    assert not any(k.startswith("serving_split.") for k in m2)


def test_collect_gate_metrics_serving_fleet_point(bench):
    """The fleet drill gates exactly p99_ms + swap_convergence_s
    (ISSUE 20) — the hedge/governor attribution rides the artifact, not
    the gate; a failed drill contributes nothing."""
    detail = {"matrix": {"serving_fleet": {
        "p99_ms": 12.5, "swap_convergence_s": 0.4, "p50_ms": 3.0,
        "hedges": 9, "hedges_won": 9, "promote_decision": "hold",
        "requests": 128}}}
    m = bench.collect_gate_metrics(1.0, detail)
    assert m["serving_fleet.p99_ms"] == 12.5
    assert m["serving_fleet.swap_convergence_s"] == 0.4
    assert not any(k.startswith("serving_fleet.") for k in m
                   if k not in ("serving_fleet.p99_ms",
                                "serving_fleet.swap_convergence_s"))
    m2 = bench.collect_gate_metrics(
        1.0, {"matrix": {"serving_fleet": {"error": "boom"}}})
    assert not any(k.startswith("serving_fleet.") for k in m2)


def test_gate_bare_s_is_lower_is_better_but_per_s_is_not(bench):
    """Bare ``_s`` metrics (the fleet's swap convergence) gate in the
    latency direction while ``_per_s`` stays throughput: a slower
    convergence regresses, and a FASTER fetch rate must not read as a
    regression through the suffix test."""
    best = {"device_kind": None, "threshold": 0.10,
            "metrics": {"serving_fleet.swap_convergence_s": 2.0,
                        "spill_10x.fetch_keys_per_s": 5000.0}}
    g = bench.apply_regression_gate(
        {"serving_fleet.swap_convergence_s": 8.0,
         "spill_10x.fetch_keys_per_s": 9000.0}, best, "cpu")
    assert not g["ok"]
    assert g["regressed"] == ["serving_fleet.swap_convergence_s"]
    assert g["lines"]["spill_10x.fetch_keys_per_s"].startswith("ok(+80%")
    g2 = bench.apply_regression_gate(
        {"serving_fleet.swap_convergence_s": 0.5,
         "spill_10x.fetch_keys_per_s": 2000.0}, best, "cpu")
    assert g2["regressed"] == ["spill_10x.fetch_keys_per_s"]
    assert g2["lines"][
        "serving_fleet.swap_convergence_s"].startswith("ok(+300%")
    # sub-floor convergence walls clamp like the other latency points:
    # a 3x swing under 0.05s is timer noise, not a regression
    g3 = bench.apply_regression_gate(
        {"serving_fleet.swap_convergence_s": 0.03,
         "spill_10x.fetch_keys_per_s": 5000.0},
        {"device_kind": None,
         "metrics": {"serving_fleet.swap_convergence_s": 0.01,
                     "spill_10x.fetch_keys_per_s": 5000.0}}, "cpu")
    assert g3["ok"]


def test_gate_latency_metrics_are_lower_is_better(bench):
    """Metrics named *_ms / *_seconds gate in the latency direction: a
    HIGHER current value regresses, a lower one is an improvement —
    throughput metrics keep the original direction in the same pass."""
    best = {"device_kind": None, "threshold": 0.10,
            "metrics": {"serving.p99_ms": 10.0,
                        "serving.publish_seconds": 2.0,
                        "headline_eps": 1000.0}}
    g = bench.apply_regression_gate(
        {"serving.p99_ms": 20.0, "serving.publish_seconds": 1.0,
         "headline_eps": 1000.0}, best, "cpu")
    assert not g["ok"] and g["regressed"] == ["serving.p99_ms"]
    assert g["lines"]["serving.p99_ms"].startswith("REGRESS(-50%")
    assert g["lines"]["serving.publish_seconds"].startswith("ok(+100%")
    g2 = bench.apply_regression_gate(
        {"serving.p99_ms": 10.5, "headline_eps": 1000.0,
         "serving.publish_seconds": 2.0}, best, "cpu")
    assert g2["ok"]                       # within threshold both ways


def test_gate_latency_floor_ignores_timer_noise(bench):
    """Sub-floor latencies (the swap pause is one attribute rebind,
    sub-µs) are timer noise: a 3x relative swing below the floor must not
    trip the gate, while a real-scale regression past it still does."""
    best = {"device_kind": "cpu",
            "metrics": {"serving.swap_pause_ms": 0.0003,
                        "serving.p99_ms": 10.0}}
    g = bench.apply_regression_gate(
        {"serving.swap_pause_ms": 0.0009, "serving.p99_ms": 10.0},
        best, "cpu")
    assert g["ok"] and g["lines"]["serving.swap_pause_ms"].startswith("ok")
    g2 = bench.apply_regression_gate(
        {"serving.swap_pause_ms": 5.0, "serving.p99_ms": 10.0}, best, "cpu")
    assert not g2["ok"] and g2["regressed"] == ["serving.swap_pause_ms"]


def _fake_step_bench(fail_dim):
    """device_step_bench stand-in: one canned point per call, and a
    forced failure at `fail_dim`."""
    def fake(small, return_ctx=False, emb_dim=8, **kw):
        if emb_dim == fail_dim:
            raise RuntimeError(f"forced failure at dim {emb_dim}")
        detail = {"device_kind": "cpu", "devices": 1,
                  "audit": {"ok": True, "step_seconds": 0.01},
                  "push_engine": "xla_scatter",
                  "pull_engine": "gather_seqpool", "pack_engine": None,
                  "push_overlap": "on", "table_layout": "single",
                  "exchange_wire": "-", "table_shards": 1}
        ctx = {"mode": "allreduce", "n_dev": 1}
        return (100.0, detail, ctx) if return_ctx else (100.0, detail)
    return fake


@pytest.mark.parametrize("fail_dim,want_rc", [(64, 3), (None, 0)])
def test_failed_matrix_point_fails_exit_code(bench, monkeypatch, capsys,
                                             fail_dim, want_rc):
    """A matrix point that raises is recorded as {"error": ...} and the
    artifact still prints — but the run exits non-zero: a section that
    did not run must not hide inside a green exit code."""
    from paddlebox_tpu.utils import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: {"dir": None, "from": "test",
                                 "warm": False})
    monkeypatch.setattr(bench, "device_step_bench",
                        _fake_step_bench(fail_dim))
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setenv("PBTPU_BENCH_SMALL", "1")
    monkeypatch.setenv("PBTPU_BENCH_BEST", os.devnull)
    monkeypatch.setenv("PBTPU_BENCH_MATRIX_ATTR", "")
    for section in ("ATTR", "SHARDED", "SPILL", "ELASTIC", "SERVING",
                    "HOST", "E2E"):
        monkeypatch.setenv(f"PBTPU_BENCH_{section}", "0")
    rc = 0
    try:
        bench.main()
    except SystemExit as e:
        rc = e.code
    assert rc == want_rc
    out = capsys.readouterr()
    artifact = json.loads(out.out.strip().splitlines()[0])
    matrix = artifact["detail"]["matrix"]
    assert "examples_per_sec_per_chip" in matrix["kstep_f32"]
    if fail_dim is None:
        assert "examples_per_sec_per_chip" in matrix["allreduce_f32_dim64"]
    else:
        assert "forced failure" in matrix["allreduce_f32_dim64"]["error"]
        assert "matrix.allreduce_f32_dim64" in out.err


def test_bench_dryrun_smoke():
    """`bench.py --dryrun` (tier-1): the gate + attribution + floor code
    paths run on CPU at tiny geometry; the gate must trip on an injected
    synthetic regression and the process must exit 0 with every check
    green."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    r = subprocess.run([sys.executable, BENCH_PY, "--dryrun"],
                       capture_output=True, text=True, env=env,
                       timeout=560, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["metric"] == "bench_dryrun" and out["ok"]
    assert out["checks"]["gate_trips_on_regression"]
    assert out["checks"]["waiver_untrips"]
    assert out["checks"]["attribution_ok"]
    assert out["checks"]["floor_ok"]
    # the per-point push-engine record (ISSUE 13): the resolver's
    # verdict is recorded per matrix point and the floor carries the
    # per-candidate-engine closure statements the doctor names concrete
    # flags.push_engine forces from
    assert out["checks"]["push_engine_recorded"]
    assert out["push_engine"] in ("xla_scatter", "binned_kernel",
                                  "scatter_accumulate")
    assert out["push_overlap"] == "on"
    assert "stages" in out and "sparse_push" in out["stages"]
    assert out["gate_example_lines"]["headline_eps"].startswith("REGRESS")
    # the serving drill's points must exist in the artifact (ISSUE 7):
    # publish timed, hot-swap paused-and-measured, tail latency recorded,
    # zero failed requests across the swap
    assert out["checks"]["serving_fields"], out.get("serving")
    assert out["checks"]["latency_gate_trips_lower_is_better"]
    assert out["serving"]["publish_seconds"] > 0
    assert out["serving"]["swap_pause_ms"] > 0
    assert out["serving"]["p99_ms"] > 0
    # the version-split point must exist with per-version attribution
    # (ISSUE 19): shadow tail latency gate-held, AUC/score-KL recorded,
    # schema-valid serving record, the three serving rules evaluated
    assert out["checks"]["serving_obs_fields"], out.get("serving_split")
    assert out["serving_split"]["shadow_p99_ms"] > 0
    assert 0 <= out["serving_split"]["stable_auc"] <= 1
    assert out["serving_split"]["score_kl"] >= 0
    assert set(out["serving_split"]["doctor_rules"]) == {
        "version-regression", "p99-burn", "swap-regression"}
    # the fleet point must exist with its acceptance property
    # (ISSUE 20): routed tail held UNDER the injected slow replica by
    # hedging, fleet-wide swap convergence timed, the governor's hold
    # recorded, and the fleet-degraded rule fired off that hold — so
    # serving_fleet enters the BENCH_BEST gate from day one
    assert out["checks"]["fleet_fields"], out.get("serving_fleet")
    assert out["checks"]["convergence_gate_trips_lower_is_better"]
    sf = out["serving_fleet"]
    assert 0 < sf["p99_ms"] < 150.0
    assert sf["swap_convergence_s"] > 0
    assert sf["hedges_won"] >= 1
    assert sf["promote_decision"] == "hold"
    assert sf["doctor_rules"] == {"fleet-degraded": "fired"}
    # the sharded-exchange matrix points must exist with their identity
    # fields (ISSUE 10): table_layout/exchange_wire/shard count recorded,
    # dedup ratio measured — so sharded points enter the BENCH_BEST gate
    # from day one
    assert out["checks"]["sharded_fields"], out.get("sharded")
    assert out["sharded"]["table_layout"] == "sharded"
    assert out["sharded"]["exchange_wire"] == "f32"
    assert out["sharded"]["table_shards"] == 2
    assert 0 < out["sharded"]["dedup_ratio"] <= 1.0
    # the tiered-table point must exist with its acceptance property
    # (ISSUE 11): a working set >= 10x the RAM cache budget through the
    # sharded+spill path, and the show-count-weighted policy's hot-tier
    # hit rate beating the direct-mapped last-wins baseline on the SAME
    # traffic — so spill_10x enters the BENCH_BEST gate from day one
    assert out["checks"]["spill_fields"], out.get("spill")
    assert out["spill"]["hot_hit_rate"] > out["spill"]["direct_hot_hit_rate"]
    assert out["spill"]["fetch_keys_per_s"] > 0
    # the set-associative geometry point (PR 17): on the adversarial
    # colliding stream the N-way cache must beat direct-mapped at the
    # SAME row budget with byte-identical row files, and the baseline
    # must show the conflict misses that explain the gap — so the
    # spill_assoc point enters the BENCH_BEST gate from day one
    assert out["checks"]["assoc_fields"], out.get("spill_assoc")
    sa = out["spill_assoc"]
    assert sa["assoc"] == 4
    assert sa["assoc_hit_rate"] > sa["direct_hit_rate"]
    assert sa["conflict_misses_direct"] > 0
    assert sa["parity"] is True
    # the world-trace embed (ISSUE 15): a traced probe pass merged into
    # a Chrome-trace summary with a publish flow edge, and the span-
    # level data reached the doctor's cross-rank-flow rule
    assert out["checks"]["trace_embedded"]
