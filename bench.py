"""Benchmark: DeepFM training throughput on the available chip(s).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "examples/sec/chip", "vs_baseline": N}

Two measurements, reported side by side (VERDICT r1 #2):

1. **device_step** — the jitted train step alone (routed embedding lookup,
   DeepFM fwd/bwd, dense pmean, sparse push with in-table adagrad), batches
   pre-staged on device. This is the device-path microbenchmark, the
   analogue of the reference's `cal` time in log_for_profile
   (boxps_worker.cc:746-759). It is NOT full-pipeline training throughput.
2. **e2e** — full `Trainer.train_pass` over TWO passes from a pre-built
   `.pbar` archive: working-set build (incremental on pass 2), per-batch
   translate, H2D, step, AUC — everything except parse (archive is
   pre-parsed, matching the reference's `read`/`trans`/`cal` split).

**Timing discipline**: every window ends by reading the final step's loss
on the host (a 4-byte D2H), which waits for every step queued before it —
JAX dispatch is asynchronous, and a window without such a terminator times
the enqueue. `chip_smoke.py`'s sync_check line records, on the chip, that
`jax.block_until_ready` waits too; the loss read stays because the artifact
carries the loss anyway.

**Self-audit**: the device-step number carries analytic FLOPs/step and
HBM bytes/step, and the implied MFU / HBM fractions against the detected
chip's peaks. An implied MFU > 60% means the measurement window is broken,
not that the code is fast — the bench then exits non-zero.

vs_baseline is measured against the north-star target of 1M examples/sec
per chip (BASELINE.md; the reference publishes no numbers of its own).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

TARGET_PER_CHIP = 1_000_000.0  # BASELINE.md north star

# (bf16 matmul FLOP/s, HBM bytes/s) per device_kind substring
PEAKS = {
    "v5 lite": (197e12, 819e9),
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v6 lite": (918e12, 1640e9),
    "v6e": (918e12, 1640e9),
    "v4": (275e12, 1228e9),
}


_STARTUP_SPLITS: list = []


def _startup_splits() -> int:
    """flags.binned_push_splits as configured at bench start (env
    override included), captured before any matrix point mutates it."""
    if not _STARTUP_SPLITS:
        from paddlebox_tpu.config import flags as config_flags
        _STARTUP_SPLITS.append(config_flags.binned_push_splits)
    return _STARTUP_SPLITS[0]


_STARTUP_FLAGS: dict = {}


def _startup_flag(name: str):
    """A flag's value at bench start, captured before any matrix point
    overrides it (the _startup_splits discipline, generalized for the
    sharded-exchange points' table_layout/exchange_wire overrides)."""
    if name not in _STARTUP_FLAGS:
        from paddlebox_tpu.config import flags as config_flags
        _STARTUP_FLAGS[name] = config_flags.get(name)
    return _STARTUP_FLAGS[name]


def _peaks(device_kind: str, cpu_smoke: bool = False):
    """(peak FLOP/s, peak HBM bytes/s) of this device. A device that is
    not in PEAKS is an error, not a default: without peaks the self-audit
    cannot tell a broken timing window from a fast step. Only the CPU
    smoke modes (PBTPU_BENCH_SMALL, --dryrun, the CPU children) run
    without — their audit says so."""
    dk = device_kind.lower()
    for key, val in PEAKS.items():
        if key in dk:
            return val
    if cpu_smoke:
        return None
    raise RuntimeError(
        f"device_kind {device_kind!r} is not in bench.py's PEAKS table: "
        f"add its published peaks (with their source) before measuring "
        f"on it")


# ---------------------------------------------------------------------------
# Round-over-round regression gate (the discipline round 5 lacked: a 1.87x
# headline regression shipped inside a green artifact). BENCH_BEST.json
# holds the best RECORDED value per metric per matrix point; every number
# this run produces is compared against it, every point gets an explicit
# ok/REGRESS line in the artifact AND the compact tail, and an unwaived
# >threshold regression fails audit_ok + the process exit code.
# ---------------------------------------------------------------------------

GATE_THRESHOLD = 0.10


def load_bench_best() -> dict | None:
    """BENCH_BEST.json next to this file (PBTPU_BENCH_BEST overrides —
    tests inject synthetic bests through it). None when absent."""
    path = os.environ.get(
        "PBTPU_BENCH_BEST",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_BEST.json"))
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def collect_gate_metrics(eps_chip: float, detail: dict) -> dict:
    """Flatten this run's recorded numbers into the gate's metric
    namespace. Throughput metrics are higher-is-better; names ending in
    ``_ms``/``_seconds``/bare ``_s`` (the serving drills' latency and
    convergence points — ``_per_s`` stays throughput) are
    lower-is-better — apply_regression_gate keys the direction off the
    suffix."""
    m = {"headline_eps": eps_chip}
    for name, point in (detail.get("matrix") or {}).items():
        if isinstance(point, dict) and \
                "examples_per_sec_per_chip" in point:
            m[f"matrix.{name}"] = point["examples_per_sec_per_chip"]
    srv = (detail.get("matrix") or {}).get("serving")
    if isinstance(srv, dict):
        # the train→publish→serve loop's operator-facing numbers: how
        # long a publish takes, how long the hot-swap pauses requests,
        # and the tail latency the frontend holds under load
        for k in ("publish_seconds", "swap_pause_ms", "p99_ms"):
            if isinstance(srv.get(k), (int, float)):
                m[f"serving.{k}"] = srv[k]
    ss = (detail.get("matrix") or {}).get("serving_split")
    if isinstance(ss, dict):
        # version-split point (ISSUE 19): the served tail latency while
        # shadow scoring doubles the predictor work per request —
        # lower-is-better off the _ms suffix like the serving points
        if isinstance(ss.get("shadow_p99_ms"), (int, float)):
            m["serving_split.shadow_p99_ms"] = ss["shadow_p99_ms"]
    sf = (detail.get("matrix") or {}).get("serving_fleet")
    if isinstance(sf, dict):
        # fleet point (ISSUE 20): the routed tail latency while one
        # replica is injected slow — hedging must hold this gate — and
        # the wall from a version publish to EVERY replica serving it
        # (``_s`` suffix without ``_per_s`` is lower-is-better)
        for k in ("p99_ms", "swap_convergence_s"):
            if isinstance(sf.get(k), (int, float)):
                m[f"serving_fleet.{k}"] = sf[k]
    sp = (detail.get("matrix") or {}).get("spill_10x")
    if isinstance(sp, dict):
        # tiered-table point: cold-tier fetch throughput + the hot-tier
        # hit rate the admission policy holds under the 10x working set
        # (both higher-is-better; gate-held like every other point)
        for k in ("fetch_keys_per_s", "hot_hit_rate"):
            if isinstance(sp.get(k), (int, float)):
                m[f"spill_10x.{k}"] = sp[k]
    sa = (detail.get("matrix") or {}).get("spill_assoc")
    if isinstance(sa, dict):
        # set-associative geometry point: the N-way hot hit rate on the
        # adversarial colliding stream (the number direct-mapped caps)
        # plus the fetch throughput — both higher-is-better, gate-held
        for k in ("assoc_hit_rate", "fetch_keys_per_s"):
            if isinstance(sa.get(k), (int, float)):
                m[f"spill_assoc.{k}"] = sa[k]
    bd = (detail.get("matrix") or {}).get("boundary_incremental")
    if isinstance(bd, dict):
        # pass-boundary point: the incremental+overlapped boundary wall
        # (lower-is-better off the _seconds suffix) and the speedup it
        # holds over the full-rebuild baseline on the same key stream
        for k in ("boundary_seconds", "speedup"):
            if isinstance(bd.get(k), (int, float)):
                m[f"boundary_incremental.{k}"] = bd[k]
    e2e = detail.get("e2e")
    if isinstance(e2e, dict) and "examples_per_sec_per_chip" in e2e:
        m["e2e_eps"] = e2e["examples_per_sec_per_chip"]
    host = detail.get("host")
    if isinstance(host, dict) and \
            isinstance(host.get("derived_max_feed_eps_per_chip"),
                       (int, float)):
        m["host.derived_max_feed_eps"] = \
            host["derived_max_feed_eps_per_chip"]
    return m


def apply_regression_gate(current: dict, best: dict | None,
                          device_kind: str) -> dict:
    """Compare `current` metrics against the recorded bests.

    Returns the gate record for the artifact: per-metric
    ``ok(+x%)`` / ``REGRESS(-x%)`` / ``REGRESS(-x%) waived: note`` lines,
    and ``ok`` False iff any metric regressed more than the threshold
    WITHOUT an explicit waiver note. Skips (ok) when no best file exists
    or it was recorded on different hardware — a CPU dryrun must not
    "regress" against chip numbers."""
    if not best:
        return {"ok": True, "skipped": "no BENCH_BEST.json recorded"}
    want_kind = best.get("device_kind")
    if want_kind is not None and want_kind != device_kind:
        return {"ok": True,
                "skipped": f"BENCH_BEST records {want_kind!r}, this run "
                           f"is on {device_kind!r} — not comparable"}
    thresh = float(best.get("threshold", GATE_THRESHOLD))
    waivers = best.get("waivers", {}) or {}
    lines: dict = {}
    ok = True
    regressed = []
    for name, best_v in (best.get("metrics") or {}).items():
        cur = current.get(name)
        if cur is None:
            lines[name] = "missing (not measured this run)"
            continue
        # latency-flavored metrics (…_ms/_seconds) are lower-is-better:
        # rel is the signed improvement fraction either way, so the
        # threshold/waiver/line machinery below is direction-blind.
        # Sub-floor latencies are timer noise — the swap pause is one
        # attribute rebind, sub-µs, where scheduler jitter alone is a
        # multi-x relative swing — so both sides clamp to the floor:
        # noise never trips the gate, real-scale regressions still do
        if name.endswith(("_ms", "_seconds")) or \
                (name.endswith("_s") and not name.endswith("_per_s")):
            floor = 1.0 if name.endswith("_ms") else 0.05
            rel = max(best_v, floor) / max(cur, floor) - 1.0
        else:
            rel = cur / best_v - 1.0
        if rel < -thresh:
            if name in waivers:
                lines[name] = (f"REGRESS({rel:+.0%}) waived: "
                               f"{waivers[name]}")
            else:
                lines[name] = f"REGRESS({rel:+.0%})"
                regressed.append(name)
                ok = False
        else:
            lines[name] = f"ok({rel:+.0%})"
    for name in current:
        if name not in lines:
            lines[name] = "new (no recorded best)"
    return {"ok": ok, "threshold": thresh, "lines": lines,
            "regressed": regressed,
            "note": "values compared against the best RECORDED value per "
                    "metric (BENCH_BEST.json); an unwaived regression "
                    "past the threshold fails audit_ok and the exit code"}


def _collect_errors(node, path: str = "") -> list:
    """Every ``{"error": ...}`` a section recorded in place of its
    result, as ``"path: error"`` strings. A section that fails must not
    take the artifact down with it — the headline still prints — but the
    run did not do what it was asked: main exits non-zero on any."""
    found = []
    if isinstance(node, dict):
        for k, v in node.items():
            here = f"{path}.{k}" if path else str(k)
            if k in ("error", "bench_error") and v:
                found.append(f"{path or 'bench'}: {v}")
            else:
                found.extend(_collect_errors(v, here))
    return found


def _mark(msg, t0=[None]):
    if t0[0] is None:
        t0[0] = time.time()
    print(f"# bench [{time.time()-t0[0]:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


def _sync_scalar(x) -> float:
    """Read a scalar on the host: waits for the device (module docstring)."""
    return float(np.asarray(x))


def _analytic_cost(batch, num_slots, emb_dim, dense_dim, hidden, emb_cfg,
                   n_pad_rows, max_len=1):
    """Matmul-dominant FLOPs and HBM traffic of one train step."""
    dims = [num_slots * emb_dim + dense_dim, *hidden, 1]
    fwd = 2.0 * batch * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    fwd += 2.0 * batch * num_slots * emb_dim * 4  # FM sum-square term
    flops = 3.0 * fwd                              # fwd + ~2x bwd
    toks = batch * num_slots * max_len
    w, pw, gw = emb_cfg.row_width, emb_cfg.pull_width, emb_cfg.grad_width
    hbm = 4.0 * (
        toks * w + toks * pw            # gather read rows, write pulled
        + toks * (gw + 3) * 2           # scatter payload write + add
        + n_pad_rows * (gw + 3) * 2     # accumulator init + read
        + n_pad_rows * w * 2            # merge-update table read+write
        + batch * 2 * sum(dims))        # activations fwd+bwd (rough)
    return flops, hbm


def device_step_bench(small: bool, mode: str = "allreduce",
                      storage: str | None = None,
                      n_steps: int | None = None, n_windows: int = 3,
                      batch_per_dev: int | None = None,
                      n_split: int | None = None,
                      emb_dim: int = 8, max_len: int = 1,
                      return_ctx: bool = False, tiny: bool = False,
                      table_layout: str | None = None,
                      exchange_wire: str | None = None):
    import jax
    from paddlebox_tpu.config import flags as config_flags
    from paddlebox_tpu.data import DataFeedSchema
    from paddlebox_tpu.embedding import (EmbeddingConfig, HostEmbeddingStore,
                                         PassWorkingSet, quant)
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.parallel import make_mesh, mesh as mesh_lib
    from paddlebox_tpu.train import Trainer, TrainerConfig

    # n_split=None keeps the STARTUP value (framework default or the
    # operator's PBTPU_BINNED_PUSH_SPLITS env override) — matrix points
    # that override it must not leak into later configs; same rule for
    # the sharded-exchange engine knobs
    config_flags.binned_push_splits = (_startup_splits() if n_split is None
                                       else n_split)
    config_flags.table_layout = (_startup_flag("table_layout")
                                 if table_layout is None else table_layout)
    config_flags.exchange_wire = (_startup_flag("exchange_wire")
                                  if exchange_wire is None
                                  else exchange_wire)
    devices = jax.devices()
    n_dev = len(devices)
    # tiny = --dryrun geometry: small enough that the full bench pipeline
    # (trainer, attribution, floor, gate) runs in seconds on one CPU —
    # the code paths are the product, the numbers are not
    num_slots, dense_dim, hidden = ((4, 3, (32,)) if tiny
                                    else (26, 13, (400, 400, 400)))
    if batch_per_dev is None:
        batch_per_dev = 64 if tiny else (256 if small else 8192)
    batch = batch_per_dev * n_dev
    schema = DataFeedSchema.ctr(num_sparse=num_slots, num_float=dense_dim,
                                batch_size=batch, max_len=max_len)
    # PBTPU_BENCH_STORAGE=int8|int16 overrides the headline storage mode
    if storage is None:
        storage = os.environ.get("PBTPU_BENCH_STORAGE", "f32")
    emb_cfg = EmbeddingConfig(dim=emb_dim, optimizer="adagrad",
                              learning_rate=0.05, storage=storage)
    store = HostEmbeddingStore(emb_cfg)
    mesh = make_mesh(n_dev)
    model = DeepFMModel(num_slots=num_slots, emb_dim=emb_dim,
                        dense_dim=dense_dim, hidden=hidden)
    tr = Trainer(model, store, schema, mesh,
                 TrainerConfig(global_batch_size=batch, auc_buckets=1 << 16,
                               dense_sync_mode=mode))
    rng = np.random.default_rng(0)
    n_keys = 1 << (9 if tiny else (14 if small else 19))
    keys = rng.choice(1 << 50, n_keys, replace=False).astype(np.uint64)
    _mark("keys ready")
    ws = PassWorkingSet.begin_pass(store, keys, mesh)
    _mark("begin_pass done")
    T = tr.layout.total_len
    sh = mesh_lib.batch_sharding(mesh)

    n_staged = 4
    host_batches = []
    # measured dedup: the pack-side plan emits the per-batch unique-lane
    # counters (trainer.plan_unique_tokens single-shard, exchange.
    # unique_lanes sharded) while these batches stage — their mean feeds
    # the push floor the measured lanes instead of the tokens upper
    # bound (ROADMAP PR-12 follow-up #3)
    from paddlebox_tpu import monitor as _mon
    _plan0 = _mon.STATS.snapshot()
    for _ in range(n_staged):
        raw = rng.choice(keys, size=(batch, T))
        if max_len > 1 and T == num_slots * max_len:
            # multi-hot: variable slot lengths with real pad masking
            # (the DLRM/DCN-v2 geometry — BASELINE.md)
            lens = rng.integers(1, max_len + 1, size=(batch, num_slots))
            mask = (np.arange(max_len)[None, None, :]
                    < lens[:, :, None]).reshape(batch, T)
        else:
            mask = np.ones((batch, T), dtype=bool)
        idx = ws.translate(raw, mask)
        dense = rng.normal(size=(batch, dense_dim)).astype(np.float32)
        labels = (rng.random(batch) < 0.25).astype(np.float32)
        # the host binned-push plan is part of the pack pipeline (overlaps
        # device compute in train_pass); staged here like the batch itself
        plan = tr._host_plan(ws, idx)
        host_batches.append((idx, mask, dense, labels, *plan))
    _plan1 = _mon.STATS.snapshot()
    _udelta = (_plan1.get("exchange.unique_lanes", 0.0)
               - _plan0.get("exchange.unique_lanes", 0.0)) \
        or (_plan1.get("trainer.plan_unique_tokens", 0.0)
            - _plan0.get("trainer.plan_unique_tokens", 0.0))
    # per-shard per-step mean (the floor models ONE chip's pass; the
    # counters sum the whole world's lanes per batch)
    measured_lanes = (int(round(_udelta / n_staged / n_dev))
                      if _udelta > 0 else None)
    staged = [tuple(jax.device_put(a, sh) for a in hb)
              for hb in host_batches]
    # superstep operands: the same batches stacked for k-per-dispatch
    # groups (what train_pass stages by default — steps_per_dispatch)
    ksd = tr.cfg.steps_per_dispatch if tr._superstep_fn is not None else 1
    staged_stacked = None
    if ksd > 1:
        assert n_staged % ksd == 0 or ksd % n_staged == 0
        reps = max(1, ksd // n_staged)
        seq = (host_batches * reps)[:ksd]
        staged_stacked = jax.device_put(
            tuple(np.stack(cols) for cols in zip(*seq)),
            mesh_lib.stacked_batch_sharding(mesh))
    _mark("staged batches on device")

    repl = mesh_lib.replicated_sharding(mesh)

    def run_steps(table, k):
        """k steps in the selected dense-sync mode, returning the final
        loss array (mode-faithful: kstep syncs every param_sync_step,
        async pulls/pushes the host dense table each step — the real
        cost profile of trainer_desc.proto:100-108's modes). Allreduce
        runs the trainer's default k-microbatch superstep (one dispatch
        per steps_per_dispatch batches, like train_pass)."""
        nonlocal params, opt, dstate
        from paddlebox_tpu import monitor
        monitor.counter_add("bench.device_steps", k)
        if mode == "allreduce" and staged_stacked is not None:
            assert k % ksd == 0, (k, ksd)
            for _ in range(k // ksd):
                out = tr._superstep_fn(table, *dstate, *staged_stacked)
                table, dstate, loss, _, _ = tr.split_step_out(out)
            return table, loss[-1:]
        for i in range(k):
            b = staged[i % n_staged]
            if mode == "async":
                p = jax.device_put(tr._unravel(tr.dense_table.pull()),
                                   repl)
                table, gp_flat, loss, preds, drop = tr._step_fn(
                    table, p, *b)
                tr.dense_table.push(np.asarray(gp_flat))
            elif mode == "kstep":
                table, params, opt, loss, preds, drop = tr._step_fn(
                    table, params, opt, *b)
                params, opt = tr._sync_fn(params, opt)
            elif tr.push_overlap:
                # deferred push pipeline (flags.push_overlap): loss-path
                # program + apply program back to back, train_pass's
                # dataflow — the headline measures the mode training runs
                out = tr._defer_step_fn(table, *dstate, *b)
                dstate, ops, loss, _, _ = tr.split_defer_out(out)
                table = tr._apply_fn(table, b[0], b[1], b[3],
                                     *b[4:9], *ops)
            else:
                out = tr._step_fn(table, *dstate, *b)
                table, dstate, loss, _, _ = tr.split_step_out(out)
        return table, loss

    params, opt = tr.params, tr.opt_state
    dstate = tr.pack_dense() if mode == "allreduce" else None
    if mode == "async":
        tr.dense_table.start()
    # compile + settle layouts (one superstep group when that's the path)
    table, loss = run_steps(ws.table, ksd if staged_stacked is not None
                            else 2)
    _sync_scalar(loss)
    _mark(f"warmup/compile done ({mode}/{storage})")

    if n_steps is None:
        n_steps = 5 if small else 200
    if staged_stacked is not None:
        n_steps = -(-n_steps // ksd) * ksd     # whole superstep groups
    windows = []
    for _ in range(1 if small else n_windows):
        t0 = time.perf_counter()
        table, loss = run_steps(table, n_steps)
        loss_v = _sync_scalar(loss)  # real D2H terminates the window
        windows.append(time.perf_counter() - t0)
    dt = min(windows)
    if mode == "async":
        tr.dense_table.flush()
    _mark(f"device-step windows done ({mode}/{storage})")

    eps_chip = n_steps * batch / dt / n_dev
    ws.table = table                       # post-donation rebind
    if mode == "allreduce":
        tr.params, tr.opt_state = tr.unpack_dense(dstate)
    elif mode == "kstep":
        tr.params, tr.opt_state = params, opt
    # stage attribution is NOT run here: _enrich is its single entry
    # point (under main's print-always guard, after this frame's staged
    # batches would otherwise be redundantly resident)
    flops, hbm = _analytic_cost(batch, num_slots, emb_dim, dense_dim,
                                hidden, emb_cfg, ws.padded_rows,
                                max_len=max_len)
    kind = devices[0].device_kind
    peaks = _peaks(kind, cpu_smoke=devices[0].platform == "cpu")
    audit = {
        "flops_per_step": flops,
        "hbm_bytes_per_step": hbm,
        "step_seconds": dt / n_steps,
        "sync": "host read of the final loss ends every window",
    }
    if peaks is not None:
        peak_f, peak_b = peaks
        audit["peak_flops"] = peak_f
        audit["peak_hbm_bytes"] = peak_b
        audit["implied_mfu"] = flops / (dt / n_steps) / peak_f
        audit["implied_hbm_frac"] = hbm / (dt / n_steps) / peak_b
        audit["ok"] = (audit["implied_mfu"] <= 0.6
                       and audit["implied_hbm_frac"] <= 1.0)
    else:
        # CPU smoke only (_peaks raises for an unknown accelerator): the
        # numbers are not device numbers and there is nothing to audit
        audit["ok"] = True
        audit["no_peaks"] = "cpu smoke run: not a device measurement"
    from paddlebox_tpu.ops import pallas_kernels as _pk
    from paddlebox_tpu.utils.step_probe import push_floor_analysis
    # sparse-push floor: analytic per-stage bounds for THIS point's
    # geometry; the closure statement is finalized once the attribution
    # measures the real push stage (_enrich) — regressions then alarm
    # against the push's own physics, not just the chip peaks. PER-SHARD
    # geometry: the kernel/engine dispatch keys on rows_per_shard and
    # each shard pushes its local tokens, so the floor must model the
    # pass one chip actually performs (global rows would overstate the
    # update bytes n_shards-fold and could even flip the engine)
    premerged = tr.push_premerged(ws)
    push_floor = push_floor_analysis(
        emb_cfg, ws.rows_per_shard, batch * T // n_dev,
        n_split=config_flags.binned_push_splits, peaks=peaks,
        premerged=premerged,
        # the RECORDED per-batch dedup counters, not the tokens upper
        # bound: on premerged engines the fused floor scales with the
        # rows the lanes actually touch (capped at tokens — a foreign
        # counter bump can only tighten toward truth, never past it)
        unique_lanes=(min(measured_lanes, batch * T // n_dev)
                      if premerged and measured_lanes else None),
        table_width=quant.row_engine_width(ws.table))
    detail = {
        "device_kind": kind,
        "storage": storage,
        "dense_sync_mode": mode,
        # which merge engine the step compiled with — THE resolver's
        # verdict (resolve_push_engine), the same call the compiled
        # dispatch makes, so the record can never name an engine the
        # program does not contain. The engine dispatches per SHARD, so
        # the per-shard row count decides.
        "push_engine": tr.resolved_push_engine(ws),
        # measured per-batch unique lanes (per shard) from the recorded
        # dedup counters — what the floor above consumed (None = no
        # plan ran, floors fall back to the tokens bound)
        "unique_lanes_measured": measured_lanes,
        # which pull engine the step compiled with (trainer heuristic:
        # fused gather-pool for multi-hot/wide layouts — the mh4d32 and
        # d128 envelope points — unfused lookup+seqpool elsewhere)
        "pull_engine": tr.pull_engine,
        # which _bp_pack width-class path the push compiled with (None =
        # scatter engine, no pack; premerged points compile no reorder
        # at all) — the per-point record whose absence let the round-5
        # pack rewrite regress the headline unnoticed
        "pack_engine": _pk.pack_engine(
            emb_cfg, ws.rows_per_shard,
            premerged=tr._use_plan and tr._dedup_premerge(ws)),
        # deferred-push pipeline state (flags.push_overlap)
        "push_overlap": "on" if tr.push_overlap else "off",
        "steps_per_dispatch": ksd,
        # sharded-exchange identity: which table engine the point
        # compiled with, the push wire format its a2a rode, and the mesh
        # partition — recorded per point like pull/push/pack_engine
        "table_layout": tr.table_layout,
        "exchange_wire": tr.exchange_wire or "-",
        "table_shards": tr.n_shards,
        "devices": n_dev,
        "global_batch": batch,
        "steps": n_steps,
        "seconds": round(dt, 3),
        "window_seconds": [round(w, 3) for w in windows],
        "working_set_keys": n_keys,
        "loss_final": loss_v,
        "audit": audit,
        "push_floor": push_floor,
    }
    if return_ctx:
        # live handles for a later attribution pass (main runs it under
        # the print-always guard); the caller MUST drop these before the
        # matrix runs or the headline buffers stay resident
        return eps_chip, detail, {
            "tr": tr, "ws": ws, "staged0": staged[0],
            "step_seconds": dt / n_steps, "mode": mode, "n_dev": n_dev}
    return eps_chip, detail


def _attribute(tr, ws, staged0, step_seconds, small, tiny=False):
    """Stage attribution (log_for_profile's cal-split analogue,
    boxps_worker.cc:746-759). A failure is recorded in the artifact —
    the headline number must still print — and fails the exit code
    (_collect_errors)."""
    from paddlebox_tpu.utils.step_probe import attribute_step
    try:
        res = attribute_step(tr, ws, staged0, step_seconds,
                             k=2 if tiny else (4 if small else 24),
                             n_loop=3 if tiny else (10 if small else 100))
    except Exception as e:
        return {"error": repr(e)}
    _mark(f"stage attribution done (coverage {res['coverage']:.0%})")
    return res


def _synth_pass(schema, n_ex, num_slots, dense_slots, slot_space, seed,
                prev=None, overlap=0.9):
    """Vectorized synthetic SlotRecordBatch (pre-parsed pass data).

    With `prev`, ~`overlap` of tokens resample prev's keys (consecutive
    CTR passes share most of their working set) and the rest draw from a
    disjoint key window — the day-over-day churn."""
    from paddlebox_tpu.data.slot_record import SlotRecordBatch
    rng = np.random.default_rng(seed)
    sparse_values, sparse_offsets = [], []
    offs = np.arange(n_ex + 1, dtype=np.int64)  # one token per slot
    for s in range(num_slots):
        if prev is None:
            ids = rng.integers(0, slot_space, size=n_ex).astype(np.int64)
            ids |= np.int64(s + 1) << np.int64(40)  # slot-salted sign space
        else:
            pool = np.unique(prev.sparse_values[s])
            old = pool[rng.integers(0, len(pool), size=n_ex)]
            fresh = rng.integers(slot_space, 2 * slot_space,
                                 size=n_ex).astype(np.int64)
            fresh |= np.int64(s + 1) << np.int64(40)
            ids = np.where(rng.random(n_ex) < overlap, old, fresh)
        sparse_values.append(ids)
        sparse_offsets.append(offs.copy())
    float_values = [(rng.random(n_ex) < 0.25).astype(np.float32)]  # label
    float_values += [rng.normal(size=n_ex).astype(np.float32)
                     for _ in range(len(dense_slots))]
    return SlotRecordBatch(
        schema=schema, num=n_ex,
        sparse_values=sparse_values, sparse_offsets=sparse_offsets,
        float_values=float_values,
        ins_id=np.zeros(n_ex, dtype=np.uint64),
        search_id=np.zeros(n_ex, dtype=np.uint64),
        rank=np.zeros(n_ex, dtype=np.int32),
        cmatch=np.zeros(n_ex, dtype=np.int32))


def e2e_bench(small: bool):
    """Two full train_pass calls from pre-built archives (parse excluded;
    translate + H2D + step + metrics + pass boundaries included)."""
    import tempfile

    from paddlebox_tpu.config import flags as config_flags
    # device_step_bench's matrix points mutate this trace-time flag (the
    # bf16-push point leaves it at 1); the e2e semantics must stay the
    # startup config regardless of run order
    config_flags.binned_push_splits = _startup_splits()

    import jax
    from paddlebox_tpu.data import DataFeedSchema, SlotDataset
    from paddlebox_tpu.data.archive import read_archive, write_archive
    from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.train import Trainer, TrainerConfig

    n_dev = len(jax.devices())
    num_slots, emb_dim, dense_dim = 26, 16, 13
    batch = (256 if small else 8192) * n_dev
    steps_per_pass = 4 if small else 56
    n_ex = steps_per_pass * batch
    slot_space = 4096 if small else 650_000     # → ~8.4M unique keys big
    schema = DataFeedSchema.ctr(num_sparse=num_slots, num_float=dense_dim,
                                batch_size=batch, max_len=1)
    dense_slots = [s for s in schema.float_slots if s.name != "label"]

    with tempfile.TemporaryDirectory(prefix="pbtpu_bench_") as tmp:
        paths = []
        rec = None
        for p in range(2):
            rec = _synth_pass(schema, n_ex, num_slots, dense_slots,
                              slot_space, seed=p, prev=rec)
            path = os.path.join(tmp, f"pass{p}.pbar")
            write_archive(path, rec)
            paths.append(path)
        _mark("e2e archives written")
        passes = [read_archive(p, schema) for p in paths]
    _mark("e2e archives loaded (pre-parsed, excluded from timing)")

    store = HostEmbeddingStore(EmbeddingConfig(dim=emb_dim,
                                               optimizer="adagrad",
                                               learning_rate=0.05))
    mesh = make_mesh(n_dev)
    tr = Trainer(DeepFMModel(num_slots=num_slots, emb_dim=emb_dim,
                             dense_dim=dense_dim, hidden=(400, 400, 400)),
                 store, schema, mesh,
                 TrainerConfig(global_batch_size=batch,
                               auc_buckets=1 << 16))
    pass_secs, stats = [], []
    all_ds = []
    for rec in passes:
        ds = SlotDataset(schema)
        ds.records = rec
        all_ds.append(ds)
    for p, ds in enumerate(all_ds):
        tr.timers.reset()
        t0 = time.perf_counter()
        # train_pass(preload_keys=...) would overlap pass p+1's
        # working-set build with pass p's training (PreLoadIntoMemory +
        # BeginFeedPass); not measured on this code — the bench reports
        # the un-overlapped pass (ROADMAP S1 owns the on/off cells)
        out = tr.train_pass(ds)
        wall = time.perf_counter() - t0
        pass_secs.append(wall)
        m = tr.feed_mgr
        # main-thread wall accounting: queue wait ("read", starvation =
        # host-bound), step dispatch ("train"), AUC, the post-loop drain
        # (where async-dispatched device time lands), and the boundary
        # (now terminated by a real D2H sync). "translate" runs on the
        # pack thread and OVERLAPS — reported but not in coverage.
        stage = {s: round(tr.timers.total[s], 3)
                 for s in ("read", "train", "auc", "drain", "translate")}
        from paddlebox_tpu.config import flags as _flags
        main_stages = ["read", "train", "auc", "drain"]
        if _flags.prefetch_batches <= 0:
            # synchronous pack: translate runs on the MAIN thread and is
            # part of the wall, not an overlapped background stage
            main_stages.append("translate")
        accounted = (sum(stage[s] for s in main_stages)
                     + m.last_boundary_seconds)
        bsec = m.last_boundary_seconds
        stats.append({
            "steps": out["steps"],
            "loss_mean": round(out["loss_mean"], 4),
            "working_set_keys": int(len(ds.unique_keys())),
            "boundary_h2d_bytes": m.last_h2d_bytes,
            "boundary_d2h_bytes": m.last_d2h_bytes,
            "fresh_rows": m.last_fresh_rows,
            "reused_rows": m.last_reused_rows,
            "boundary_seconds": round(bsec, 3),
            "boundary_split": {k: round(v, 3)
                               for k, v in m.last_boundary_split.items()},
            "boundary_h2d_mbps": round(
                m.last_h2d_bytes / bsec / 1e6, 1) if bsec > 0.01 else None,
            "stage_seconds": stage,
            "wall_coverage": round(accounted / wall, 3),
        })
        _mark(f"e2e pass {p} done in {pass_secs[-1]:.1f}s "
              f"({stats[-1]['working_set_keys']} keys, coverage "
              f"{stats[-1]['wall_coverage']:.0%})")
    # eval_pass rides the same background pack pipeline as train_pass
    # (VERDICT r3 weak #6); record its wall against the train pass so a
    # regression to a serialized host path is visible
    t0 = time.perf_counter()
    ev = tr.eval_pass(all_ds[-1])
    eval_wall = time.perf_counter() - t0
    _mark(f"e2e eval pass done in {eval_wall:.1f}s (auc {ev['auc']:.3f})")
    eps_chip = n_ex / min(pass_secs) / n_dev
    return eps_chip, {
        "eval_pass_seconds": round(eval_wall, 2),
        "eval_vs_train_wall": round(eval_wall / min(pass_secs), 3),
        "examples_per_pass": n_ex,
        "emb_dim": emb_dim,
        "pass_seconds": [round(s, 2) for s in pass_secs],
        "passes": stats,
        "note": "translate+H2D+step+metrics+boundaries; parse excluded "
                "(pre-built archive); translate+pack+plan+H2D overlap "
                "on a background thread (flags.prefetch_batches); "
                "'read' wait + 'drain' are where host<->device stalls "
                "surface",
    }


def host_bench(small: bool) -> dict:
    """Host-path timings with no device in any timed window (run in a
    child process pinned to the CPU backend; see _enrich).

    The reference treats parse as the pass bottleneck (dozens of parser
    threads, flags.cc:480-484) and times download/parse/shuffle per pass
    (box_wrapper.h:896-899). These are what each host stage costs on
    THIS host, and the feed ceiling they impose on a chip at the headline
    geometry."""
    import time as _t

    from paddlebox_tpu.data import DataFeedSchema
    from paddlebox_tpu.data.archive import read_archive, write_archive
    from paddlebox_tpu.data.parser import _parse_python
    from paddlebox_tpu.embedding import (EmbeddingConfig,
                                         HostEmbeddingStore,
                                         PassWorkingSet)
    from paddlebox_tpu.native import key_index
    from paddlebox_tpu.native import slot_parser_binding as native_parser
    from paddlebox_tpu.parallel import make_mesh

    rng = np.random.default_rng(0)
    num_slots, dense_dim = 26, 13
    batch = 256 if small else 8192
    n_keys = 1 << (14 if small else 19)
    schema = DataFeedSchema.ctr(num_sparse=num_slots, num_float=dense_dim,
                                batch_size=batch, max_len=1)
    out: dict = {
        "host_cores": os.cpu_count(),
        "note": "pure host timings; this machine has "
                f"{os.cpu_count()} core(s), so thread counts >1 "
                "measure oversubscription here — per-thread numbers "
                "extrapolate to the reference's many-core ingest hosts",
    }

    def best_of(fn, reps=3):
        w = []
        for _ in range(reps):
            t0 = _t.perf_counter()
            fn()
            w.append(_t.perf_counter() - t0)
        return min(w)

    # --- parse: MultiSlot text -> SlotRecordBatch (native vs python) ---
    n_lines = 200 if small else 20_000
    ids = rng.integers(1, 1 << 50, size=(n_lines, num_slots))
    dn = rng.random((n_lines, dense_dim))
    lab = (rng.random(n_lines) < 0.25).astype(int)
    lines = []
    for i in range(n_lines):
        parts = [f"1 {lab[i]}"]
        parts += [f"1 {v:.6f}" for v in dn[i]]
        parts += [f"1 {k}" for k in ids[i]]
        lines.append(" ".join(parts))
    buf = ("\n".join(lines) + "\n").encode()
    mb = len(buf) / 1e6
    parse = {"input_mb": round(mb, 2), "lines": n_lines}
    if native_parser.available():
        for nt in (1, 2):
            dt = best_of(lambda: native_parser.parse_buffer(
                buf, schema, n_threads=nt))
            parse[f"native_t{nt}_mb_per_s"] = round(mb / dt, 1)
            parse[f"native_t{nt}_ex_per_s"] = round(n_lines / dt)
    py_lines = lines[:max(1, n_lines // 10)]
    dt = best_of(lambda: _parse_python(py_lines, schema,
                                       with_ins_id=False), reps=2)
    parse["python_ex_per_s"] = round(len(py_lines) / dt)
    parse["python_mb_per_s"] = round(
        mb * len(py_lines) / n_lines / dt, 2)
    out["parse"] = parse

    # --- archive read (the pre-parsed fast path the e2e bench feeds on)
    import tempfile
    rec = _synth_pass(schema, n_lines, num_slots,
                      [s for s in schema.float_slots
                       if s.name != "label"],
                      n_keys, seed=0)
    with tempfile.TemporaryDirectory(prefix="pbtpu_host_") as tmp:
        pth = os.path.join(tmp, "p.pbar")
        write_archive(pth, rec)
        amb = os.path.getsize(pth) / 1e6
        dt = best_of(lambda: read_archive(pth, schema))
        out["archive_read"] = {"mb": round(amb, 2),
                               "mb_per_s": round(amb / dt, 1),
                               "ex_per_s": round(n_lines / dt)}

    # --- working-set build + translate + binned-push plan ---
    keys = rng.choice(1 << 50, n_keys, replace=False).astype(np.uint64)
    store = HostEmbeddingStore(EmbeddingConfig(dim=8, optimizer="adagrad",
                                               learning_rate=0.05))
    mesh = make_mesh(1)
    t0 = _t.perf_counter()
    ws = PassWorkingSet.begin_pass(store, keys, mesh)
    dt = _t.perf_counter() - t0
    out["ws_build"] = {
        "keys": n_keys, "keys_per_s": round(n_keys / dt),
        "note": "store fetch/init + sort + pad + CPU staging "
                "(device_put on the cpu backend = memcpy)"}

    T = num_slots
    raw = rng.choice(keys, size=(batch, T))
    mask = np.ones((batch, T), dtype=bool)
    dt = best_of(lambda: ws.translate(raw, mask), reps=5)
    tokens = batch * T
    out["translate"] = {
        "tokens": tokens, "seconds": round(dt, 5),
        "tokens_per_s": round(tokens / dt),
        "backend": "native" if ws._tindex.is_native else "searchsorted"}
    t_translate = dt

    idx = ws.translate(raw, mask)
    from paddlebox_tpu.ops import pallas_kernels
    geom = pallas_kernels.binned_push_geometry(store.cfg, ws.padded_rows)
    t_plan = 0.0
    if geom is not None:
        dt = best_of(lambda: key_index.block_plan(
            idx.reshape(-1), geom[0], geom[1]), reps=5)
        t_plan = dt
        out["block_plan"] = {
            "tokens": tokens, "seconds": round(dt, 5),
            "tokens_per_s": round(tokens / dt),
            "native": key_index.native_available()}

    # --- the derived line: what this host could FEED a chip at the
    # headline geometry (translate + plan per batch on one pack thread;
    # parse/archive are per-pass upstream stages with their own ceilings
    # above). flags.prefetch_batches pipelines pack against device
    # compute, so the ceiling scales ~linearly with pack threads on a
    # multicore host.
    per_batch = t_translate + t_plan
    out["derived_max_feed_eps_per_chip"] = round(batch / per_batch)
    out["derived_note"] = (
        f"one pack thread on this host sustains batch={batch} every "
        f"{per_batch*1e3:.1f}ms = {batch/per_batch:,.0f} ex/s of "
        "translate+plan; compare against THIS artifact's recorded "
        "headline eps (feed_margin_vs_headline) — no hardcoded "
        "device-step constants here")

    # --- superstep A/B: steps_per_dispatch exists for DISPATCH-BOUND
    # hosts. The CPU backend is one — record the win (or its absence)
    # here, in the regime the knob targets (ROADMAP D3 owns the pair on
    # the chip).
    try:
        from paddlebox_tpu.data import SlotDataset
        from paddlebox_tpu.models import DeepFMModel
        from paddlebox_tpu.train import Trainer, TrainerConfig
        ss_schema = DataFeedSchema.ctr(num_sparse=4, num_float=1,
                                       batch_size=64, max_len=1)
        n_ex = 64 * (8 if small else 64)
        rec = _synth_pass(ss_schema, n_ex, 4,
                          [s for s in ss_schema.float_slots
                           if s.name != "label"], 2000, seed=1)
        ab = {}
        for k in (1, 4):
            st = HostEmbeddingStore(EmbeddingConfig(
                dim=4, optimizer="adagrad", learning_rate=0.05))
            trk = Trainer(DeepFMModel(num_slots=4, emb_dim=4,
                                      dense_dim=1, hidden=(16,)),
                          st, ss_schema, make_mesh(1),
                          TrainerConfig(global_batch_size=64,
                                        steps_per_dispatch=k))
            ds = SlotDataset(ss_schema)
            ds.records = rec
            trk.train_pass(ds)             # warmup pass (compiles)
            t0 = _t.perf_counter()
            trk.train_pass(ds)
            ab[f"k{k}_pass_seconds"] = round(_t.perf_counter() - t0, 3)
        ab["speedup_k4"] = round(ab["k1_pass_seconds"]
                                 / ab["k4_pass_seconds"], 3)
        out["superstep_ab"] = ab
    except Exception as e:
        out["superstep_ab"] = {"error": repr(e)}
    return out


def elastic_drill(small: bool, tiny: bool = False) -> dict:
    """Elastic rank-loss recovery drill (ISSUE 6): measure what a world
    shrink actually costs. A 2-member elastic world trains one pass on
    its shard, "loses" rank 1, and runs the REAL recovery path — world
    re-formation (generation seal over a FileStore), coordinated resume
    election, restore, and the cursor-preserving re-route of the departed
    rank's records — timed as ``world_resize_seconds``; the continued
    pass then trains the whole working set at N−1 and its throughput is
    recorded as the ``elastic_degraded`` matrix point (gated by
    BENCH_BEST.json like every other point). The numbers answer the two
    operator questions: how long is the pass stalled by a rank loss, and
    how fast does the shrunk world train."""
    import tempfile as _tempfile
    import time as _t
    from paddlebox_tpu.data import DataFeedSchema, SlotDataset
    from paddlebox_tpu.distributed.resilience import (ElasticWorld,
                                                      coordinated_resume)
    from paddlebox_tpu.distributed.store import FileStore
    from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.train import Trainer, TrainerConfig

    bs = 64
    n_ex = bs * (4 if tiny else (16 if small else 128))
    schema = DataFeedSchema.ctr(num_sparse=4, num_float=1, batch_size=bs,
                                max_len=1)
    rec = _synth_pass(schema, n_ex, 4,
                      [s for s in schema.float_slots if s.name != "label"],
                      2000, seed=3)
    store = HostEmbeddingStore(EmbeddingConfig(dim=8, optimizer="adagrad",
                                               learning_rate=0.05))
    tr = Trainer(DeepFMModel(num_slots=4, emb_dim=8, dense_dim=1,
                             hidden=(16,)),
                 store, schema, make_mesh(1),
                 TrainerConfig(global_batch_size=bs))
    box = BoxPS(store)
    with _tempfile.TemporaryDirectory() as td:
        from paddlebox_tpu.utils.pass_ckpt import PassCheckpointer
        ckpt = PassCheckpointer(os.path.join(td, "snaps"), keep_last_n=2)
        world = ElasticWorld(
            FileStore(os.path.join(td, "store"), namespace="bench",
                      poll_s=0.005),
            0, [0, 1], heartbeat_interval_s=0.2, lost_after_s=600,
            stall_after_s=600, reform_timeout_s=0.25)
        ds = SlotDataset(schema)
        ds.records = rec
        shards = ds.member_shards(2)
        ds_mine = SlotDataset(schema)
        ds_mine.records = shards[0]
        box.begin_pass()
        tr.train_pass(ds_mine)
        box.end_pass(checkpointer=ckpt, trainer=tr, dataset=ds)
        # rank 1 "dies" at the pass boundary: re-form, re-elect, re-route
        t0 = _t.perf_counter()
        world2 = world.reform([1])
        cursor = coordinated_resume(ckpt, tr, world2.collectives, box=box)
        routed = ds.reroute_records(shards[1], world2.world)
        resize_s = _t.perf_counter() - t0
        # degraded continuation: the shrunk world carries the whole
        # working set (warm, like steady state after a shrink)
        ds_all = SlotDataset(schema)
        ds_all.records = rec
        box.begin_pass()
        tr.train_pass(ds_all)          # warmup (compiles at new shapes)
        box.end_pass(trainer=tr)
        box.begin_pass()
        t1 = _t.perf_counter()
        out = tr.train_pass(ds_all)
        seconds = _t.perf_counter() - t1
        box.end_pass(trainer=tr)
        world2.close()
    eps = out["steps"] * bs / max(seconds, 1e-9)
    return {"examples_per_sec_per_chip": round(eps, 1),
            "world_resize_seconds": round(resize_s, 4),
            "resumed_pass": None if cursor is None else cursor["pass_id"],
            "rerouted_records": sum(int(r.num) for r in routed
                                    if r is not None),
            "world": 1}


def serving_drill(small: bool, tiny: bool = False) -> dict:
    """Train→publish→serve drill (ISSUE 7): the online loop's three
    operator numbers, measured on the REAL path. A one-pass job publishes
    a base artifact (timed as ``publish_seconds`` — plane snapshot, int8
    cold-row quantization, CRC-chained manifest, donefile announce), a
    ServingServer tails + loads it, and a BatchingFrontend drives the
    predictor at concurrency while pass 2's delta publish hot-swaps
    underneath the traffic — ``swap_pause_ms`` (the atomic handle rebind
    requests actually see) and the served ``p50_ms``/``p99_ms`` land as
    gate-held matrix points (latency metrics compare lower-is-better off
    the ``_ms``/``_seconds`` suffix). Zero request failures across the
    swap is asserted — the drill fails loudly rather than record a tail
    latency from a broken loop."""
    import tempfile as _tempfile
    import threading as _threading
    import time as _t
    from paddlebox_tpu.data import DataFeedSchema, SlotDataset
    from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.serving import (BatchingFrontend, ServingPublisher,
                                       ServingServer)
    from paddlebox_tpu.train import Trainer, TrainerConfig

    bs = 64
    n_ex = bs * (2 if tiny else (8 if small else 64))
    schema = DataFeedSchema.ctr(num_sparse=4, num_float=1, batch_size=bs,
                                max_len=1)
    rec = _synth_pass(schema, n_ex, 4,
                      [s for s in schema.float_slots if s.name != "label"],
                      2000, seed=11)
    store = HostEmbeddingStore(EmbeddingConfig(dim=8, optimizer="adagrad",
                                               learning_rate=0.05))
    model = DeepFMModel(num_slots=4, emb_dim=8, dense_dim=1, hidden=(16,))
    tr = Trainer(model, store, schema, make_mesh(1),
                 TrainerConfig(global_batch_size=bs))
    box = BoxPS(store)
    ds = SlotDataset(schema)
    ds.records = rec
    with _tempfile.TemporaryDirectory() as td:
        root = os.path.join(td, "serve")
        pub = ServingPublisher(root, model, schema, publish_base_every=8,
                               quant="int8", hot_top_k=64)
        box.begin_pass()
        tr.train_pass(ds)
        info = box.end_pass(trainer=tr, publisher=pub)["publish"]
        srv = ServingServer(root, poll_s=0.01)
        if srv.poll_once() != 1:
            raise RuntimeError("server failed to load the published base")
        pb = next(iter(ds.batches(batch_size=bs)))
        lc, lw, _ = schema.float_split_cols("label")
        floats = np.concatenate(
            [pb.floats[:, :lc], pb.floats[:, lc + lw:]], axis=1)
        ids64 = pb.ids.astype(np.uint64)
        fe = BatchingFrontend(srv, max_batch=32, max_wait_s=0.002).start()
        try:
            # warmup OUTSIDE the window: the first batch compiles the
            # frontend's one fixed shape
            for f in [fe.submit(ids64[i], pb.mask[i], floats[i])
                      for i in range(32)]:
                f.result(timeout=300)
            # pass 2 trains + publishes its delta while the frontend is
            # live; the swap itself lands mid-traffic below
            box.begin_pass()
            tr.train_pass(ds)
            d_info = box.end_pass(trainer=tr, publisher=pub)["publish"]
            n_req = bs * (4 if tiny else (16 if small else 64))
            futs: list = []

            def _load():
                r = np.random.default_rng(5)
                while len(futs) < n_req:
                    i = int(r.integers(0, bs))
                    futs.append(fe.submit(ids64[i], pb.mask[i],
                                          floats[i]))

            t_load = _threading.Thread(target=_load, daemon=True)
            t0 = _t.perf_counter()
            t_load.start()
            _t.sleep(0.01)                   # traffic in flight
            if srv.poll_once() != 1:         # THE hot-swap, under load
                raise RuntimeError("delta hot-swap did not apply")
            t_load.join(timeout=600)
            done = [f.result(timeout=300) for f in list(futs)]
            serve_s = _t.perf_counter() - t0
            st = fe.stats()
        finally:
            fe.stop()
            srv.stop()
    if srv.active is None or srv.active.version != 2:
        raise RuntimeError("drill ended off the delta version")
    if st.get("failures"):
        raise RuntimeError(f"{st['failures']} requests failed across the "
                           f"hot-swap — the latency numbers are not "
                           f"trustable")
    return {"publish_seconds": round(info["seconds"], 4),
            "delta_publish_seconds": round(d_info["seconds"], 4),
            "publish_bytes": int(info["bytes"]),
            "swap_pause_ms": round(max(srv._last_swap_pause_ms, 1e-6), 6),
            "p50_ms": st["p50_ms"], "p99_ms": st["p99_ms"],
            "serve_eps": round(len(done) / max(serve_s, 1e-9), 1),
            "requests": len(done), "failures": int(st["failures"]),
            "swapped_to_version": srv.active.version}


def serving_split_drill(small: bool, tiny: bool = False) -> dict:
    """Version-split serving drill (ISSUE 19): shadow-mode scoring on the
    REAL two-version path. Pass 1 publishes the stable version, pass 2's
    publish is HELD as the candidate (``flags.serving_shadow``) while
    every request scores on both — the drill records the served tail
    latency under the doubled predictor work (``shadow_p99_ms``,
    gate-held lower-is-better), joins the pass's labels back to both
    versions' scores for the per-version AUC + candidate-vs-stable
    score-KL, commits a serving window record, schema-checks it, and
    runs the doctor's three serving rules over it — the whole
    capture→record→diagnose loop the chip run will lean on."""
    import tempfile as _tempfile
    import time as _t
    from paddlebox_tpu.config import flags as _flags
    from paddlebox_tpu.data import DataFeedSchema, SlotDataset
    from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.monitor import doctor as doctor_lib
    from paddlebox_tpu.monitor import flight as flight_lib
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.serving import ServingPublisher, ServingServer
    from paddlebox_tpu.train import Trainer, TrainerConfig

    bs = 64
    n_ex = bs * (2 if tiny else (8 if small else 32))
    schema = DataFeedSchema.ctr(num_sparse=4, num_float=1, batch_size=bs,
                                max_len=1)
    rec = _synth_pass(schema, n_ex, 4,
                      [s for s in schema.float_slots if s.name != "label"],
                      2000, seed=13)
    store = HostEmbeddingStore(EmbeddingConfig(dim=8, optimizer="adagrad",
                                               learning_rate=0.05))
    model = DeepFMModel(num_slots=4, emb_dim=8, dense_dim=1, hidden=(16,))
    tr = Trainer(model, store, schema, make_mesh(1),
                 TrainerConfig(global_batch_size=bs))
    box = BoxPS(store)
    ds = SlotDataset(schema)
    ds.records = rec
    prev_shadow = _flags.serving_shadow
    try:
        _flags.serving_shadow = True
        with _tempfile.TemporaryDirectory() as td:
            root = os.path.join(td, "serve")
            pub = ServingPublisher(root, model, schema,
                                   publish_base_every=8, quant="f32",
                                   hot_top_k=64)
            box.begin_pass()
            tr.train_pass(ds)
            box.end_pass(trainer=tr, publisher=pub)
            srv = ServingServer(root, poll_s=0.01)
            if srv.poll_once() != 1:
                raise RuntimeError(
                    "server failed to load the published base")
            # pass 2's publish lands as the HELD candidate
            box.begin_pass()
            tr.train_pass(ds)
            box.end_pass(trainer=tr, publisher=pub)
            if srv.poll_once() != 1 or srv.candidate is None:
                raise RuntimeError("candidate did not load under shadow")
            pb = next(iter(ds.batches(batch_size=bs)))
            lc, lw, _ = schema.float_split_cols("label")
            floats = np.concatenate(
                [pb.floats[:, :lc], pb.floats[:, lc + lw:]], axis=1)
            ids64 = pb.ids.astype(np.uint64)
            labels = pb.floats[:, lc:lc + lw].reshape(-1)
            # warmup OUTSIDE the measured window: first batch compiles
            srv.predict(ids64, pb.mask, floats)
            srv.observe_labels(labels)
            srv.commit_window(force=True)
            n_batches = 2 if tiny else (8 if small else 32)
            t0 = _t.perf_counter()
            for _ in range(n_batches):
                srv.predict(ids64, pb.mask, floats)
                srv.observe_labels(labels)
            serve_s = _t.perf_counter() - t0
            fields = srv.commit_window(force=True)
            srv.stop()
    finally:
        _flags.serving_shadow = prev_shadow
    full_rec = {"ts": _t.time(), "type": "serving_record",
                "name": "serving_window", "pass_id": None, "step": None,
                "phase": -1, "thread": "bench", "fields": fields}
    schema_errors = flight_lib.validate_serving_record(full_rec)
    rep = doctor_lib.diagnose(servings=[full_rec])
    rules = {r["rule"]: r["status"] for r in rep["rules"]
             if r["rule"] in ("version-regression", "p99-burn",
                              "swap-regression")}
    by_role = {e.get("role"): (vid, e)
               for vid, e in (fields.get("versions") or {}).items()}
    stable = by_role.get("stable", (None, {}))
    cand = by_role.get("candidate", (None, {}))
    return {"shadow": True,
            "stable_version": stable[0], "candidate_version": cand[0],
            "requests": int(fields["requests"]),
            "shadow_p50_ms": float(fields["p50_ms"]),
            "shadow_p99_ms": float(fields["p99_ms"]),
            "serve_eps": round(n_batches * bs / max(serve_s, 1e-9), 1),
            "stable_auc": stable[1].get("auc"),
            "candidate_auc": cand[1].get("auc"),
            "score_kl": cand[1].get("score_kl"),
            "record_schema_errors": schema_errors,
            "doctor_rules": rules}


def serving_fleet_drill(small: bool, tiny: bool = False) -> dict:
    """Fleet resilience drill (ISSUE 20): two in-process replicas behind
    the health-aware router with ONE injected slow — the routed tail
    under hedging is the gate (``p99_ms``: the hedge must cut the slow
    replica's latency out of the fleet tail), a version publish is timed
    to EVERY replica serving it (``swap_convergence_s``,
    lower-is-better), the promotion governor is fed a regressing
    candidate window and must HOLD, and the composed fleet window record
    is schema-checked and run through the doctor's fleet-degraded rule
    (which must fire on the recorded hold)."""
    import random as _random
    import tempfile as _tempfile
    import threading as _threading
    import time as _t
    from concurrent.futures import Future as _Future
    from paddlebox_tpu.config import flags as _flags
    from paddlebox_tpu.data import DataFeedSchema, SlotDataset
    from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.monitor import doctor as doctor_lib
    from paddlebox_tpu.monitor import flight as flight_lib
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.serving import ServingPublisher
    from paddlebox_tpu.serving.fleet import (FleetReplicaServer,
                                             LocalReplica,
                                             PromotionGovernor)
    from paddlebox_tpu.serving.frontend import BatchingFrontend
    from paddlebox_tpu.serving.router import Router
    from paddlebox_tpu.train import Trainer, TrainerConfig

    class _SlowReplica:
        """LocalReplica wrapper with a mutable injected service delay —
        the drill's 'one replica went slow' fault. The delayed future is
        marked running so a hedge-loser cancel fails and the router's
        discard accounting is the path exercised."""

        def __init__(self, inner):
            self._inner = inner
            self.name = inner.name
            self.delay_s = 0.0

        @property
        def quarantined(self):
            return self._inner.quarantined

        @property
        def inflight(self):
            return self._inner.inflight

        def health(self):
            return self._inner.health()

        def promote(self):
            return self._inner.promote()

        def submit(self, ids, mask, dense=None):
            inner_fut = self._inner.submit(ids, mask, dense)
            delay = float(self.delay_s)
            if delay <= 0:
                return inner_fut
            out = _Future()
            out.set_running_or_notify_cancel()

            def _later(f):
                def _fire():
                    try:
                        out.set_result(f.result())
                    except Exception as e:  # noqa: BLE001 — relay, not
                        # swallow: the inner failure must surface on the
                        # delayed future exactly as it would undelayed
                        out.set_exception(e)
                _threading.Timer(delay, _fire).start()
            inner_fut.add_done_callback(_later)
            return out

    bs = 64
    n_ex = bs * (2 if tiny else (8 if small else 32))
    schema = DataFeedSchema.ctr(num_sparse=4, num_float=1, batch_size=bs,
                                max_len=1)
    rec = _synth_pass(schema, n_ex, 4,
                      [s for s in schema.float_slots if s.name != "label"],
                      2000, seed=17)
    store = HostEmbeddingStore(EmbeddingConfig(dim=8, optimizer="adagrad",
                                               learning_rate=0.05))
    model = DeepFMModel(num_slots=4, emb_dim=8, dense_dim=1, hidden=(16,))
    tr = Trainer(model, store, schema, make_mesh(1),
                 TrainerConfig(global_batch_size=bs))
    box = BoxPS(store)
    ds = SlotDataset(schema)
    ds.records = rec
    prev_promote = _flags.serving_auto_promote
    slow_ms = 150.0
    try:
        _flags.serving_auto_promote = True
        with _tempfile.TemporaryDirectory() as td:
            root = os.path.join(td, "serve")
            pub = ServingPublisher(root, model, schema,
                                   publish_base_every=8, quant="f32",
                                   hot_top_k=64)
            box.begin_pass()
            tr.train_pass(ds)
            box.end_pass(trainer=tr, publisher=pub)
            servers = [FleetReplicaServer(root, poll_s=0.01)
                       for _ in range(2)]
            for s in servers:
                if s.poll_once() != 1:
                    raise RuntimeError(
                        "replica failed to load the published base")
            fes = [BatchingFrontend(s, max_batch=32,
                                    max_wait_s=0.002).start()
                   for s in servers]
            fast = LocalReplica("replica-0", servers[0], fes[0])
            slow = _SlowReplica(
                LocalReplica("replica-1", servers[1], fes[1]))
            router = Router([fast, slow], timeout_s=10.0,
                            health_ttl_s=0.2, hedge_factor=1.5,
                            hedge_min_count=8, window_s=60.0,
                            rng=_random.Random(7))
            pb = next(iter(ds.batches(batch_size=bs)))
            lc, lw, _ = schema.float_split_cols("label")
            floats = np.concatenate(
                [pb.floats[:, :lc], pb.floats[:, lc + lw:]], axis=1)
            ids64 = pb.ids.astype(np.uint64)
            # compile OUTSIDE the router: the first request per replica
            # pays the predict compile (seconds) — routed through, it
            # would land in the hedge-threshold window and a threshold
            # derived off a compile-scale p99 never hedges anything
            for fe in fes:
                fe.submit(ids64[0], pb.mask[0], floats[0]).result(
                    timeout=300)
            # warmup through the router: fill its latency window so the
            # hedge threshold derives from the healthy-fleet p99
            n_warm = 12 if tiny else (16 if small else 32)
            for i in range(n_warm):
                router.score(ids64[i % bs], pb.mask[i % bs],
                             floats[i % bs])
            # inject the slow replica, then the measured phase: hedging
            # must keep the routed tail well under the injected delay
            slow.delay_s = slow_ms / 1e3
            n_req = 16 if tiny else (32 if small else 96)
            t0 = _t.perf_counter()
            for i in range(n_req):
                router.score(ids64[i % bs], pb.mask[i % bs],
                             floats[i % bs])
            serve_s = _t.perf_counter() - t0
            slow.delay_s = 0.0
            # publish the next version and time fleet-wide convergence:
            # the wall from donefile append to BOTH replicas serving it
            box.begin_pass()
            tr.train_pass(ds)
            box.end_pass(trainer=tr, publisher=pub)
            t0 = _t.perf_counter()
            deadline = t0 + 60.0
            while _t.perf_counter() < deadline:
                for s in servers:
                    if s.active is None or s.active.version != 2:
                        s.poll_once()
                if all(s.active is not None and s.active.version == 2
                       for s in servers):
                    break
            swap_convergence_s = _t.perf_counter() - t0
            if any(s.active is None or s.active.version != 2
                   for s in servers):
                raise RuntimeError("fleet never converged on version 2")
            # the governor leg: a window where the candidate regresses
            # hard on AUC must HOLD promotion fleet-wide
            gov = PromotionGovernor([fast, slow], windows=2)
            decision = gov.observe({
                "ts": _t.time(), "requests": 2 * bs,
                "candidate_version": 3,
                "versions": {
                    "2": {"role": "stable", "auc": 0.74, "requests": bs},
                    "3": {"role": "candidate", "auc": 0.52,
                          "requests": bs, "score_kl": 0.7}}})
            rs = router.stats()
            healthy = sum(
                1 for s in servers
                if str(s.health().get("status", "")).startswith("ok"))
            for fe in fes:
                fe.stop()
            for s in servers:
                s.stop()
    finally:
        _flags.serving_auto_promote = prev_promote
    fields = {"window_s": round(serve_s, 3), "replicas": 2,
              "healthy": healthy, "quarantined": 0,
              "requests": int(rs["requests"]), "sheds": int(rs["sheds"]),
              "retries": int(rs["retries"]), "hedges": int(rs["hedges"]),
              "hedges_won": int(rs["hedges_won"]), "restarts": 0,
              "promote_holds": int(gov.promote_holds),
              "p50_ms": float(rs.get("p50_ms", 0.0)),
              "p99_ms": float(rs.get("p99_ms", 0.0))}
    full_rec = {"ts": _t.time(), "type": "fleet_record",
                "name": "fleet_window", "pass_id": None, "step": None,
                "phase": -1, "thread": "bench", "fields": fields}
    schema_errors = flight_lib.validate_fleet_record(full_rec)
    rep = doctor_lib.diagnose(fleets=[full_rec])
    rules = {r["rule"]: r["status"] for r in rep["rules"]
             if r["rule"] == "fleet-degraded"}
    return {"replicas": 2, "healthy": healthy,
            "requests": int(rs["requests"]),
            "p50_ms": float(rs.get("p50_ms", 0.0)),
            "p99_ms": float(rs.get("p99_ms", 0.0)),
            "slow_replica_ms": slow_ms,
            "hedges": int(rs["hedges"]),
            "hedges_won": int(rs["hedges_won"]),
            "retries": int(rs["retries"]), "sheds": int(rs["sheds"]),
            "failures": int(rs["failures"]),
            "serve_eps": round(n_req / max(serve_s, 1e-9), 1),
            "swap_convergence_s": round(swap_convergence_s, 4),
            "swapped_to_version": 2,
            "promote_decision": decision,
            "promote_holds": int(gov.promote_holds),
            "record_schema_errors": schema_errors,
            "doctor_rules": rules}


def spill_drill(small: bool, tiny: bool = False) -> dict:
    """Tiered-table drill (ISSUE 11): a working set >= 10x the RAM
    row-cache budget through the sharded+spill path — 2 hash-partitioned
    shards, each a SpillEmbeddingStore (memmap row file + capped RAM
    cache), the configuration ``flags.table_tiering=spill`` selects.

    Four passes of skewed traffic (a hot set re-read every pass under a
    rotating cold scan that floods every direct-mapped slot — the
    Parallax skew argument) run TWICE on identical key sequences: once
    under the show-count-weighted admission policy (``freq``, the
    product) and once under the legacy direct-mapped last-wins install
    (``direct``, the baseline bench_spill.py records). The drill records
    both hot-tier hit rates side by side — the acceptance bar is the
    policy's rate beating the baseline's on the same traffic — plus the
    admission/eviction counters, the dedup ratio of the simulated token
    stream, and the cold-tier fetch throughput (gate-held)."""
    import tempfile as _tf
    import time as _t
    from paddlebox_tpu.embedding import (EmbeddingConfig,
                                         ShardedEmbeddingStore)
    from paddlebox_tpu.embedding.tiering import (end_pass_rebalance,
                                                 shard_store_factory,
                                                 spill_stats)

    n_shards = 2
    cache_rows = 128 if tiny else (1 << 11 if small else 1 << 15)
    budget = n_shards * cache_rows          # total RAM hot-tier rows
    n_keys = budget * 10                    # the >=10x working set
    n_hot = budget // 2
    n_cold = budget * 2                     # per pass: floods every slot
    passes = 4
    cfg = EmbeddingConfig(dim=8, optimizer="adagrad", learning_rate=0.05)

    def key_window(lo, hi):
        return (np.arange(lo, hi, dtype=np.uint64)
                * np.uint64(2654435761) + np.uint64(1))

    hot = key_window(0, n_hot)
    results: dict = {}
    with _tf.TemporaryDirectory(prefix="pbtpu_spill_drill_") as td:
        for policy in ("freq", "direct"):
            ss = ShardedEmbeddingStore(
                cfg, n_shards,
                store_factory=shard_store_factory(
                    tiering="spill", cache_rows=cache_rows,
                    spill_dir=os.path.join(td, policy), policy=policy))
            # build: the whole key space lands on the spill tier first
            # (LoadSSD2Mem's table, bigger than the hot tier by 10x)
            chunk = 1 << 18
            for lo in range(0, n_keys, chunk):
                ss.lookup_or_init(key_window(lo, min(n_keys, lo + chunk)))
            hot_hits_last = 0
            fetch_s = 0.0
            for p in range(passes):
                cold_lo = n_hot + (p * n_cold) % (n_keys - n_hot - n_cold)
                cold = key_window(cold_lo, cold_lo + n_cold)
                h0 = sum(s.cache_hits for s in ss._shards)
                t0 = _t.perf_counter()
                rows = ss.lookup_or_init(hot)
                hot_hits_last = sum(s.cache_hits
                                    for s in ss._shards) - h0
                cr = ss.lookup_or_init(cold)
                fetch_s = _t.perf_counter() - t0
                # train-like write-back: hot rows accumulate real shows
                # (the admission weight), cold ones one impression each
                rows[:, 0] += 4.0
                ss.write_back(hot, rows)
                cr[:, 0] += 1.0
                ss.write_back(cold, cr)
                end_pass_rebalance(ss)      # the pass-boundary re-score
            st = spill_stats(ss)
            results[policy] = {
                "hot_hit_rate": round(hot_hits_last / n_hot, 4),
                "hit_rate": st["hit_rate"],
                "admitted": st["admitted"], "evicted": st["evicted"],
                "spill_bytes": st["spill_bytes"],
                "fetch_keys_per_s": round((n_hot + n_cold) / fetch_s),
            }
    f, d = results["freq"], results["direct"]
    # simulated token stream of the last pass: hot keys appear 4x (their
    # show increment), cold once — what the exchange would dedup
    tokens = 4 * n_hot + n_cold
    return {
        "table_tiering": "spill", "table_shards": n_shards,
        "tier_policy": "freq", "cache_rows": int(cache_rows),
        "cache_budget_rows": int(budget),
        "working_set_keys": int(n_keys),
        "ws_over_cache": round(n_keys / budget, 1),
        "passes": passes,
        "dedup_ratio": round((n_hot + n_cold) / tokens, 4),
        "hot_hit_rate": f["hot_hit_rate"],
        "direct_hot_hit_rate": d["hot_hit_rate"],
        "hit_rate": f["hit_rate"], "direct_hit_rate": d["hit_rate"],
        "admitted": f["admitted"], "evicted": f["evicted"],
        "direct_evicted": d["evicted"],
        "spill_bytes": f["spill_bytes"],
        "fetch_keys_per_s": f["fetch_keys_per_s"],
    }


def spill_assoc_drill(small: bool, tiny: bool = False) -> dict:
    """spill_assoc point: set-associative RAM-cache geometry
    (``flags.spill_cache_assoc``) vs the direct-mapped baseline on an
    ADVERSARIAL colliding stream — a hot set built so ``assoc`` rows
    land on every set index. Direct-mapped, those rows evict each other
    on every pass (conflict misses — the whole set is one slot); N-way,
    they coexist and the hot re-read holds. Both variants replay the
    IDENTICAL key/write sequence and the drill byte-compares the row
    files at the end: geometry is placement only, never a math change
    (the ``parity`` field the dryrun gate asserts)."""
    import tempfile as _tf
    import time as _t
    from paddlebox_tpu.embedding import EmbeddingConfig
    from paddlebox_tpu.embedding.spill_store import SpillEmbeddingStore

    cache_rows = 128 if tiny else (1 << 11 if small else 1 << 14)
    assoc = 4
    n_keys = cache_rows * 8
    passes = 3
    cfg = EmbeddingConfig(dim=8, optimizer="adagrad", learning_rate=0.05)

    def key_window(lo, hi):
        return (np.arange(lo, hi, dtype=np.uint64)
                * np.uint64(2654435761) + np.uint64(1))

    # row ids are assigned in first-lookup order, so building the whole
    # space with key_window(0, n_keys) pins id i to key i — the hot set
    # below then holds `assoc` ids per direct-mapped slot j (ids
    # j, j+C, j+2C, j+3C all map to slot j mod C) and exactly fills the
    # N-way set j under the set-major geometry
    hot_ids = np.concatenate(
        [np.arange(cache_rows // assoc) + i * cache_rows
         for i in range(assoc)])
    results: dict = {}
    with _tf.TemporaryDirectory(prefix="pbtpu_assoc_drill_") as td:
        for name, policy, ways in (("assoc", "freq", assoc),
                                   ("direct", "direct", 1)):
            st = SpillEmbeddingStore(
                cfg, spill_dir=os.path.join(td, name),
                cache_rows=cache_rows, initial_capacity=n_keys + 16,
                tier_policy=policy, cache_assoc=ways)
            chunk = 1 << 18
            for lo in range(0, n_keys, chunk):
                st.lookup_or_init(key_window(lo, min(n_keys, lo + chunk)))
            hot = key_window(0, n_keys)[hot_ids]
            hot_hits_last = 0
            fetch_s = 1e-9
            for p in range(passes):
                cold_lo = 4 * cache_rows + (p * cache_rows) % (
                    3 * cache_rows)
                cold = key_window(cold_lo, cold_lo + cache_rows)
                h0 = st.cache_hits
                t0 = _t.perf_counter()
                rows = st.lookup_or_init(hot)
                hot_hits_last = st.cache_hits - h0
                cr = st.lookup_or_init(cold)
                fetch_s = _t.perf_counter() - t0
                rows[:, 0] += 4.0
                st.write_back(hot, rows)
                cr[:, 0] += 1.0
                st.write_back(cold, cr)
                st.tier_end_pass()
            st._rows.flush()
            results[name] = {
                "hit_rate": round(hot_hits_last / len(hot_ids), 4),
                "conflicts": int(st.conflict_misses),
                "fetch_keys_per_s": round(
                    (len(hot_ids) + len(cold)) / fetch_s),
                "rows": np.array(st._rows[:st._n], np.float32),
            }
    a, d = results["assoc"], results["direct"]
    return {
        "cache_rows": int(cache_rows), "assoc": int(assoc),
        "working_set_keys": int(n_keys),
        "hot_set_rows": int(len(hot_ids)),
        "passes": passes,
        "assoc_hit_rate": a["hit_rate"],
        "direct_hit_rate": d["hit_rate"],
        "conflict_misses_assoc": a["conflicts"],
        "conflict_misses_direct": d["conflicts"],
        "parity": bool(np.array_equal(a.pop("rows"), d.pop("rows"))),
        "fetch_keys_per_s": a["fetch_keys_per_s"],
    }


def boundary_drill(small: bool, tiny: bool = False) -> dict:
    """boundary_incremental point (ISSUE 14): the same key stream through
    (a) the incremental + overlapped feed — resident reuse, background
    staging consumed at the boundary, stale-delta patching after a
    shrink, spill-tier madvise prefetch — and (b) the full-rebuild feed
    (``flags.incremental_feed=False``, no staging, the resident set
    dropped every boundary), with a pure-eviction ``shrink`` between
    passes so every boundary crosses a store mutation (the case that
    used to force the full rebuild even with reuse on). Records
    boundary_seconds + the build/h2d/spill_fault_in split for both
    variants and proves the two land bit-identical store bytes."""
    import tempfile as _tf
    import jax.numpy as jnp
    from paddlebox_tpu.config import flags as config_flags
    from paddlebox_tpu.embedding import EmbeddingConfig
    from paddlebox_tpu.embedding.feed_pass import FeedPassManager
    from paddlebox_tpu.embedding.spill_store import SpillEmbeddingStore

    # tiny keeps the SMALL working set: below ~20k rows the full-rebuild
    # baseline costs less than the combine's fixed jit dispatch on CPU
    # and the point would measure dispatch overhead, not the feed
    n_keys = 40_000 if (tiny or small) else 200_000
    churn = n_keys // 10                 # 90% overlap pass to pass
    passes = 5
    timed_from = 2        # pass-1 boundary compiles the combine/patch
    #                       jits once; steady-state boundaries gate
    cfg = EmbeddingConfig(dim=8, optimizer="adagrad", learning_rate=0.05)

    def key_window(lo, hi):
        return np.sort(np.arange(lo, hi, dtype=np.uint64)
                       * np.uint64(2654435761) + np.uint64(1))

    def run(incremental: bool, spill_dir: str) -> dict:
        config_flags.incremental_feed = incremental
        store = SpillEmbeddingStore(cfg, spill_dir=spill_dir,
                                    cache_rows=max(256, n_keys // 8))
        mgr = FeedPassManager(store)
        bsec, split = 0.0, {"build": 0.0, "h2d": 0.0,
                            "spill_fault_in": 0.0}
        stats = {"fresh_rows": 0, "reused_rows": 0, "patched_rows": 0,
                 "stale_rows": 0}
        for p in range(passes):
            keys = key_window(p * churn, p * churn + n_keys)
            ws = mgr.begin_pass(keys)
            if p >= timed_from:          # steady state (see timed_from)
                bsec += mgr.last_boundary_seconds
                for k in split:
                    split[k] += mgr.last_boundary_split.get(k, 0.0)
            if p:                        # pass-1 full build is identical
                stats["fresh_rows"] += mgr.last_fresh_rows
                stats["reused_rows"] += mgr.last_reused_rows
                stats["patched_rows"] += mgr.last_patched_rows
                stats["stale_rows"] += mgr.last_stale_rows
            # train: touch every key; the cold tail (keys absent from
            # the next pass) zeroes its show counter so the boundary
            # shrink evicts exactly it — a pure store-side mutation
            # every single boundary crosses
            idx = ws.translate(keys)
            t = np.asarray(ws.table).copy()
            staying = np.isin(keys, key_window((p + 1) * churn,
                                               (p + 1) * churn + n_keys),
                              assume_unique=True)
            t[idx[staying], 0] += 1.0
            t[idx[~staying], 0] = 0.0
            t[idx, 2] += 0.5
            mgr.end_pass(ws, jnp.asarray(t))
            if incremental:
                # overlap: stage the next pass BEFORE the shrink, so the
                # boundary exercises the staged-patch delta plane
                mgr.begin_feed_pass(key_window((p + 1) * churn,
                                               (p + 1) * churn + n_keys))
            # pure-eviction hygiene shrink (decay=1.0): flushes the
            # device tier, then evicts this pass's cold tail — a
            # mutation whose reach the stale log can prove
            store.shrink(min_show=0.5, decay=1.0)
        mgr.drop()
        all_keys = key_window(passes * churn, passes * churn + n_keys
                              - churn)
        rows = store.peek_rows(all_keys)
        return {"bsec": bsec, "split": split, "stats": stats,
                "rows": rows, "prefetched": int(store.prefetched_rows)}

    with _tf.TemporaryDirectory(prefix="pbtpu_boundary_drill_") as td:
        startup = config_flags.incremental_feed
        try:
            inc = run(True, os.path.join(td, "inc"))
            full = run(False, os.path.join(td, "full"))
        finally:
            config_flags.incremental_feed = startup
    parity = bool(np.array_equal(inc["rows"], full["rows"]))
    return {
        "working_set_keys": int(n_keys), "passes": passes,
        "overlap_frac": round(1 - churn / n_keys, 2),
        "boundary_seconds": round(inc["bsec"], 4),
        "full_rebuild_seconds": round(full["bsec"], 4),
        "speedup": round(full["bsec"] / inc["bsec"], 2)
        if inc["bsec"] > 0 else None,
        "boundary_split": {k: round(v, 4)
                           for k, v in inc["split"].items()},
        "full_boundary_split": {k: round(v, 4)
                                for k, v in full["split"].items()},
        # the incremental variant fetches almost nothing from disk, so
        # the readahead shows on the FULL-rebuild side (its every
        # boundary re-faults the working set through the spill tier)
        "prefetched_rows": inc["prefetched"],
        "full_prefetched_rows": full["prefetched"],
        "parity": parity,
        **{k: int(v) for k, v in inc["stats"].items()},
    }


def adaptive_wire_drill(small: bool, tiny: bool = False) -> dict:
    """Drifting-sparsity adaptive-wire drill (ISSUE 16): the REAL
    trainer on a 2-shard mesh with flags.exchange_adaptive on, fed a
    key stream whose duplication depth drifts across the wire regimes —
    duplication-heavy passes (tiny key pool: the merged f32 sum
    amortizes over many contributions) then unique-heavy passes (wide
    pool: the wire bytes dominate and the narrow wire wins). The
    controller must flip the wire within the hysteresis bound, and the
    pass-summed modeled wire cost of the ADAPTIVE run must be <= every
    fixed wire's cost on the same stream (``adaptive_best`` — the
    deterministic gate; real-chip wall-clock wire A/B stays queued for
    the consolidated chip round). Throughput rides along gate-held like
    the other sharded points."""
    import time as _t
    from paddlebox_tpu import monitor
    from paddlebox_tpu.config import flags as config_flags
    from paddlebox_tpu.data import DataFeedSchema, SlotDataset
    from paddlebox_tpu.embedding import (EmbeddingConfig,
                                         HostEmbeddingStore, exchange)
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.train import Trainer, TrainerConfig

    bs = 64
    steps = 2 if tiny else (4 if small else 8)
    num_slots = 4
    schema = DataFeedSchema.ctr(num_sparse=num_slots, num_float=1,
                                batch_size=bs, max_len=1)
    dense = [s for s in schema.float_slots if s.name != "label"]
    # The drift: duplication-heavy passes draw from a single hot key per
    # slot (merge depth ~32, deep in the f32 regime — the per-lane lane
    # cost amortizes over dozens of duplicates) and carry 6x the
    # traffic — the busy head of a stream, where the exact wide wire
    # wins outright; the tail's unique-heavy passes (pool 16x the
    # stream, depth ~1) are bytes-bound, where the narrow wire wins.
    # 4 heavy + 5 light passes: the hysteresis window (2 suboptimal
    # passes after the drift) must cost less than a pinned wire loses
    # across the other seven.
    phases = ["dup"] * 4 + ["uni"] * 5

    def pass_dataset(kind, seed):
        n_ex = bs * steps * (6 if kind == "dup" else 1)
        space = 1 if kind == "dup" else 16 * n_ex
        ds = SlotDataset(schema)
        ds.records = _synth_pass(schema, n_ex, num_slots, dense, space,
                                 seed=seed)
        return ds

    def build_trainer():
        store = HostEmbeddingStore(EmbeddingConfig(
            dim=8, optimizer="adagrad", learning_rate=0.05))
        return Trainer(DeepFMModel(num_slots=num_slots, emb_dim=8,
                                   dense_dim=1, hidden=(16,)),
                       store, schema, make_mesh(2),
                       TrainerConfig(global_batch_size=bs)), store

    saved = (config_flags.table_layout, config_flags.exchange_wire,
             config_flags.exchange_adaptive)
    try:
        config_flags.table_layout = "sharded"
        config_flags.exchange_wire = "f32"
        config_flags.exchange_adaptive = True
        tr, store = build_trainer()
        cfg = store.cfg
        per_pass = []
        total = {w: 0.0 for w in exchange.WIRES}
        adaptive_cost = 0.0
        examples = 0
        t0 = _t.perf_counter()
        for i, kind in enumerate(phases):
            active = tr.exchange_wire
            snap0 = monitor.STATS.snapshot()
            out = tr.train_pass(pass_dataset(kind, seed=100 + i))
            snap = monitor.STATS.snapshot()
            toks = int(snap.get("exchange.tokens", 0)
                       - snap0.get("exchange.tokens", 0))
            uniq = int(snap.get("exchange.unique_lanes", 0)
                       - snap0.get("exchange.unique_lanes", 0))
            examples += out["steps"] * bs
            adaptive_cost += exchange.wire_cost(cfg, toks, uniq, active)
            for w in exchange.WIRES:
                total[w] += exchange.wire_cost(cfg, toks, uniq, w)
            per_pass.append({"kind": kind, "wire": active,
                             "tokens": toks, "unique": uniq})
        seconds = _t.perf_counter() - t0
        switches = tr._wire_controller.switches
        hysteresis = tr._wire_controller.hysteresis
    finally:
        (config_flags.table_layout, config_flags.exchange_wire,
         config_flags.exchange_adaptive) = saved
    wire_path = [p["wire"] for p in per_pass]
    return {
        "examples_per_sec_per_chip": round(
            examples / max(seconds, 1e-9) / 2, 1),
        "passes": per_pass,
        "wire_path": wire_path,
        "switches": int(switches),
        "hysteresis": int(hysteresis),
        # the gate: summed modeled cost, adaptive vs each fixed wire
        "adaptive_cost": round(adaptive_cost, 1),
        "fixed_costs": {w: round(c, 1) for w, c in total.items()},
        "adaptive_best": bool(
            switches >= 1
            and all(adaptive_cost <= c + 1e-6 for c in total.values())),
        "table_shards": 2,
        "simulated": True,
    }


def self_healing_drill(small: bool, tiny: bool = False) -> dict:
    """Self-healing runtime drill (ISSUE 18): the doctor-driven
    remediation loop and the elastic shrink→grow round trip, end to end
    on the REAL paths. Part one trains a tiny job with resident reuse
    OFF and a seeded pass-boundary wall: the doctor's boundary-wall rule
    fires over the drill's own flight records, the RemediationController
    applies ``enable-incremental-feed`` under the parity guard, and the
    before/after counter deltas land in the (schema-validated) flight
    record — then the drill's telemetry stream is fed back through the
    doctor CLI, whose ``--fail-on warn`` must gate (exit 1) on the same
    finding CI would see. Part two forms a 2-member elastic world, loses
    rank 1, and a joiner thread re-enters via ``ElasticWorld.admit``
    while ``poll_grow`` consumes heartbeat-gap evidence: the round trip
    must converge back to a FULL world — degraded gauge cleared,
    ``world_grow`` event carrying ``joined=[1]``."""
    import contextlib
    import io
    import tempfile as _tempfile
    import threading as _threading
    import time as _t
    from paddlebox_tpu import monitor
    from paddlebox_tpu.config import flags as _flags, set_flags
    from paddlebox_tpu.data import DataFeedSchema, SlotDataset
    from paddlebox_tpu.distributed.resilience import ElasticWorld
    from paddlebox_tpu.distributed.store import FileStore
    from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.monitor import doctor as doctor_lib
    from paddlebox_tpu.monitor.flight import validate_flight_record
    from paddlebox_tpu.monitor.hub import STATS
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.runtime.remediation import RemediationController
    from paddlebox_tpu.train import Trainer, TrainerConfig

    out: dict = {}
    hub = monitor.hub()
    was_enabled = hub.enabled
    ms = monitor.MemorySink()
    hub.enable(ms)
    f0 = (_flags.incremental_feed, _flags.self_healing,
          _flags.self_healing_sustain)
    set_flags(incremental_feed=False, self_healing=True,
              self_healing_sustain=1)
    try:
        with _tempfile.TemporaryDirectory() as td:
            # -- part one: finding -> guarded apply -> flight record ------
            bs = 64
            n_ex = bs * (2 if tiny else (8 if small else 32))
            schema = DataFeedSchema.ctr(num_sparse=4, num_float=1,
                                        batch_size=bs, max_len=1)
            rec = _synth_pass(schema, n_ex, 4,
                              [s for s in schema.float_slots
                               if s.name != "label"], 2000, seed=11)
            store = HostEmbeddingStore(EmbeddingConfig(
                dim=8, optimizer="adagrad", learning_rate=0.05))
            tr = Trainer(DeepFMModel(num_slots=4, emb_dim=8, dense_dim=1,
                                     hidden=(16,)),
                         store, schema, make_mesh(1),
                         TrainerConfig(global_batch_size=bs))
            box = BoxPS(store)
            ctl = tr.enable_self_healing()
            ds = SlotDataset(schema)
            ds.records = rec
            # findings are fed from a diagnosis over the DRILL's own
            # flight records (feed_report, the world-view path): the
            # process-global flight ring may carry earlier bench passes
            # whose reuse counters would mask this run's symptom
            my_flights: list = []
            applied = after = None
            flight_errs: list = ["unvalidated"]
            for _ in range(4):
                box.begin_pass()
                tr.train_pass(ds)
                # the seeded wall: a boundary account dominating the
                # tiny pass is the rule's trigger — the seconds are
                # synthetic, the decision path is not
                monitor.hub().record_train(boundary_seconds=30.0)
                ctl.feed_report(doctor_lib.diagnose(flights=my_flights))
                res = box.end_pass(trainer=tr)
                my_flights.append(res["flight_record"])
                healed = res.get("remediation")
                if applied is None:
                    if healed and healed.get("status") == "applied":
                        applied = healed
                        flight_errs = validate_flight_record(
                            res["flight_record"])
                elif healed and "after" in healed:
                    after = healed
                    break
            out["applied"] = applied
            out["after_keys"] = sorted((after or {}).get("after") or {})
            out["flight_schema_errors"] = flight_errs
            out["flag_flipped"] = bool(_flags.incremental_feed)
            out["remediation_events"] = len(ms.find("remediation_applied"))
            # the CI gate sees what the runtime did to itself: the same
            # stream through the doctor CLI must trip --fail-on warn
            tele = os.path.join(td, "telemetry")
            os.makedirs(tele)
            with open(os.path.join(tele, "events.jsonl"), "w") as f:
                for r in ms.records:
                    f.write(json.dumps(r, default=str) + "\n")
            rep_out = io.StringIO()
            with contextlib.redirect_stdout(rep_out):
                out["doctor_fail_on_warn"] = doctor_lib.main(
                    [tele, "--json", "--fail-on", "warn"])
                out["doctor_fail_on_critical"] = doctor_lib.main(
                    [tele, "--json", "--fail-on", "critical"])
            rep = json.loads(rep_out.getvalue().splitlines()[0])
            out["doctor_found"] = sorted(f["rule"]
                                         for f in rep["findings"])
            # -- part two: shrink -> admit -> poll_grow round trip --------
            wkw = dict(heartbeat_interval_s=0.05, lost_after_s=30.0,
                       stall_after_s=60.0, reform_timeout_s=2.0,
                       initial_world=2)
            spath = os.path.join(td, "world")
            w0 = ElasticWorld(FileStore(spath, namespace="heal",
                                        poll_s=0.01), 0, [0, 1], **wkw)
            t0 = _t.perf_counter()
            w1 = w0.reform([1])           # rank 1 lost: degraded gen 1
            out["degraded_after_shrink"] = STATS.snapshot().get(
                "resilience.degraded")
            jres: dict = {}
            jerr: list = []

            def _joiner():
                try:
                    w = ElasticWorld.admit(
                        FileStore(spath, namespace="heal", poll_s=0.01),
                        1, timeout_s=60.0, **wkw)
                    jres["gen"], jres["members"] = w.gen, w.members
                    w.collectives.barrier("post_grow")
                    w.close()
                except BaseException as e:   # surfaced via joiner_errors
                    jerr.append(repr(e))

            jt = _threading.Thread(target=_joiner)
            jt.start()
            gctl = RemediationController()
            hbgap = {"rule": "heartbeat-gap", "severity": "critical",
                     "summary": "drill", "suggestion": "",
                     "evidence": {"degraded": True, "world_size": 1}}
            w2 = w1
            deadline = _t.monotonic() + 90.0
            while w2 is w1 and _t.monotonic() < deadline:
                w2, _cur = gctl.poll_grow(w1, findings=[hbgap])
            if w2 is not w1:
                w2.collectives.barrier("post_grow")
            round_trip = _t.perf_counter() - t0
            jt.join(timeout=60.0)
            out["degraded_after_grow"] = STATS.snapshot().get(
                "resilience.degraded")
            grows = ms.find("world_grow")
            out.update(
                round_trip_seconds=round(round_trip, 4),
                grow_gen=w2.gen, grow_members=list(w2.members),
                joiner_gen=jres.get("gen"),
                joiner_members=jres.get("members"),
                joiner_errors=jerr,
                world_grow_joined=(grows[-1]["fields"]["joined"]
                                   if grows else None))
            w2.close()
    finally:
        set_flags(incremental_feed=f0[0], self_healing=f0[1],
                  self_healing_sustain=f0[2])
        if was_enabled:
            # detach only the drill's sink; the caller's sinks stay
            with hub._lock:
                hub._sinks = tuple(s for s in hub._sinks if s is not ms)
        else:
            hub.disable()
    return out


def _run_sharded_probe(small: bool, tiny: bool = False) -> dict:
    """Run the sharded-exchange matrix points in a 2-virtual-device CPU
    subprocess (``--sharded-probe``): a single-device environment cannot
    host an in-process multi-shard mesh, and the backend's device count
    is fixed at init. The probe's numbers are simulated (CPU), but the
    FIELDS — table_layout, exchange_wire, table_shards, dedup ratio —
    are the product, and the eps values gate like-for-like because the
    probe environment is stable round over round."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2"
                        ).strip()
    env.pop("PBTPU_BENCH_SMALL", None)
    args = [sys.executable, os.path.abspath(__file__), "--sharded-probe"]
    if tiny:
        args.append("--tiny")
    elif small:
        args.append("--small")
    try:
        r = subprocess.run(args, capture_output=True, text=True, env=env,
                           timeout=1200)
        if r.returncode != 0:
            return {"error": r.stderr[-500:]}
        return _cpu_child_result(r.stdout)
    except Exception as e:
        return {"error": repr(e)}


def _cpu_child_result(stdout: str) -> dict:
    """The one JSON line a CPU child printed. A chip belongs to one
    process, and this parent holds it: a child is safe only while it is
    pinned to the CPU, so it reports its platform and the parent
    refuses anything else."""
    out = json.loads(stdout.strip().splitlines()[-1])
    if out.get("platform") != "cpu":
        return {"error": f"child ran on platform "
                         f"{out.get('platform')!r}, not pinned to the CPU"}
    return out


def sharded_probe_main() -> int:
    """Subprocess entry for the sharded-exchange matrix points (see
    _run_sharded_probe). Prints ONE JSON line."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddlebox_tpu import monitor
    tiny = "--tiny" in sys.argv
    small = "--small" in sys.argv or tiny
    out: dict = {"simulated": True, "devices": len(jax.devices()),
                 "platform": jax.devices()[0].platform, "points": {}}
    for mname, w in (("sharded_wire_f32", "f32"),
                     ("sharded_wire_bf16", "bf16"),
                     ("sharded_wire_int8", "int8")):
        snap0 = monitor.STATS.snapshot()
        try:
            eps, detail = device_step_bench(
                small, n_steps=2 if tiny else 3, n_windows=1, tiny=tiny,
                table_layout="sharded", exchange_wire=w)
            snap = monitor.STATS.snapshot()
            toks = snap.get("exchange.tokens", 0.0) - snap0.get(
                "exchange.tokens", 0.0)
            uniq = snap.get("exchange.unique_lanes", 0.0) - snap0.get(
                "exchange.unique_lanes", 0.0)
            out["points"][mname] = {
                "examples_per_sec_per_chip": round(eps, 1),
                "table_layout": detail["table_layout"],
                "exchange_wire": detail["exchange_wire"],
                "table_shards": detail["table_shards"],
                "pull_engine": detail["pull_engine"],
                "push_engine": detail["push_engine"],
                "dedup_ratio": (round(uniq / toks, 4) if toks else None),
                "simulated": True,
            }
        except Exception as e:
            out["points"][mname] = {"error": repr(e)}
    # the drifting-sparsity adaptive point: same 2-device mesh, but the
    # wire is the CONTROLLER's to pick — the point is the proof that
    # per-pass re-costing beats every pinned wire on a stream whose
    # dedup depth drifts (the fixed points above are its baselines)
    try:
        out["points"]["adaptive_wire"] = adaptive_wire_drill(
            small, tiny=tiny)
    except Exception as e:
        out["points"]["adaptive_wire"] = {"error": repr(e)}
    print(json.dumps(out), flush=True)
    return 0


def dryrun_main() -> int:
    """Fast CPU smoke of the bench's regression-gate, stage-attribution,
    and push-floor code paths (tier-1: exercised on every PR instead of
    only on-chip). Tiny geometry — the numbers are meaningless, the
    MACHINERY is the product: the attribution must produce a stage
    account, the floor must close (or abstain with a reason), and the
    gate must (a) skip bests recorded on foreign hardware, (b) TRIP on
    an injected synthetic >10% regression, (c) honor an explicit waiver
    note, (d) pass at parity. Prints ONE JSON line; exit 0 iff all four
    behaved."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddlebox_tpu import monitor
    from paddlebox_tpu.utils.step_probe import finalize_push_floor

    # telemetry rides the dryrun too: the artifact must embed the hub
    # summary (counters + any flight records) — asserted as a check below
    # (the sink is kept: the world-trace embed merges its record ring)
    dryrun_sink = monitor.MemorySink()
    monitor.hub().enable(dryrun_sink)
    checks: dict = {}
    eps, detail, ctx = device_step_bench(True, n_steps=2, n_windows=1,
                                         tiny=True, return_ctx=True)
    attr = _attribute(ctx["tr"], ctx["ws"], ctx["staged0"],
                                 ctx["step_seconds"], True, tiny=True)
    detail["stage_attribution"] = attr
    checks["attribution_ok"] = bool(attr.get("stages"))
    if "push_floor" in detail:
        finalize_push_floor(detail["push_floor"],
                            (attr.get("stages") or {}).get("sparse_push"))
    checks["floor_ok"] = "closed" in (detail.get("push_floor") or {})
    # the per-point push-engine record (ISSUE 13): every training point
    # must name the resolver's engine, and the floor must carry the
    # per-candidate-engine closure statements the doctor's push-floor
    # rule names concrete flags.push_engine forces from
    from paddlebox_tpu.ops import pallas_kernels as _pk_chk
    _pf = detail.get("push_floor") or {}
    checks["push_engine_recorded"] = (
        detail.get("push_engine") in _pk_chk.PUSH_ENGINES
        and isinstance(_pf.get("engines"), dict)
        and all(e in _pk_chk.PUSH_ENGINES for e in _pf["engines"])
        and all("closed" in v for v in _pf["engines"].values())
        and _pf.get("engine") == detail.get("push_engine"))
    ctx.clear()
    # elastic drill rides the dryrun too: the artifact schema must carry
    # world_resize_seconds and the degraded matrix point, and tier-1 must
    # catch drift in those fields before a chip run does
    try:
        drill = elastic_drill(True, tiny=True)
    except Exception as e:
        drill = {"error": repr(e)}
    detail.setdefault("matrix", {})["elastic_degraded"] = drill
    detail["world_resize_seconds"] = drill.get("world_resize_seconds")
    checks["elastic_fields"] = (
        isinstance(drill.get("world_resize_seconds"), float)
        and drill["world_resize_seconds"] > 0
        and isinstance(drill.get("examples_per_sec_per_chip"),
                       (int, float))
        and drill.get("resumed_pass") == 1
        and drill.get("rerouted_records", 0) > 0)
    # serving drill rides the dryrun too: the artifact schema must carry
    # publish/swap/latency points (and their lower-is-better gating must
    # hold) before a chip run records them
    try:
        sdrill = serving_drill(True, tiny=True)
    except Exception as e:
        sdrill = {"error": repr(e)}
    detail.setdefault("matrix", {})["serving"] = sdrill
    checks["serving_fields"] = (
        isinstance(sdrill.get("publish_seconds"), float)
        and sdrill["publish_seconds"] > 0
        and isinstance(sdrill.get("swap_pause_ms"), float)
        and sdrill["swap_pause_ms"] > 0
        and isinstance(sdrill.get("p99_ms"), (int, float))
        and sdrill.get("p99_ms", 0) > 0
        and sdrill.get("failures") == 0
        and sdrill.get("swapped_to_version") == 2)
    # version-split drill rides the dryrun too (ISSUE 19): the shadow
    # two-version loop must produce a schema-valid serving window record
    # with per-version AUC + score-KL attribution, and the doctor's
    # three serving rules must have evaluated it (version-regression off
    # real signal, not no-data) — before a chip round records the point
    try:
        ssd = serving_split_drill(True, tiny=True)
    except Exception as e:
        ssd = {"error": repr(e)}
    detail.setdefault("matrix", {})["serving_split"] = ssd
    _ssr = ssd.get("doctor_rules") or {}
    checks["serving_obs_fields"] = (
        ssd.get("record_schema_errors") == []
        and ssd.get("requests", 0) > 0
        and isinstance(ssd.get("shadow_p99_ms"), float)
        and ssd.get("shadow_p99_ms", 0) > 0
        and isinstance(ssd.get("stable_auc"), float)
        and isinstance(ssd.get("candidate_auc"), float)
        and isinstance(ssd.get("score_kl"), float)
        and ssd.get("score_kl", -1) >= 0
        and set(_ssr) == {"version-regression", "p99-burn",
                          "swap-regression"}
        and _ssr.get("version-regression") in ("quiet", "fired"))
    # fleet drill rides the dryrun too (ISSUE 20): two replicas behind
    # the router with one injected slow — hedging must keep the routed
    # tail under the injected delay with zero failed/shed requests, the
    # publish must converge fleet-wide, the governor must HOLD the
    # regressing candidate, and the composed fleet window record must be
    # schema-valid and fire the doctor's fleet-degraded rule (off the
    # recorded hold) — before a chip round records the point
    try:
        fsd = serving_fleet_drill(True, tiny=True)
    except Exception as e:
        fsd = {"error": repr(e)}
    detail.setdefault("matrix", {})["serving_fleet"] = fsd
    checks["fleet_fields"] = (
        fsd.get("record_schema_errors") == []
        and fsd.get("requests", 0) > 0
        and fsd.get("failures", -1) == 0
        and fsd.get("sheds", -1) == 0
        and isinstance(fsd.get("p99_ms"), float)
        and 0 < fsd.get("p99_ms", 0) < fsd.get("slow_replica_ms", 0)
        and fsd.get("hedges", 0) >= 1
        and fsd.get("hedges_won", 0) >= 1
        and isinstance(fsd.get("swap_convergence_s"), float)
        and fsd.get("swap_convergence_s", 0) > 0
        and fsd.get("swapped_to_version") == 2
        and fsd.get("promote_decision") == "hold"
        and fsd.get("promote_holds") == 1
        and (fsd.get("doctor_rules") or {}).get("fleet-degraded")
        == "fired")
    # tiered-table drill rides the dryrun too (ISSUE 11): the spill_10x
    # point must carry a working set >= 10x the RAM cache budget through
    # the sharded+spill path, with the tier identity + cache budget +
    # dedup ratio recorded, and the show-count-weighted admission policy
    # must beat the direct-mapped baseline's hot-tier hit rate on the
    # same traffic — before a chip round ever records the point
    try:
        spd = spill_drill(True, tiny=True)
    except Exception as e:
        spd = {"error": repr(e)}
    detail.setdefault("matrix", {})["spill_10x"] = spd
    checks["spill_fields"] = (
        spd.get("table_tiering") == "spill"
        and spd.get("table_shards") == 2
        and isinstance(spd.get("cache_rows"), int)
        and spd.get("working_set_keys", 0)
        >= 10 * spd.get("cache_budget_rows", 1 << 30)
        and isinstance(spd.get("dedup_ratio"), float)
        and 0 < spd["dedup_ratio"] <= 1
        and isinstance(spd.get("fetch_keys_per_s"), int)
        and spd.get("hot_hit_rate", 0.0)
        > spd.get("direct_hot_hit_rate", 1.0)
        and spd.get("evicted", 1 << 30) < spd.get("direct_evicted", 0))
    # set-associative geometry drill rides the dryrun too: on the
    # adversarial colliding stream the N-way cache must hold a hot hit
    # rate STRICTLY above direct-mapped at the same row budget, the
    # baseline must show the conflict misses that explain it, and the
    # two variants' row files must be byte-identical (geometry is
    # placement only) — before a chip round ever records the point
    try:
        sad = spill_assoc_drill(True, tiny=True)
    except Exception as e:
        sad = {"error": repr(e)}
    detail.setdefault("matrix", {})["spill_assoc"] = sad
    checks["assoc_fields"] = (
        sad.get("assoc") == 4
        and isinstance(sad.get("cache_rows"), int)
        and sad.get("parity") is True
        and sad.get("conflict_misses_direct", 0) > 0
        and isinstance(sad.get("assoc_hit_rate"), float)
        and isinstance(sad.get("direct_hit_rate"), float)
        and sad.get("assoc_hit_rate", 0.0)
        > sad.get("direct_hit_rate", 1.0)
        and isinstance(sad.get("fetch_keys_per_s"), int))
    # pass-boundary drill rides the dryrun too (ISSUE 14): the
    # incremental + overlapped feed must land bit-identical store bytes
    # AND a boundary wall strictly below the full-rebuild baseline on
    # the same key stream (with the 3-way split + the fresh/reused/
    # patched accounting recorded) — before a chip round ever records it
    try:
        bdrill = boundary_drill(True, tiny=True)
        if not (0 < bdrill.get("boundary_seconds", 0.0)
                < bdrill.get("full_rebuild_seconds", 0.0)):
            # the only wall-clock comparison in the dryrun: one
            # scheduler stall on a loaded runner can invert a ~1.5x
            # margin, so the timing race gets one retry — the
            # deterministic fields (parity, row accounting) never do
            bdrill = boundary_drill(True, tiny=True)
    except Exception as e:
        bdrill = {"error": repr(e)}
    detail.setdefault("matrix", {})["boundary_incremental"] = bdrill
    checks["boundary_fields"] = (
        bdrill.get("parity") is True
        and isinstance(bdrill.get("boundary_seconds"), float)
        and isinstance(bdrill.get("full_rebuild_seconds"), float)
        and 0 < bdrill["boundary_seconds"]
        < bdrill["full_rebuild_seconds"]
        and set(bdrill.get("boundary_split", {}))
        == {"build", "h2d", "spill_fault_in"}
        and bdrill.get("reused_rows", 0) > 0
        and bdrill.get("fresh_rows", 0) > 0
        and bdrill.get("patched_rows", 0) > 0
        # the readahead is advisory BY CONTRACT: require it only where
        # the platform has madvise at all (elsewhere the documented
        # fallback is the synchronous fault-in)
        and (bdrill.get("full_prefetched_rows", 0) > 0
             or not hasattr(__import__("mmap"), "MADV_WILLNEED")))
    # the self-healing runtime rides the dryrun too (ISSUE 18): the
    # remediation loop must CLOSE — a boundary-wall finding diagnosed
    # from the drill's own flight records auto-applies
    # enable-incremental-feed under the parity guard with the
    # before/after delta in a schema-valid flight record, the drill's
    # telemetry gates under doctor --fail-on warn, and the elastic
    # shrink->grow round trip converges back to a full world with the
    # degraded gauge cleared — before any chip run leans on it
    try:
        heal = self_healing_drill(True, tiny=True)
    except Exception as e:
        heal = {"error": repr(e)}
    detail.setdefault("matrix", {})["self_healing"] = heal
    _ap = heal.get("applied") or {}
    checks["self_healing_fields"] = (
        _ap.get("rule") == "boundary-wall"
        and _ap.get("action") == "enable-incremental-feed"
        and _ap.get("status") == "applied"
        and isinstance(_ap.get("before"), dict)
        and heal.get("after_keys") == ["feed_pass.fresh_rows",
                                       "feed_pass.reused_rows"]
        and heal.get("flight_schema_errors") == []
        and heal.get("flag_flipped") is True
        and heal.get("remediation_events", 0) >= 1
        and heal.get("doctor_fail_on_warn") == 1
        and heal.get("doctor_fail_on_critical") == 0
        and "boundary-wall" in (heal.get("doctor_found") or ())
        and heal.get("degraded_after_shrink") == 1.0
        and heal.get("degraded_after_grow") == 0.0
        and heal.get("grow_gen") == 2
        and heal.get("grow_members") == [0, 1]
        and heal.get("joiner_members") == [0, 1]
        and heal.get("joiner_errors") == []
        and heal.get("world_grow_joined") == [1])
    # sharded-exchange points ride the dryrun too (ISSUE 10): the 2-
    # virtual-device probe must produce the sharded matrix points with
    # table_layout / exchange_wire / table_shards recorded and a real
    # dedup ratio, before a multi-chip run ever records them
    probe = _run_sharded_probe(True, tiny=True)
    for pname, p in (probe.get("points") or {}).items():
        detail.setdefault("matrix", {})[pname] = p
    sp = probe.get("points") or {}
    f32p = sp.get("sharded_wire_f32") or {}
    bfp = sp.get("sharded_wire_bf16") or {}
    i8p = sp.get("sharded_wire_int8") or {}
    checks["sharded_fields"] = (
        f32p.get("table_layout") == "sharded"
        and f32p.get("exchange_wire") == "f32"
        and bfp.get("exchange_wire") == "bf16"
        and i8p.get("exchange_wire") == "int8"
        and f32p.get("push_engine") in _pk_chk.PUSH_ENGINES
        and f32p.get("table_shards") == 2
        and isinstance(f32p.get("examples_per_sec_per_chip"),
                       (int, float))
        and isinstance(bfp.get("examples_per_sec_per_chip"),
                       (int, float))
        and isinstance(i8p.get("examples_per_sec_per_chip"),
                       (int, float))
        and (f32p.get("dedup_ratio") or 0) > 0
        and "table_layout" in detail and "exchange_wire" in detail
        and "table_shards" in detail)
    # the adaptive point's CONTRACT (ISSUE 16): on the drifting-sparsity
    # stream the controller must actually flip (within its hysteresis
    # bound of the drift pass) and land a modeled wire cost no worse
    # than EVERY fixed wire — adaptive that loses to a pinned wire is a
    # regression, not a feature
    from paddlebox_tpu.embedding import exchange as _exch_chk
    adp = sp.get("adaptive_wire") or {}
    wpath = adp.get("wire_path") or []
    n_dup = sum(1 for k in (adp.get("passes") or [])
                if k.get("kind") == "dup")
    checks["adaptive_wire_fields"] = (
        adp.get("adaptive_best") is True
        and adp.get("switches", 0) >= 1
        and isinstance(adp.get("adaptive_cost"), (int, float))
        and set(adp.get("fixed_costs") or {}) == set(_exch_chk.WIRES)
        and len(wpath) == len(adp.get("passes") or ())
        # the flip lands within hysteresis passes of the dup->uni drift
        and 0 < n_dup < len(wpath)
        and wpath[:n_dup] == ["f32"] * n_dup
        and all(w == wpath[-1] for w in
                wpath[n_dup + adp.get("hysteresis", 2):])
        and wpath[-1] != "f32")
    g_lat = apply_regression_gate(
        {"serving.p99_ms": 10.0},
        {"device_kind": None, "metrics": {"serving.p99_ms": 5.0}}, "")
    checks["latency_gate_trips_lower_is_better"] = (
        not g_lat["ok"]
        and apply_regression_gate(
            {"serving.p99_ms": 4.0},
            {"device_kind": None,
             "metrics": {"serving.p99_ms": 5.0}}, "")["ok"])
    # bare _s is lower-is-better (the fleet's swap convergence) while
    # _per_s stays throughput — a slower convergence must trip, a faster
    # fetch rate must NOT read as a regression
    checks["convergence_gate_trips_lower_is_better"] = (
        not apply_regression_gate(
            {"serving_fleet.swap_convergence_s": 8.0},
            {"device_kind": None,
             "metrics": {"serving_fleet.swap_convergence_s": 2.0}},
            "")["ok"]
        and apply_regression_gate(
            {"spill_10x.fetch_keys_per_s": 9000.0},
            {"device_kind": None,
             "metrics": {"spill_10x.fetch_keys_per_s": 5000.0}},
            "")["ok"])
    # the world trace rides the dryrun too (ISSUE 15): a traced probe
    # pass whose publish flow pair must merge into a Chrome-trace summary
    # embedded in the artifact — asserted like doctor_embedded. The probe
    # runs the REAL machinery end to end (sampled begin_pass -> stamped
    # span -> flow points -> in-memory merge), not a synthetic dict.
    from paddlebox_tpu.config import flags as _flags
    from paddlebox_tpu.monitor import trace as trace_lib
    _prev_trace = _flags.trace
    try:
        _flags.trace = True
        hub = monitor.hub()
        hub.begin_pass(9001, owner="bench")
        with monitor.span("publish"):
            trace_lib.flow("publish", "v9001", role="src")
        trace_lib.flow("publish", "v9001", role="dst")
        hub.end_pass()
    finally:
        _flags.trace = _prev_trace
    _stream = trace_lib.records_to_stream(dryrun_sink.records)
    detail["world_trace"] = trace_lib.summarize(
        trace_lib.merge_streams([_stream], [0]))
    detail["telemetry"] = monitor.hub().summary()
    # the run-doctor verdict rides the dryrun too (ISSUE 12): the
    # artifact must embed a schema-valid report with the boundary-wall
    # rule evaluated and the dryrun's own push_floor fed to the
    # push-floor rule — asserted like telemetry_embedded
    from paddlebox_tpu.monitor import doctor as doctor_lib
    detail["doctor"] = doctor_lib.diagnose_hub(
        monitor.hub(), detail={"push_floor": detail.get("push_floor"),
                               "world_trace": detail["world_trace"]})
    monitor.hub().disable()
    checks["telemetry_embedded"] = (
        isinstance(detail["telemetry"], dict)
        and bool(detail["telemetry"].get("counters")))
    checks["doctor_embedded"] = (
        doctor_lib.validate_report(detail["doctor"]) == []
        and isinstance(detail["doctor"].get("verdict"), str)
        and any(r["rule"] == "boundary-wall"
                for r in detail["doctor"]["rules"])
        # the dryrun's push_floor must have reached the rule: its status
        # is fired/quiet/no-data depending on closure, but an evaluated
        # entry must exist
        and any(r["rule"] == "push-floor"
                for r in detail["doctor"]["rules"]))
    checks["trace_embedded"] = (
        detail["world_trace"].get("spans", 0) >= 1
        and any(e.get("kind") == "publish"
                for e in detail["world_trace"].get("flow_edges", []))
        and isinstance(detail["world_trace"].get("clock_offsets_s"),
                       dict)
        # the span-level data must have reached the doctor's cross-rank
        # rule (any status but an evaluated entry — like push-floor)
        and any(r["rule"] == "cross-rank-flow"
                for r in detail["doctor"]["rules"]))
    metrics = collect_gate_metrics(eps, detail)
    kind = detail.get("device_kind", "")
    committed = load_bench_best()
    g0 = apply_regression_gate(metrics, committed, kind)
    checks["gate_skips_foreign_hardware"] = (committed is None
                                            or bool(g0.get("skipped")))
    synth = {"device_kind": None,
             "metrics": {"headline_eps": eps * 2.0}}
    g1 = apply_regression_gate(metrics, synth, kind)
    checks["gate_trips_on_regression"] = not g1["ok"]
    g2 = apply_regression_gate(
        metrics, dict(synth, waivers={"headline_eps":
                                      "synthetic dryrun waiver"}), kind)
    checks["waiver_untrips"] = g2["ok"]
    g3 = apply_regression_gate(
        metrics, {"device_kind": None,
                  "metrics": {"headline_eps": eps}}, kind)
    checks["gate_ok_at_parity"] = g3["ok"]
    # the pblint gate must not be able to rot silently: the linter module
    # imports and carries its full rule set (the tier-1 lint-clean test
    # runs the CLI itself; this catches an import-time breakage even if
    # that test is ever skipped/filtered)
    try:
        from paddlebox_tpu.analysis import lint as lint_mod
        from paddlebox_tpu.analysis.rules import ALL_RULES
        checks["lint_importable"] = (callable(lint_mod.main)
                                     and len(ALL_RULES) >= 6)
    except Exception:
        checks["lint_importable"] = False
    ok = all(checks.values())
    print(json.dumps({
        "metric": "bench_dryrun", "ok": ok, "checks": checks,
        "value": round(eps, 1),
        "pack_engine": detail.get("pack_engine"),
        "push_engine": detail.get("push_engine"),
        "push_overlap": detail.get("push_overlap"),
        "push_floor_closed": (detail.get("push_floor") or {}
                              ).get("closed"),
        "doctor": detail["doctor"].get("verdict"),
        "world_resize_seconds": detail.get("world_resize_seconds"),
        "sharded": {k: f32p.get(k) for k in
                    ("table_layout", "exchange_wire", "table_shards",
                     "dedup_ratio", "error") if k in f32p},
        "serving": {k: sdrill.get(k) for k in
                    ("publish_seconds", "swap_pause_ms", "p99_ms",
                     "error") if k in sdrill},
        "serving_split": {k: ssd.get(k) for k in
                          ("shadow_p99_ms", "stable_auc",
                           "candidate_auc", "score_kl", "requests",
                           "doctor_rules", "error") if k in ssd},
        "serving_fleet": {k: fsd.get(k) for k in
                          ("p99_ms", "swap_convergence_s", "hedges",
                           "hedges_won", "promote_decision",
                           "doctor_rules", "error") if k in fsd},
        "spill": {k: spd.get(k) for k in
                  ("hot_hit_rate", "direct_hot_hit_rate",
                   "fetch_keys_per_s", "error") if k in spd},
        "spill_assoc": {k: sad.get(k) for k in
                        ("assoc", "assoc_hit_rate", "direct_hit_rate",
                         "conflict_misses_assoc",
                         "conflict_misses_direct", "parity", "error")
                        if k in sad},
        "boundary": {k: bdrill.get(k) for k in
                     ("boundary_seconds", "full_rebuild_seconds",
                      "speedup", "parity", "error") if k in bdrill},
        "self_healing": {k: heal.get(k) for k in
                         ("applied", "doctor_fail_on_warn",
                          "grow_gen", "round_trip_seconds", "error")
                         if k in heal},
        "overlap_ab": attr.get("overlap_ab"),
        "stages": attr.get("stages"),
        "gate_example_lines": g1.get("lines"),
    }), flush=True)
    return 0 if ok else 2


def main() -> None:
    import jax

    if "--dryrun" in sys.argv:
        raise SystemExit(dryrun_main())

    if "--sharded-probe" in sys.argv:
        raise SystemExit(sharded_probe_main())

    if "--host" in sys.argv:
        # host-section child entry (see _enrich): pinned to the CPU
        # backend — the parent holds the chip — and prints ONE JSON line
        # with the host timings and the platform it ran on
        jax.config.update("jax_platforms", "cpu")
        out = host_bench("--small" in sys.argv)
        out["platform"] = jax.devices()[0].platform
        print(json.dumps(out), flush=True)
        return

    small = os.environ.get("PBTPU_BENCH_SMALL") == "1"  # CPU smoke mode
    if small:
        jax.config.update("jax_platforms", "cpu")

    from paddlebox_tpu.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    _mark(f"compile cache: {cache['dir']} (from {cache['from']}, "
          f"{'warm' if cache['warm'] else 'cold'})")

    eps_chip, detail, ctx = device_step_bench(small, return_ctx=True)
    detail["compile_cache"] = cache
    # From here on, NOTHING may prevent the one JSON line from printing
    # (VERDICT r3 weak #2: the artifact was hostage to its most fragile
    # stage). Attribution/matrix/e2e enrich `detail` in place; any
    # escape — including KeyboardInterrupt mid-attribution — is recorded
    # in detail and the line still prints. Non-Exception escapes (Ctrl-C,
    # SystemExit) re-raise after the print so the recorded rc still says
    # the run was interrupted.
    pending = None
    try:
        _enrich(small, detail, ctx, eps_chip)
    except BaseException as e:
        detail["bench_error"] = repr(e)
        if not isinstance(e, Exception):
            pending = e

    # telemetry summary rides every artifact (counters accumulated across
    # the run + flight records from the e2e section's real passes) — the
    # hub may be disabled; the cumulative registry still tells the story
    try:
        from paddlebox_tpu import monitor as _monitor
        detail["telemetry"] = _monitor.hub().summary()
    except Exception as e:
        detail["telemetry"] = {"error": repr(e)}

    # the merged world-trace summary rides every artifact (ISSUE 15):
    # the hub's in-memory flight records render as per-rank pass slices
    # (flow points live only in the JSONL streams — the offline
    # `python -m paddlebox_tpu.monitor.trace` merge reads those)
    try:
        from paddlebox_tpu.monitor import trace as _trace
        detail["world_trace"] = _trace.summarize(_trace.merge_streams(
            [_trace.records_to_stream(_monitor.hub().flight_records())],
            [0]))
    except Exception as e:
        detail["world_trace"] = {"error": repr(e)}

    # the run-doctor verdict rides every artifact (ISSUE 12): critical-
    # path attribution over the e2e passes' flight records + the rule
    # set, with this round's push_floor closing the push-floor rule
    try:
        from paddlebox_tpu.monitor import doctor as _doctor
        detail["doctor"] = _doctor.diagnose_hub(
            _monitor.hub(),
            detail={"push_floor": detail.get("push_floor"),
                    "world_trace": detail.get("world_trace")})
    except Exception as e:
        detail["doctor"] = {"error": repr(e)}

    # round-over-round regression gate: every recorded number vs the best
    # recorded value for this hardware (BENCH_BEST.json); an unwaived
    # >10% regression fails audit_ok — the alarm round 5 did not have.
    # Guarded like _enrich: a hand-edited BENCH_BEST.json with a zero /
    # quoted / malformed value must not hold the artifact hostage
    # (the one JSON line below prints NO MATTER WHAT).
    try:
        gate = apply_regression_gate(
            collect_gate_metrics(eps_chip, detail), load_bench_best(),
            detail.get("device_kind", ""))
    except Exception as e:
        gate = {"ok": False, "regressed": [],
                "error": f"gate failed on BENCH_BEST.json: {e!r}",
                "lines": {}}
    detail["regression_gate"] = gate
    detail["audit"]["ok"] = detail["audit"]["ok"] and gate["ok"]

    print(json.dumps({
        "metric": "deepfm_device_step_examples_per_sec_per_chip",
        "value": round(eps_chip, 1),
        "unit": "examples/sec/chip",
        "vs_baseline": round(eps_chip / TARGET_PER_CHIP, 4),
        "detail": detail,
    }), flush=True)
    # compact self-contained summary, printed LAST: the driver records a
    # bounded TAIL of stdout, and BENCH_r04 lost its headline to exactly
    # that truncation (VERDICT r4 missing #4) — this line alone must
    # carry the verdict-grade numbers (<= ~500 chars)
    short = {"kstep_f32": "kstep", "async_f32": "async",
             "allreduce_int16": "i16", "allreduce_int8": "i8",
             "allreduce_f32_b16384": "b16k",
             "allreduce_f32_push_exact": "px3",
             "allreduce_f32_push_bf16": "px1",
             "allreduce_f32_dim64": "d64",
             "allreduce_f32_dim128": "d128",
             "allreduce_f32_multihot4_dim32": "mh4d32"}
    mshort = {short.get(k, k): int(v["examples_per_sec_per_chip"])
              for k, v in detail.get("matrix", {}).items()
              if isinstance(v, dict)
              and "examples_per_sec_per_chip" in v}
    # compact gate tail: one token per regressed metric (ok runs print
    # "ok"); the tail line alone must carry the verdict
    if gate.get("error"):
        gate_short = f"error({gate['error'][:80]})"
    elif gate.get("skipped"):
        gate_short = f"skipped({gate['skipped'][:60]})"
    elif gate["ok"]:
        gate_short = "ok"
    else:
        gate_short = "REGRESS:" + ",".join(
            f"{n}({gate['lines'][n].split('(')[1].rstrip(')')})"
            for n in gate.get("regressed", []))
    summary = {
        "metric": "deepfm_device_step_examples_per_sec_per_chip",
        "value": round(eps_chip, 1),
        "unit": "examples/sec/chip",
        "vs_baseline": round(eps_chip / TARGET_PER_CHIP, 4),
        "step_ms": round(detail["audit"]["step_seconds"] * 1e3, 2),
        "audit_ok": detail["audit"]["ok"],
        "gate": gate_short,
        "push_engine": detail.get("push_engine"),
        "pull_engine": detail.get("pull_engine"),
        "pack_engine": detail.get("pack_engine"),
        "push_overlap": detail.get("push_overlap"),
        "matrix_eps": mshort,
        "e2e_eps": (detail.get("e2e", {}).get(
            "examples_per_sec_per_chip")
            if isinstance(detail.get("e2e"), dict) else None),
        "serving": ({k: detail["matrix"]["serving"].get(k) for k in
                     ("publish_seconds", "swap_pause_ms", "p99_ms",
                      "error")
                     if k in detail["matrix"]["serving"]}
                    if isinstance(detail.get("matrix", {}).get("serving"),
                                  dict) else None),
        "spill": ({k: detail["matrix"]["spill_10x"].get(k) for k in
                   ("hot_hit_rate", "direct_hot_hit_rate",
                    "fetch_keys_per_s", "ws_over_cache", "error")
                   if k in detail["matrix"]["spill_10x"]}
                  if isinstance(detail.get("matrix", {}).get("spill_10x"),
                                dict) else None),
        "host_feed_cap_eps": (detail.get("host", {}).get(
            "derived_max_feed_eps_per_chip")
            if isinstance(detail.get("host"), dict) else None),
        "bench_error": detail.get("bench_error"),
    }
    print(json.dumps(summary), flush=True)
    if pending is not None:
        raise pending
    errors = _collect_errors({k: detail.get(k) for k in
                              ("bench_error", "stage_attribution",
                               "matrix", "host", "e2e")})
    if errors:
        print("BENCH SECTIONS FAILED (artifact printed above):\n  "
              + "\n  ".join(e[:300] for e in errors), file=sys.stderr)
        raise SystemExit(3)
    if not gate["ok"]:
        print("REGRESSION GATE FAIL: " + (gate.get("error") or "; ".join(
            f"{n} {gate['lines'][n]}" for n in gate.get("regressed", []))),
            file=sys.stderr)
        raise SystemExit(2)
    if not detail["audit"]["ok"]:
        print("AUDIT FAIL: implied MFU/HBM exceeds hardware peaks — the "
              "measurement window is broken; do not trust the number",
              file=sys.stderr)
        raise SystemExit(2)


def _enrich(small: bool, detail: dict, ctx: dict,
            eps_chip: float | None = None) -> None:
    """Attribution + matrix + e2e datapoints, mutating `detail` in place
    so partial progress survives any failure (main prints whatever
    landed)."""
    from paddlebox_tpu.utils.step_probe import finalize_push_floor
    if ctx["mode"] == "allreduce" and ctx["n_dev"] == 1 \
            and os.environ.get("PBTPU_BENCH_ATTR", "1") != "0":
        detail["stage_attribution"] = _attribute(
            ctx["tr"], ctx["ws"], ctx["staged0"], ctx["step_seconds"],
            small)
        if "push_floor" in detail:
            finalize_push_floor(
                detail["push_floor"],
                detail["stage_attribution"].get("stages", {})
                .get("sparse_push"))
    # release the headline run's device buffers before the matrix
    # re-allocates its own table + staged batches
    ctx.clear()
    if os.environ.get("PBTPU_BENCH_MATRIX", "1") != "0":
        # one device-step datapoint per dense-sync mode and per storage
        # mode (VERDICT r3 item #6): regressions in the non-headline
        # configs become visible round over round
        # stage-attributed points (the envelope's slowest — the audit
        # must name the stage behind each gap, VERDICT r4 weak #1; the
        # dim128 and multihot4 points are where the fused gather-pool
        # pull engages, so their splits name the fused stages);
        # override with PBTPU_BENCH_MATRIX_ATTR="name1,name2" or "" off
        attr_points = set(filter(None, os.environ.get(
            "PBTPU_BENCH_MATRIX_ATTR",
            "allreduce_f32_dim64,allreduce_f32_dim128,"
            "allreduce_f32_multihot4_dim32").split(",")))
        matrix = {}
        for mname, kw in (
                ("kstep_f32", dict(mode="kstep", storage="f32")),
                ("async_f32", dict(mode="async", storage="f32")),
                ("allreduce_int16", dict(storage="int16")),
                ("allreduce_int8", dict(storage="int8")),
                # batch scaling: the ~1.3ms/step dispatch floor amortizes
                ("allreduce_f32_b16384",
                 dict(storage="f32",
                      batch_per_dev=512 if small else 16384)),
                # push-precision endpoints around the 2-plane default:
                # 3-plane f32-exact and 1-plane bf16 (the reference's
                # quantized-push capacity/precision trade)
                ("allreduce_f32_push_exact",
                 dict(storage="f32", n_split=3)),
                ("allreduce_f32_push_bf16",
                 dict(storage="f32", n_split=1)),
                # wide-row envelope (VERDICT r3 missing #1): the binned
                # push must hold up where the reference dispatches big
                # embedx (box_wrapper.cc:444-461), not just at dim 8/16
                ("allreduce_f32_dim64",
                 dict(storage="f32", emb_dim=64)),
                ("allreduce_f32_dim128",
                 dict(storage="f32", emb_dim=128)),
                # DLRM-style multi-hot: variable lengths + pad masking
                # through seqpool and the wide-row push (BASELINE.md)
                ("allreduce_f32_multihot4_dim32",
                 dict(storage="f32", emb_dim=32, max_len=4))):
            try:
                want_attr = mname in attr_points
                res = device_step_bench(
                    small, n_steps=3 if small else 50, n_windows=2,
                    return_ctx=want_attr, **kw)
                m_eps, m_detail = res[0], res[1]
                m_audit = m_detail["audit"]
                matrix[mname] = {
                    "examples_per_sec_per_chip": round(m_eps, 1),
                    "step_seconds": m_audit["step_seconds"],
                    "push_engine": m_detail["push_engine"],
                    "pull_engine": m_detail["pull_engine"],
                    "pack_engine": m_detail["pack_engine"],
                    "push_overlap": m_detail["push_overlap"],
                    "table_layout": m_detail["table_layout"],
                    "exchange_wire": m_detail["exchange_wire"],
                    "table_shards": m_detail["table_shards"],
                    "push_floor": m_detail.get("push_floor"),
                    # per-point self-audit (VERDICT r4 weak #1): the
                    # headline's founding rule — a number without a
                    # FLOPs/bytes audit is not trusted — applied to
                    # every envelope point, slowest ones included
                    "audit": {
                        k: m_audit[k] for k in
                        ("flops_per_step", "hbm_bytes_per_step",
                         "implied_mfu", "implied_hbm_frac", "ok")
                        if k in m_audit},
                }
                if want_attr:
                    m_ctx = res[2]
                    # device-time stage split for the envelope's slow
                    # points: the dim64/multihot gaps need a named
                    # stage, not just a slower total
                    matrix[mname]["stage_attribution"] = \
                        _attribute(
                            m_ctx["tr"], m_ctx["ws"], m_ctx["staged0"],
                            m_ctx["step_seconds"], small)
                    if matrix[mname].get("push_floor"):
                        finalize_push_floor(
                            matrix[mname]["push_floor"],
                            matrix[mname]["stage_attribution"]
                            .get("stages", {}).get("sparse_push"))
                    m_ctx.clear()
                if kw.get("mode") == "async":
                    matrix[mname]["note"] = (
                        "BoxPSAsynDenseTable pulls and pushes the full "
                        "flat dense vector through the host each step: "
                        "this point times host<->device transfer as "
                        "much as the device")
            except Exception as e:   # a matrix point must not kill the run
                matrix[mname] = {"error": repr(e)}
            _mark(f"matrix point {mname} done")
        if os.environ.get("PBTPU_BENCH_SHARDED", "1") != "0":
            # sharded-exchange points (ISSUE 10): the mesh-partitioned
            # table with the dedup-plan-keyed a2a, one point per push
            # wire format — gate-held like every other matrix point,
            # with table_layout/exchange_wire/table_shards recorded. On
            # a single-device environment the points run in a 2-virtual-
            # device CPU subprocess (marked simulated: like-for-like
            # round over round, since the probe environment is stable).
            if detail.get("devices", 1) >= 2:
                from paddlebox_tpu.config import flags as config_flags
                try:
                    for mname, w in (("sharded_wire_f32", "f32"),
                                     ("sharded_wire_bf16", "bf16"),
                                     ("sharded_wire_int8", "int8")):
                        try:
                            s_eps, s_detail = device_step_bench(
                                small, n_steps=3 if small else 50,
                                n_windows=2, table_layout="sharded",
                                exchange_wire=w)
                            matrix[mname] = {
                                "examples_per_sec_per_chip":
                                    round(s_eps, 1),
                                "step_seconds":
                                    s_detail["audit"]["step_seconds"],
                                "table_layout": s_detail["table_layout"],
                                "exchange_wire":
                                    s_detail["exchange_wire"],
                                "table_shards": s_detail["table_shards"],
                                "pull_engine": s_detail["pull_engine"],
                                "push_engine": s_detail["push_engine"],
                            }
                        except Exception as e:
                            matrix[mname] = {"error": repr(e)}
                        _mark(f"matrix point {mname} done")
                finally:
                    # the forced engine must not leak into the elastic /
                    # serving drills below — they build 1-device
                    # trainers, and a leaked 'sharded' would error both
                    # gate-held points
                    config_flags.table_layout = \
                        _startup_flag("table_layout")
                    config_flags.exchange_wire = \
                        _startup_flag("exchange_wire")
                # drifting-sparsity adaptive point: the controller picks
                # the wire per pass and must beat every fixed point
                # above on its modeled cost (the drill saves/restores
                # its own flags)
                try:
                    matrix["adaptive_wire"] = adaptive_wire_drill(small)
                except Exception as e:
                    matrix["adaptive_wire"] = {"error": repr(e)}
                _mark("matrix point adaptive_wire done")
            else:
                probe = _run_sharded_probe(small)
                for mname, p in (probe.get("points") or {}).items():
                    matrix[mname] = p
                if "error" in probe:
                    matrix["sharded_wire_f32"] = {"error": probe["error"]}
                _mark("matrix sharded probe done")
        if os.environ.get("PBTPU_BENCH_SPILL", "1") != "0":
            # tiered-table drill: the sharded+spill path under a working
            # set >= 10x the RAM cache budget, admission policy vs the
            # direct-mapped baseline — gate-held like every other point
            try:
                matrix["spill_10x"] = spill_drill(small)
            except Exception as e:
                matrix["spill_10x"] = {"error": repr(e)}
            _mark("matrix point spill_10x done")
            # set-associative geometry drill: N-way vs direct-mapped on
            # the adversarial colliding stream, bit-parity held — the
            # assoc_hit_rate/fetch points are gate-held like the rest
            try:
                matrix["spill_assoc"] = spill_assoc_drill(small)
            except Exception as e:
                matrix["spill_assoc"] = {"error": repr(e)}
            _mark("matrix point spill_assoc done")
            # pass-boundary drill: incremental + overlapped feeds vs the
            # full-rebuild baseline on one key stream — gate-held
            # (boundary_seconds is lower-is-better off the suffix)
            try:
                matrix["boundary_incremental"] = boundary_drill(small)
            except Exception as e:
                matrix["boundary_incremental"] = {"error": repr(e)}
            _mark("matrix point boundary_incremental done")
        if os.environ.get("PBTPU_BENCH_ELASTIC", "1") != "0":
            # elastic rank-loss drill: world_resize_seconds + the
            # degraded (N−1) throughput point, gate-held like the rest
            try:
                matrix["elastic_degraded"] = elastic_drill(small)
                detail["world_resize_seconds"] = \
                    matrix["elastic_degraded"]["world_resize_seconds"]
            except Exception as e:
                matrix["elastic_degraded"] = {"error": repr(e)}
            _mark("matrix point elastic_degraded done")
        if os.environ.get("PBTPU_BENCH_SERVING", "1") != "0":
            # train→publish→serve drill: publish_seconds, swap_pause_ms
            # and served p50/p99 — gate-held like every other point
            # (latency metrics compare lower-is-better)
            try:
                matrix["serving"] = serving_drill(small)
            except Exception as e:
                matrix["serving"] = {"error": repr(e)}
            _mark("matrix point serving done")
            # version-split drill: shadow-mode two-version scoring —
            # shadow_p99_ms is gate-held (lower-is-better), the AUC /
            # score-KL attribution and doctor verdicts ride the artifact
            try:
                matrix["serving_split"] = serving_split_drill(small)
            except Exception as e:
                matrix["serving_split"] = {"error": repr(e)}
            _mark("matrix point serving_split done")
            try:
                matrix["serving_fleet"] = serving_fleet_drill(small)
            except Exception as e:
                matrix["serving_fleet"] = {"error": repr(e)}
            _mark("matrix point serving_fleet done")
        detail["matrix"] = matrix
    if os.environ.get("PBTPU_BENCH_HOST", "1") != "0":
        # host section, in a child pinned to the CPU: this process
        # holds the chip, and the host numbers must not share a process
        # with it
        try:
            import subprocess
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env.pop("PBTPU_BENCH_SMALL", None)
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--host"]
                + (["--small"] if small else []),
                capture_output=True, text=True, env=env, timeout=1800)
            if r.returncode == 0:
                detail["host"] = _cpu_child_result(r.stdout)
                cap = detail["host"].get("derived_max_feed_eps_per_chip")
                if eps_chip and isinstance(cap, (int, float)):
                    # the margin cites THIS run's measured headline, not
                    # a hardcoded constant (reconciled: the r5 artifact
                    # said "~1.2M" while recording 645k)
                    detail["host"]["feed_margin_vs_headline"] = round(
                        cap / eps_chip, 2)
            else:
                detail["host"] = {"error": r.stderr[-500:]}
        except Exception as e:
            detail["host"] = {"error": repr(e)}
        _mark("host section done")
    if os.environ.get("PBTPU_BENCH_E2E", "1") != "0":
        try:
            e2e_eps, e2e_detail = e2e_bench(small)
            detail["e2e"] = e2e_detail
            detail["e2e"]["examples_per_sec_per_chip"] = round(e2e_eps, 1)
            detail["e2e"]["vs_baseline"] = round(e2e_eps / TARGET_PER_CHIP,
                                                 4)
        except Exception as e:  # e2e failure must not hide the step number
            detail["e2e"] = {"error": repr(e)}


if __name__ == "__main__":
    main()
