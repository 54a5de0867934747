"""Complete day/pass CTR training workflow — the user-facing shape of the
framework, end to end:

  slot-text files → SlotDataset (load + shuffle) → day loop of passes
  (BoxPS lifecycle, join/update phase flip, per-pass AUC + cmatch metrics)
  → crash-safe per-pass snapshots (PassCheckpointer: atomic manifested
  base/delta chain) + day-end base models with donefiles (FleetUtil) →
  crash recovery via both paths → serving export (Predictor scores the
  eval slice) → online serving (every end_pass publishes a versioned
  base/delta artifact; a ServingServer tails the donefile, hot-swaps it
  in, and a BatchingFrontend scores at concurrency — README "Serving
  runbook").

Runs hardware-free on the 8-virtual-device CPU mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_ctr.py

On a TPU host, drop the env vars — the same script trains on the chips.
This mirrors the reference's user workflow (dataset.set_date / begin_pass /
train_from_dataset / end_pass / fleet_util.save_*_model — SURVEY.md §3.4).

Observability (the telemetry-hub quickstart, README "Observability"):
``PBTPU_TELEMETRY_DIR=/some/dir`` turns the hub's event stream on — a
JSONL event file (``events.jsonl``: tagged events/spans + one flight
record per pass), log_for_profile-parity pass lines on stdout, a
Prometheus text exposition (``metrics.prom``), and a chrome trace
(``trace.json``) with pass-boundary / checkpoint-commit markers.
``--short`` trains one day instead of two (the tier-1 telemetry smoke
runs this path).

``--multihost`` demos the ISSUE-5 whole-world crash recovery instead: a
2-process world (FileStore control plane, run-scoped heartbeats +
watchdog, lockstep pass barriers, per-rank crash-safe snapshots) loses
rank 1 to a hard kill mid-run; the relaunched world runs the COORDINATED
resume election — every rank publishes its intact snapshot cursors, the
highest cursor every rank holds intact wins — and finishes training from
the same cursor on every rank.
"""

from __future__ import annotations

import os
import sys
import tempfile

import numpy as np


def synth_files(root: str, schema, n_files: int = 4, lines: int = 512,
                seed: int = 0) -> list[str]:
    """Write Criteo-like MultiSlot text: label, dense floats, id slots —
    with real signal (ids carry latent weights)."""
    rng = np.random.default_rng(seed)
    S = len(schema.sparse_slots)
    F = len(schema.float_slots) - 1
    id_w = np.random.default_rng(99).normal(size=(S, 1000)) * 1.2
    files = []
    for f in range(n_files):
        rows = []
        for _ in range(lines):
            ids = rng.integers(0, 1000, size=S)
            logit = id_w[np.arange(S), ids].sum() * 0.7
            label = float(rng.random() < 1 / (1 + np.exp(-logit)))
            parts = [f"1 {label}"]
            parts += [f"1 {rng.normal():.4f}" for _ in range(F)]
            parts += [f"1 {int(i) + s * 1000003}"
                      for s, i in enumerate(ids)]
            rows.append(" ".join(parts))
        p = os.path.join(root, f"part-{f:03d}.txt")
        with open(p, "w") as fh:
            fh.write("\n".join(rows) + "\n")
        files.append(p)
    return files


def _multihost_worker() -> int:
    """One rank of the --multihost recovery demo (spawned by launch)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddlebox_tpu.data import DataFeedSchema, SlotDataset
    from paddlebox_tpu.distributed import HeartbeatMonitor, RoleMaker
    from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.models import DNNCTRModel
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.train import Trainer, TrainerConfig
    from paddlebox_tpu.utils.pass_ckpt import PassCheckpointer

    rm = RoleMaker.from_env()
    col = rm.collectives(timeout_s=120)
    # col.store is already run-id-namespaced by RoleMaker
    hb = HeartbeatMonitor(col.store, rm.rank, rm.world_size,
                          interval_s=1.0)
    col.watchdog = hb          # barrier waits fail with NAMED dead ranks

    num_slots = 4
    schema = DataFeedSchema.ctr(num_sparse=num_slots, num_float=1,
                                batch_size=64, max_len=1)
    data_dir = tempfile.mkdtemp(prefix=f"pbtpu_mh_rank{rm.rank}_")
    files = synth_files(data_dir, schema, n_files=2, lines=256,
                        seed=100 + rm.rank)      # per-rank shard
    ds = SlotDataset(schema)
    ds.set_filelist(files)
    ds.load_into_memory(global_shuffle=False)

    store = HostEmbeddingStore(EmbeddingConfig(dim=4, learning_rate=0.1))
    tr = Trainer(DNNCTRModel(num_slots=num_slots, emb_dim=4, dense_dim=1,
                             hidden=(16,)),
                 store, schema, make_mesh(1),
                 TrainerConfig(global_batch_size=64, dense_lr=3e-3,
                               auc_buckets=1 << 10), seed=7 + rm.rank)
    box = BoxPS(store)
    box.set_date(20260803)
    box.attach_collectives(col, heartbeat=hb)    # lockstep pass barriers
    ckpt = PassCheckpointer(
        os.path.join(os.environ["PBTPU_MH_ROOT"], f"rank{rm.rank}"),
        keep_last_n=3, base_every=2)

    # coordinated resume election: all ranks restore the SAME cursor
    cursor = tr.resume(ckpt, box=box, collectives=col)
    start = (int(cursor["pass_id"]) if cursor is not None else 0) + 1
    print(f"[rank {rm.rank}] elected cursor: "
          f"{None if cursor is None else cursor.get('elected')} "
          f"-> entering pass {start}", flush=True)
    for p in range(start, 4):
        box.begin_pass()
        stats = tr.train_pass(ds)
        box.end_pass(checkpointer=ckpt, trainer=tr, dataset=ds)
        print(f"[rank {rm.rank}] pass {box.pass_id}: "
              f"auc={stats['auc']:.3f}", flush=True)
        if (p == 2 and rm.rank == 1
                and os.environ.get("PBTPU_MH_KILL") == "1"):
            print("[rank 1] simulating preemption: hard kill, no cleanup",
                  flush=True)
            os._exit(137)
    hb.close()
    print(f"[rank {rm.rank}] done", flush=True)
    return 0


def _multihost_demo() -> int:
    """Parent of the --multihost demo: world 1 loses rank 1 mid-run; the
    relaunched world 2 elects the newest snapshot every rank holds intact
    and finishes from it."""
    from paddlebox_tpu.distributed.launch import launch
    root = tempfile.mkdtemp(prefix="pbtpu_mh_demo_")
    env = {"PBTPU_MH_ROOT": root, "JAX_PLATFORMS": "cpu"}
    print("== world 1: rank 1 will be hard-killed after pass 2 ==")
    code = launch(2, [sys.executable, os.path.abspath(__file__),
                      "--mh-worker"], base_env=dict(env, PBTPU_MH_KILL="1"))
    print(f"== world 1 fail-stopped (exit {code}) ==")
    print("== world 2: coordinated resume election ==")
    code = launch(2, [sys.executable, os.path.abspath(__file__),
                      "--mh-worker"], base_env=env)
    print(f"== world 2 finished (exit {code}) ==")
    assert code == 0, "resumed world failed"
    print("multihost recovery demo complete:", root)
    return 0


def main() -> int:
    import jax
    from paddlebox_tpu import monitor
    from paddlebox_tpu.data import DataFeedSchema, SlotDataset
    from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
    from paddlebox_tpu.fleet import BoxPS, FleetUtil
    from paddlebox_tpu.inference import Predictor, save_inference_model
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.train import Trainer, TrainerConfig
    from paddlebox_tpu.utils import profiler

    from paddlebox_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    short = "--short" in sys.argv
    telemetry_dir = os.environ.get("PBTPU_TELEMETRY_DIR")
    if telemetry_dir:
        # observability quickstart: JSONL event stream + parity stdout
        # lines; host spans collected for the chrome trace exported below
        os.makedirs(telemetry_dir, exist_ok=True)
        monitor.hub().enable(
            monitor.JsonlSink(os.path.join(telemetry_dir, "events.jsonl")),
            monitor.ParityLogSink())
        profiler.enable_profiler()

    work = tempfile.mkdtemp(prefix="pbtpu_example_")
    out_root = os.path.join(work, "output")
    num_slots, emb_dim = 8, 8
    schema = DataFeedSchema.ctr(num_sparse=num_slots, num_float=2,
                                batch_size=128, max_len=1)
    files = synth_files(work, schema)

    store = HostEmbeddingStore(EmbeddingConfig(dim=emb_dim,
                                               optimizer="adagrad",
                                               learning_rate=0.1))
    from paddlebox_tpu.utils.pass_ckpt import PassCheckpointer

    box = BoxPS(store)
    box.init_metric("auc", method="plain")
    fleet = FleetUtil(out_root)
    # crash-safe pass snapshots: atomic manifested base/delta chain +
    # dense/optimizer/metric planes + cursor; resume() falls back past a
    # torn newest snapshot by checksum
    ckpt = PassCheckpointer(os.path.join(work, "snapshots"),
                            keep_last_n=3, base_every=2)
    mesh = make_mesh(min(8, len(jax.devices())))
    model = DeepFMModel(num_slots=num_slots, emb_dim=emb_dim, dense_dim=2,
                        hidden=(64, 32))
    tr = Trainer(model, store, schema, mesh,
                 TrainerConfig(global_batch_size=128, dense_lr=3e-3,
                               auc_buckets=1 << 12))

    ds = SlotDataset(schema)
    ds.set_filelist(files)

    # online serving publisher (ISSUE 7): every end_pass below also
    # ships this pass's model to the serving root — a full base every
    # publish_base_every passes, an exact key-delta otherwise, cold rows
    # int8, announced by donefile only after a verified commit
    from paddlebox_tpu.serving import (BatchingFrontend, ServingPublisher,
                                       ServingServer)
    serve_root = os.path.join(work, "serving")
    pub = ServingPublisher(serve_root, model, schema,
                           publish_base_every=2, quant="int8",
                           hot_top_k=256)

    days = [20260729] if short else [20260729, 20260730]
    passes_per_day = 2
    for day in days:
        box.set_date(day)
        for p in range(passes_per_day):
            ds.load_into_memory(global_shuffle=False)
            box.begin_pass()
            stats = tr.train_pass(ds, metrics=box.metrics)
            # single delta writer per store: save_delta consumes the
            # dirty mask, so per-pass persistence belongs to ONE owner —
            # here the crash-safe checkpointer. (Stacking
            # fleet.save_delta_model on top would write EMPTY fleet
            # deltas; the day-end fleet base below is a full snapshot
            # and stays exact regardless.)
            info = box.end_pass(checkpointer=ckpt, trainer=tr,
                                publisher=pub)
            last_snapshot_keys = len(store)
            msg = box.get_metric_msg("auc")
            pinfo = info.get("publish", {})
            print(f"day {day} pass {box.pass_id}: "
                  f"auc={stats['auc']:.3f} "
                  f"registry_auc={msg.get('auc', float('nan')):.3f} "
                  f"loss={stats['loss_mean']:.4f} "
                  f"({info['seconds']:.1f}s) → published "
                  f"v{pinfo.get('version')} ({pinfo.get('kind')}, "
                  f"{pinfo.get('bytes', 0)} bytes)")
        # end of day: table hygiene, then persist the base model — the
        # saved base must reflect the post-shrink table so recovery
        # reproduces the live store exactly
        evicted = box.shrink_table(min_show=0.5, decay=0.98)
        fleet.save_model(store, tr.eval_params(), day)
        print(f"day {day}: shrink evicted {evicted}, base model saved")

    # ---- crash recovery path 1: rebuild from the newest donefiles ----
    store2, dense2, rec_day = fleet.load_model(tr.eval_params())
    print(f"recovered day {rec_day}: {len(store2)} keys "
          f"(live {len(store)})")
    assert len(store2) == len(store)

    # ---- crash recovery path 2: resume-from-pass (PassCheckpointer) ----
    # A preempted worker restarts, resumes every plane from the newest
    # verified snapshot, and re-enters the pass loop at the cursor.
    store3 = HostEmbeddingStore(EmbeddingConfig(dim=emb_dim,
                                                optimizer="adagrad",
                                                learning_rate=0.1))
    box3 = BoxPS(store3)
    box3.init_metric("auc", method="plain")
    tr3 = Trainer(model, store3, schema, mesh,
                  TrainerConfig(global_batch_size=128, dense_lr=3e-3,
                                auc_buckets=1 << 12), seed=123)
    cursor = tr3.resume(ckpt, box=box3)
    print(f"resumed at cursor {cursor}: {len(store3)} keys, "
          f"next pass {box3.pass_id + 1}")
    assert cursor["pass_id"] == box.pass_id
    # the snapshot is pass-granular: it captures the table as of the last
    # end_pass, i.e. BEFORE the day-end shrink that followed it
    assert len(store3) == last_snapshot_keys

    # ---- serving ----
    export = os.path.join(work, "export")
    save_inference_model(export, model, tr.eval_params(), store, schema)
    pred = Predictor.load(export)
    pb = next(iter(ds.batches(batch_size=128)))
    probs = pred.predict_batch(pb)
    labels, _ = tr.split_floats(pb.floats)
    order = np.argsort(probs)
    ranks = np.empty(len(probs)); ranks[order] = np.arange(len(probs))
    pos = labels > 0.5
    auc = ((ranks[pos].mean() - ranks[~pos].mean()) / len(probs) + 0.5
           if pos.any() and (~pos).any() else float("nan"))
    print(f"serving: scored {len(probs)} examples, AUC={auc:.3f}")
    assert auc > 0.6, "serving scores lost the training signal"

    # ---- online serving: tail the donefile, hot-swap, score at
    # concurrency (README "Serving runbook"; the same server runs
    # standalone as `python -m paddlebox_tpu.serving.server ROOT`) ----
    srv = ServingServer(serve_root, poll_s=0.1)
    applied = srv.poll_once()
    h = srv.health()
    print(f"serving host: applied {applied} published versions, "
          f"status={h['status']} v{h['active_version']} "
          f"(pass {h['active_pass']}, {h['table_keys']} keys, "
          f"{h['hot_cached_keys']} hot-cached, "
          f"swap pause {h['last_swap_pause_ms']}ms)")
    assert h["status"] == "ok" and h["active_pass"] == box.pass_id
    served = srv.predict_batch(pb)
    # published artifacts quantize cold rows int8 and the publish ran
    # BEFORE the day-end shrink — served scores track the live export
    # within that bounded skew, and must carry the same ranking signal
    assert np.corrcoef(probs, served)[0, 1] > 0.98
    fe = BatchingFrontend(srv, max_batch=64, max_wait_s=0.005).start()
    try:
        lc, lw, _ = schema.float_split_cols("label")
        floats = np.concatenate([pb.floats[:, :lc], pb.floats[:, lc + lw:]],
                                axis=1)
        futs = [fe.submit(pb.ids[i].astype(np.uint64), pb.mask[i],
                          floats[i]) for i in range(32)]
        got = np.asarray([f.result(timeout=300) for f in futs])
        st = fe.stats()
        np.testing.assert_allclose(got, served[:32], rtol=1e-5, atol=1e-6)
        assert st["failures"] == 0
        print(f"frontend: {st['count']} requests in {st['batches']} "
              f"batches, p50={st['p50_ms']}ms p99={st['p99_ms']}ms, "
              f"0 failures")
    finally:
        fe.stop()
        srv.stop()

    if telemetry_dir:
        # flush the event stream, write the Prometheus exposition, and
        # export the chrome trace (pass_begin/pass_end +
        # checkpoint_commit instant markers — open it in Perfetto)
        n_spans = profiler.export_chrome_trace(
            os.path.join(telemetry_dir, "trace.json"))
        with open(os.path.join(telemetry_dir, "metrics.prom"), "w") as f:
            f.write(monitor.hub().prometheus_text())
        flights = monitor.hub().flight_records()
        # run-doctor verdict over this run's records (the same analysis
        # `python -m paddlebox_tpu.monitor.doctor <dir>` runs offline —
        # README "Run doctor")
        from paddlebox_tpu.monitor import doctor as doctor_lib
        verdict = doctor_lib.diagnose_hub(monitor.hub())["verdict"]
        monitor.hub().disable()
        profiler.disable_profiler()
        print(f"telemetry: {len(flights)} flight records, {n_spans} trace "
              f"events -> {telemetry_dir}")
        print(f"doctor: {verdict}")
        from paddlebox_tpu.config import flags as _flags
        if _flags.trace:
            # world trace (PBTPU_TRACE=1): merge this rank's stream into
            # the Perfetto timeline — multi-rank runs merge every rank's
            # dir with `python -m paddlebox_tpu.monitor.trace` instead
            from paddlebox_tpu.monitor import trace as trace_lib
            wt = trace_lib.merge_roots([telemetry_dir])
            trace_lib.write_trace(
                wt, os.path.join(telemetry_dir, "world_trace.json"))
            s = trace_lib.summarize(wt)
            print(f"world trace: {s['spans']} spans, "
                  f"{len(s['flow_edges'])} flow edges -> "
                  f"{telemetry_dir}/world_trace.json")
    print("example complete:", work)
    return 0


if __name__ == "__main__":
    # runnable as a plain script (and as its own --mh-worker subprocess)
    # without an installed package or PYTHONPATH
    _REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    if "--mh-worker" in sys.argv:
        sys.exit(_multihost_worker())
    if "--multihost" in sys.argv:
        sys.exit(_multihost_demo())
    sys.exit(main())
