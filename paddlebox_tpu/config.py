"""Global flag registry.

Replaces the reference's three-tier config (gflags in
paddle/fluid/platform/flags.cc, protobuf TrainerDesc/DataFeedDesc descriptors,
and the external box_ps conf file — SURVEY.md §5 "Config / flag system") with a
single typed registry. Flags can be set programmatically, or via environment
variables ``PBTPU_<NAME>`` (mirroring how the reference exposes gflags through
``pybind/global_value_getter_setter.cc``).
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any


@dataclasses.dataclass
class Flags:
    """Framework-wide knobs.

    Each field mirrors a reference gflag where one exists (citation in the
    comment); new TPU-specific knobs are marked (new).
    """

    # --- data pipeline (reference platform/flags.cc:478-483) ---
    # This registry is CLOSED like the reference's flags.cc: every field
    # must have a live reader somewhere in the tree (the flag-audit pblint
    # rule enforces it), so knobs that only existed as documentation-by-
    # dataclass (record_pool_max_size, dataset_shuffle/merge_thread_num,
    # shuffle_by_searchid, slot_pool_capacity, pull_padding_zero,
    # embedding_max_keys_per_pass, binding_train_cpu, fix_dayid, and the
    # TrainerConfig duplicates param_sync_step / sync_dense_moment /
    # compute_dtype / embedding_dtype) were removed rather than waived —
    # the surviving reference-gflag citations live on the fields that do
    # something.
    dataset_load_thread_num: int = 8        # (new) parse/download threads

    # --- embedding engine (role of libbox_ps; flags.cc:603,607) ---
    pullpush_dedup_keys: bool = True        # FLAGS_enable_pullpush_dedup_keys
    # FLAGS_use_gpu_replica_cache (flags.cc:486): the trainer-side HBM
    # replica hot tier (embedding/replica_cache.TrainerReplicaCache) ABOVE
    # the spill store's RAM row cache — the top of the SSD→RAM→HBM
    # hierarchy. At every pass boundary the trainer rebuilds the replica
    # from the rows the TierManager already ranks hottest (show-count-
    # weighted freq EMA); the feed-pass stager then serves a fresh key's
    # row straight from the replica's device-resident plane instead of
    # faulting it through the RAM/SSD path. Placement only, never a math
    # change: pushes fold back through the store's stale-key log plus
    # explicit write-back invalidation, so training is bit-identical with
    # the tier on or off (tested). Telemetry: tiering.replica_hits
    # counter + tiering.replica_rows gauge in the flight record.
    use_replica_cache: bool = False         # FLAGS_use_gpu_replica_cache (flags.cc:486)
    # Pass-boundary transfer compression: embedx crosses host<->device as
    # bf16 (counters/opt state stay f32). TPU-native analogue of the
    # reference's Quant/ShowClk quantized feature types; rounds embedx to
    # 8 mantissa bits once per pass boundary. Opt-in. It splits and
    # rejoins the columns of ONE device array, so setting it switches the
    # lane-tile plane layout off (working_set.plane_layout): a dim-128
    # table is then the one array again, with the accumulator push.
    transfer_compress_embedx: bool = False  # (new)
    # Routed all_to_all capacity overflow policy (new — the reference sizes
    # buffers dynamically, box_wrapper_impl.h:44-81; fixed lanes are the
    # static-shape trade). Drops are counted per pass and NEVER silent:
    # fatal raises at pass end; adapt doubles Trainer capacity_factor for
    # the next pass (bounded by the shard count, which cannot drop).
    routed_drop_fatal: bool = False         # (new)
    routed_drop_adapt: bool = True          # (new)
    # Size the all_to_all capacity from the pass's ACTUAL per-(device,
    # destination) token histogram before the first step compiles, so a
    # skewed pass can never train lossily while the adaptive doubling
    # catches up (the reference never drops — it sizes buffers
    # dynamically, box_wrapper_impl.h:44-81). One extra vectorized
    # translate scan over the pass data; multi-shard meshes only.
    routed_capacity_preplan: bool = True    # (new)
    # Pack-pipeline depth: translate + host plan + H2D for batch k+1 run
    # on a background thread while step k trains (the MiniBatchGpuPack
    # role, data_feed.h:1372-1535). 0 = synchronous.
    prefetch_batches: int = 2               # (new)
    # Carry the dense params + f32 optimizer state through the jitted
    # step as TWO flat vectors instead of ~30 pytree leaves: each
    # argument leaf costs host-side dispatch processing (the reference's
    # single param_sync_ tensor, boxps_worker.cc:453-472; not measured
    # on this code). Allreduce mode only; read at Trainer construction.
    flat_dense_state: bool = True           # (new)
    # Scatter-free push: sort+bin tokens and build the per-block merge with
    # one-hot MXU matmuls, optimizer fused in VMEM (pallas_kernels.
    # binned_push). Engages only on real-TPU f32 tables whose row count
    # fits the block geometry; read at trace time like PBTPU_PALLAS.
    binned_push: bool = True                # (new)
    # bf16 planes the push payload crosses the MXU in (built in-kernel
    # by mantissa masking): 3 = f32-exact (24 mantissa bits), 2 = 16
    # exact bits, 1 = bf16 grads. Default 2: the sparse grads arriving
    # here already carry bf16-level rounding from the backward matmuls
    # (TPU MXU), so plane 3's bits 17-24 are below the gradient noise
    # floor; dropping it measured 7.60 -> 6.95ms on the v5e headline
    # step (+8.5%) in the first rounds. No cell of BENCHMARK.json
    # resolves to the binned kernel, so neither endpoint is measured on
    # this code (ROADMAP D2).
    binned_push_splits: int = 2             # (new)
    # Physical column count of the f32 device table. TPU random-row
    # gathers run ~2x faster from 64/128-column sources than from narrow
    # odd widths (measured on v5e: 213k-row gather 4.3ms at width 13,
    # 2.1ms at 64/128; widths 24-32 are WORSE than 13). Default OFF: with
    # the acc-only binned_push (one fused XLA update pass over the table)
    # the full train step measured FASTER at logical width (8.0ms vs
    # 11.8ms on one v5e, batch 8192) — the wide where/update pass costs
    # more than the gather saves — and padding multiplies HBM footprint
    # (no lane padding in HBM: a 64-wide table really stores 64 cols).
    # Opt-in for lookup-dominated workloads: "auto" = 64 (or 128 for
    # wide rows); 0 = logical width; N = explicit width >= row_width.
    # A padded table is one array by definition: where this flag pads
    # (device_width > row_width) the lane-tile plane layout is off
    # (working_set.plane_layout), and a dim-128 table padded to 256
    # takes the row-DMA kernels as before.
    table_pad_width: Any = 0                # (new)
    # Host-plan dedup pre-merge (the reference's DedupKeysAndFillIdx +
    # PushMergeCopy pairing, box_wrapper_impl.h:103): the pack thread's
    # counting sort additionally emits unique-row segment bounds, and
    # the device segment-sums per-token payloads onto one lane per
    # unique row BEFORE the merge engine runs — each duplicate crosses
    # the engine once. "auto" = geometries where the in-step A/B
    # measured a win (see sharded.push); "on"/"off" force. Trace-time,
    # single-shard TPU tables only (like the plan itself).
    push_dedup_premerge: str = "auto"       # (new)
    # Fused gather-pool pull: multi-hot/wide layouts gather table rows
    # and sum-pool them per (example, slot) INSIDE the pull
    # (pallas_kernels.gather_pool), so the (B*T, pull_width) token
    # matrix never materializes through the model; the pooled cotangent
    # expands back per token straight into the dedup premerge + binned
    # push (sharded.pooled_grad_tokens). "auto" = the trainer heuristic
    # (multi-hot or total_dim >= 64, single-shard mesh, pooled-pull-
    # capable model, uniform slot layout); "on"/"off" force. Read at
    # Trainer construction (trace time), like binned_push.
    fused_gather_pool: str = "auto"         # (new)
    # Push merge-engine override for A/B runs (resolve_push_engine —
    # ONE resolver shared by the compiled dispatch and
    # Trainer.engines()). "auto" picks per (width class, lane contract,
    # storage): premerged f32 unique lanes take the fused
    # "scatter_accumulate" (row-wise gather→update→write-back, no
    # O(table) pass — the dim64/dim128/multihot4 floor closer), narrow
    # raw token streams take the "binned_kernel" one-hot MXU merge (the
    # headline winner), everything else "xla_scatter". Forcing
    # "scatter_accumulate" also forces the dedup premerge on (the fused
    # engine consumes unique lanes) and runs the identical-math jnp
    # fallback off-TPU — the CPU-parity/A/B knob.
    push_engine: str = "auto"               # (new)
    # Deferred sparse-push apply (the reference hides push latency behind
    # the next pass's work — boxps_worker per-card push timers overlap
    # pass boundaries): the jitted step returns the packed push operands
    # (dedup plan + premerged grads/shows/clks) instead of applying them
    # inline, and the trainer dispatches the binned scatter-update for
    # step N as its OWN program while step N+1's pack/plan-H2D runs.
    # Bounded staleness of one step, enforced (PushOperandStager refuses
    # a second pending apply); flushed at pass boundaries and before
    # eval/save. Bit-identical to the inline push: the apply is always
    # data-sequenced before the next step consumes the table. "auto" =
    # on where dense sync permits (allreduce — mirroring
    # AsyncDenseTable's dispatch-decoupling semantics);
    # "on"/"off" force. Read at Trainer construction (trace time).
    push_overlap: str = "auto"              # (new)
    # Sharded table exchange (embedding/exchange.py): which engine the
    # trainer compiles the embedding traffic with. "auto" = "sharded"
    # on multi-device TPU meshes (the dedup-plan-keyed all-to-all with
    # the compressed push wire), "single" elsewhere — CPU test meshes
    # keep the legacy routed path's exact numerics unless a test opts
    # in. "sharded" forced on a one-device mesh is an error (there is
    # nothing to exchange); "single" forced on a multi-device mesh is
    # the A/B knob against the legacy token-level routed path.
    table_layout: str = "auto"              # (new)
    # Push-payload wire format over the exchange all_to_all: grads cross
    # as f32 (exact — the parity baseline), bf16, or int8 with a
    # per-lane scale; show/clk increments always stay f32 (counters
    # must not round). "auto" = bf16 (int8 for int8-storage tables) —
    # see exchange.select_wire for the rationale.
    exchange_wire: str = "auto"             # (new)
    # Initial all_to_all capacity factor for the sharded engine (0 =
    # keep TrainerConfig.capacity_factor). Overflow is NEVER silent
    # regardless: drops are counted (exchange.overflow_dropped), evented
    # (exchange_overflow), preplanned away (routed_capacity_preplan),
    # adaptively doubled for the next pass, and eval passes re-run
    # in place at the grown factor (exchange.eval.pre_retry).
    exchange_capacity_factor: float = 0.0   # (new)
    # Per-pass wire adaptation (exchange.WireController): at every owned
    # pass boundary the controller re-costs the f32/bf16/int8 wires from
    # the pass's OWN exchange counters (tokens, unique lanes — the dedup
    # depth that moves the crossover) plus any clock-corrected flow-edge
    # attribution fed from a world trace, and switches flags.exchange_wire
    # for the NEXT pass once a challenger wins `hysteresis` consecutive
    # passes (a switch recompiles the steps, exactly like the adaptive
    # capacity doubling). Decisions land in the flight-record extras
    # (exchange_wire / exchange_wire_next) and the exchange_wire_adapted
    # event. Parity guard holds on every wire: show/clk counters and the
    # int8 scale always ride the f32 side plane — a wire switch is never
    # a counter-precision change. Opt-in like spill_cache_autotune.
    exchange_adaptive: bool = False         # (new)
    # All_to_all decomposition for the sharded exchange push: "flat" =
    # one global exchange (the PR-9 shape); "hier" = two-stage — an
    # intra-host shuffle over the dp axis (f32, in-host bandwidth),
    # then a host-level merge of the received runs so the inter-host
    # leg over the node axis carries each host's UNIQUE lanes once,
    # wire-compressed, instead of per-device duplicates (the two-stage
    # array-redistribution decomposition). "auto" = hier exactly when
    # the mesh has a real multi-host (node, dp) shape, flat elsewhere.
    # Bit-identical to flat under exact arithmetic (f32 wire): the same
    # per-row contributions sum in the same merged order.
    exchange_topology: str = "auto"         # (new)
    # --- tiered table: SSD + host-RAM + HBM (embedding/tiering.py) ---
    # Storage tier of the host table (and of every shard of a
    # ShardedEmbeddingStore built through tiering.store_from_flags /
    # shard_store_factory): "off" = in-RAM HostEmbeddingStore (capacity
    # bounded by host DRAM), "spill" = SpillEmbeddingStore (memory-mapped
    # row file — the BoxPS SSD tier, LoadSSD2Mem box_wrapper.h:487-494 —
    # under a show-count-weighted RAM row cache). The tier is a storage
    # choice, not a math change: training is bit-identical either way.
    table_tiering: str = "off"              # (new)
    # RAM row-cache slots per spill-backed (sub-)store: the host-DRAM hot
    # tier's budget. Rule of thumb: size it to the per-pass working set's
    # hot fraction (row bytes = cache_rows * row_width * 4 per shard).
    spill_cache_rows: int = 1 << 16         # (new)
    # RAM row-cache associativity: the slot plane is n_sets sets of
    # `assoc` ways (set = row_id % n_sets), so up to `assoc` rows that
    # collide on a set index coexist instead of evicting each other —
    # conflict misses (tiering.conflict_misses: a miss whose whole set
    # is live) stop capping the hit rate below the budget on adversarial
    # slot collisions. The victim within a set is the coldest way by the
    # TierManager score. 1 = the legacy direct-mapped geometry (also
    # what tier_policy="direct" measures as the baseline); geometry is
    # placement only, never a math change.
    spill_cache_assoc: int = 4              # (new)
    # Root directory for spill row files ("" = a fresh temp dir per
    # store); sharded stores put shard s under <spill_dir>/shard-SS.
    spill_dir: str = ""                     # (new)
    # Autotune spill_cache_rows from the hit-rate/eviction telemetry the
    # flight record already carries: at each pass boundary the tier
    # re-evaluation doubles a thrashing (sub-)store's RAM row cache
    # (low hit rate + heavy eviction churn) and halves a mostly-idle
    # one, bounded by [256, 1<<22] slots; the chosen value lands in
    # the flight-record extras (spill_cache_rows) and the tiering.
    # cache_rows gauge. Opt-in: resizing drops the cache contents (the
    # spill file stays authoritative — a resize is never a math change).
    spill_cache_autotune: bool = False      # (new)
    # madvise(WILLNEED)-style async prefetch of the NEXT pass's spill
    # rows on the feed-pass stager thread: the working-set build issues
    # the disk-tier readahead for every row it is ABOUT to fault in
    # before the first read, so the kernel pages the spill file in
    # parallel with the host-side build instead of serially inside it
    # (the LoadSSD2Mem pairing — box_wrapper.h:487-494 pulls the pass's
    # range up BEFORE the working-set build reads it).
    spill_prefetch: bool = True             # (new)
    # Incremental delta feeds (embedding/feed_pass.py): when the host
    # store mutates between passes (shrink / delta replay) the feed
    # manager re-fetches ONLY the rows the mutation touched (the store's
    # bounded stale-key log) and keeps every other resident device row,
    # instead of discarding the working set and re-transferring the full
    # table; a background staging invalidated by such a mutation is
    # PATCHED with a compact delta plane rather than thrown away. Off =
    # the pre-incremental behavior (any mutation forces a full rebuild)
    # — the A/B knob the doctor's boundary-wall rule names when reuse
    # is off.
    incremental_feed: bool = True           # (new)

    # _bp_pack width-class engine override for A/B runs: "auto" selects
    # per payload width (narrow < 14 lanes reorders at logical width and
    # pads after; gather-zone 14..63 pads to 64 lanes BEFORE the reorder
    # — the v5e 14..63-lane row-gather cliff, 3-8x slower per row; wide
    # >= 64 packs at the full DMA width first). "narrow"/"gather_zone"/
    # "wide" force one path everywhere its layout allows — the
    # in-composed-step A/B knob whose absence let the round-5 _bp_pack
    # rewrite regress the headline 1.87x unnoticed.
    # pallas_kernels.pack_engine() names the path a geometry compiles.
    pack_engine: str = "auto"               # (new)

    # --- trainer (trainer_desc.proto:100-108, flags.cc:591-597) ---
    # (param_sync_step / sync_dense_moment live on TrainerConfig — the
    # per-trainer descriptor, like the reference's TrainerDesc proto —
    # not here; duplicating them in the global registry proved to be pure
    # drift: nothing ever read the flag copies.)
    check_nan_inf: bool = False             # FLAGS_check_nan_inf

    # --- pass/day (flags.cc:492) ---
    # FLAGS_padbox_auc_runner_mode: the feature-ablation AUC-runner mode.
    # metrics/auc_runner.py ships; this knob turns the trainer's eval loop
    # into runner mode when the ROADMAP scenario-diversity arc ("the
    # auc_runner feature-ablation mode at scale") wires it.
    # pblint: disable=flag-audit -- reserved for the ROADMAP
    # scenario-diversity arc: trainer-level auc_runner wiring
    auc_runner_mode: bool = False           # FLAGS_padbox_auc_runner_mode

    # --- crash-safe checkpoints (new — utils/pass_ckpt.py) ---
    # Pass snapshots retained by PassCheckpointer; >= 2 so a torn newest
    # snapshot always has a verified predecessor to fall back to.
    ckpt_keep_last_n: int = 3               # (new)
    # A fresh sparse base chain every N passes (bounds delta-replay length
    # at resume and lets retention reclaim old chain dirs); deltas between.
    ckpt_base_every: int = 8                # (new)
    # CommandFS shell-out resilience: bounded retry with exponential
    # backoff on put/get/ls/rm + idempotent mkdir -p (transient
    # HDFS/object-store failures are the norm, not the exception), and a
    # per-command timeout (0 = none). Retry deliberately excludes append
    # (a retried partial append could double-write a donefile line) and
    # cat (streaming).
    fs_retry_attempts: int = 3              # (new)
    fs_retry_backoff_s: float = 0.2         # (new) doubles per attempt
    fs_command_timeout_s: float = 0.0       # (new) 0 disables

    # --- multi-host resilience (new — distributed/resilience.py) ---
    # Heartbeat publish/scan period per rank (run-scoped FileStore keys).
    heartbeat_interval_s: float = 2.0       # (new)
    # A peer whose heartbeat SEQ stops advancing this long is dead
    # (peer_lost): the publisher is a daemon thread, so a frozen seq means
    # the process itself is gone.
    heartbeat_lost_s: float = 30.0          # (new)
    # A peer whose heartbeat beats but whose pass/step progress is frozen
    # this long is hung (peer_stalled) — stuck collective, dead remote FS.
    heartbeat_stall_s: float = 120.0        # (new)
    # Mid-pass snapshot cadence (steps) for Trainer.enable_midpass_snapshots
    # drivers; 0 = pass-boundary snapshots only. A mid-pass kill then
    # resumes from the dataset/shuffle cursor instead of replaying the
    # whole pass.
    ckpt_midpass_every_steps: int = 0       # (new)
    # --- elastic rank-loss recovery (new — distributed/resilience.py) ---
    # World-size floor for shrink-to-N−1 continuation: when survivors of a
    # peer failure would number fewer than this, the world checkpoints and
    # exits cleanly instead of re-forming (an operator decided N/2 ranks
    # can't carry the working set; 1 = always continue, down to solo).
    elastic_min_world: int = 1              # (new)
    # Re-formation epoch patience: how long a survivor waits for its
    # believed-surviving peers to arrive at (and then ack) a proposed
    # generation before sealing without them / escalating past them.
    # Bounds the blast radius of a SECOND failure inside re-formation.
    elastic_reform_timeout_s: float = 30.0  # (new)
    # Bounded retry around the re-formation + election + restore sequence
    # (each retry escalates the generation, dropping newly-failed ranks),
    # with exponential backoff between attempts. Exhaustion raises the
    # original PeerFailureError — fail-stop, the pre-elastic behavior.
    elastic_max_reforms: int = 4            # (new)
    elastic_reform_backoff_s: float = 0.5   # (new) doubles per attempt

    # --- self-healing runtime (new — runtime/remediation.py) ---
    # Doctor-driven remediation loop: at each pass boundary the
    # RemediationController consumes the live doctor findings, applies at
    # most ONE machine-applicable action per pass (flag flip + recompile,
    # cache resize, world grow) under the parity guard, and records the
    # before/after counter deltas in the flight record. Off = today's
    # operator-reads-the-suggestion behavior.
    self_healing: bool = False              # (new)
    # How many CONSECUTIVE pass boundaries a rule must fire before its
    # action is applied — one noisy pass never reconfigures the run.
    self_healing_sustain: int = 2           # (new)

    # --- telemetry (new — monitor/ TelemetryHub + utils/profiler) ---
    # RecordEvent span ring capacity: the profiler keeps at most this many
    # spans, dropping oldest-first (profiler.dropped_spans counts); 0 =
    # unbounded (the pre-hub behavior — a day-scale run grows forever).
    profiler_max_events: int = 200_000      # (new)
    # JsonlSink bounded queue: a slow/failed writer drops events (counted)
    # instead of ever blocking the training thread.
    telemetry_queue_size: int = 8192        # (new)
    # JsonlSink size-based rotation: when a segment exceeds this many
    # MB the writer thread closes it and opens the next numbered
    # segment (events.jsonl -> events.00001.jsonl -> ...). 0 = off (one
    # unbounded file — fine for bounded runs, not for streaming/day-
    # scale ones). Segments stay schema-clean; monitor/aggregate.py
    # reads them back in order.
    telemetry_rotate_mb: int = 0            # (new)
    # Run doctor live mode (monitor/doctor.py): evaluate the incident
    # rule set against the in-memory flight records at every end_pass
    # and emit `doctor.finding` events into the hub (BoxPS.end_pass
    # also returns the findings). Off by default: the rules read only
    # committed records, but day-scale operators opt in explicitly.
    doctor_live: bool = False               # (new)
    # --- world trace (new — monitor/trace.py) ---
    # Distributed tracing: every hub record emitted inside a sampled
    # pass carries a trace context (trace_id / span_id / parent links),
    # flow points mark the cross-rank edges (the exchange all_to_all,
    # publish -> serving swap), and `python -m paddlebox_tpu.monitor.
    # trace <rank_dirs...>` merges the per-rank streams into ONE
    # clock-corrected Chrome-trace JSON (Perfetto). Off by default; the
    # disabled cost is one module-flag check per scope.
    trace: bool = False                     # (new)
    # Trace every Nth pass (1 = every pass). Sampling keeps day-scale
    # streams bounded: an unsampled pass emits NO trace records and
    # pays only the begin_pass sampling decision.
    trace_sample_passes: int = 1            # (new)
    # Stable run identity baked into every trace_id so two runs sharing
    # a telemetry root can never interleave ("" = "run"). All ranks of
    # one run must agree (set it from the launcher like the FileStore
    # namespace).
    trace_run_id: str = ""                  # (new)
    # Per-pass-window DEVICE capture: start a jax.profiler trace at
    # every sampled begin_pass and stop it at end_pass, dumping under
    # trace_device_dir/pass-NNNNN, with the program's spans inside it
    # as pbtpu/<name> annotations (`python -m paddlebox_tpu.monitor.trace
    # --device` reads it). Any backend; a profiler failure is counted
    # and warned once, never raised: tracing must not take down training.
    trace_device: bool = False              # (new)
    trace_device_dir: str = ""              # (new) "" = <tmp>/pbtpu_device_trace
    # --- serving observability (new — serving/obs.py, ISSUE 19) ---
    # Version-split traffic: fraction of live requests the server routes
    # to the CANDIDATE version (the newest published model held next to
    # the stable one). 0.0 = no split: every new version hot-swaps to
    # active immediately, exactly the pre-split behavior.
    serving_split_fraction: float = 0.0     # (new)
    # Shadow mode: score every request on BOTH versions but always serve
    # the stable answer — per-version latency/score/AUC attribution with
    # zero user-facing risk (the paper's AUC-runner A/B, serving half).
    serving_shadow: bool = False            # (new)
    # Serving flight-record cadence: commit one schema-validated
    # `serving_window` record to the hub every this-many seconds of
    # request traffic. 0 disables windowed records.
    serving_window_s: float = 30.0          # (new)
    # Request tracing: every Nth dispatched batch opens serve/wait +
    # serve/score spans under the standing `ensure_service` scope,
    # parent-linked to the served version's publish span via the
    # donefile-carried ids. 0 = no request spans (one flag check).
    serving_trace_sample: int = 0           # (new)
    # Serving latency SLO (ms) the doctor's p99-burn rule burns against;
    # stamped into every serving window record.
    serving_slo_ms: float = 50.0            # (new)
    # --- serving fleet (new — serving/fleet.py + router.py, ISSUE 20) ---
    # Replicas per host the fleet CLI supervises off ONE donefile (each
    # builds from the shared verified staging copy).
    serving_fleet_replicas: int = 2         # (new)
    # Verdict-guarded auto-promotion: the version-regression rule's
    # verdict drives promote_candidate() fleet-wide — a critical
    # do-not-promote verdict HOLDS the candidate and quarantines that
    # version; promotion fires only after serving_promote_windows clean
    # windows. Off = promotion stays a manual operator call.
    serving_auto_promote: bool = False      # (new)
    # Consecutive clean (version-regression quiet) serving windows
    # required before auto-promotion fires.
    serving_promote_windows: int = 2        # (new)
    # Router hedging: once a request has waited hedge_factor * observed
    # p99, launch a second request on a different replica (first answer
    # wins, the loser is cancelled and counted). 0.0 = hedging off.
    serving_hedge_factor: float = 0.0       # (new)

    def set(self, name: str, value: Any) -> None:
        if not hasattr(self, name):
            raise KeyError(f"unknown flag {name!r}")
        setattr(self, name, value)

    def get(self, name: str) -> Any:
        if not hasattr(self, name):
            raise KeyError(f"unknown flag {name!r}")
        return getattr(self, name)

    @classmethod
    def from_env(cls) -> "Flags":
        f = cls()
        for field in dataclasses.fields(cls):
            env_key = "PBTPU_" + field.name.upper()
            if env_key in os.environ:
                raw = os.environ[env_key]
                if field.type in ("int", int):
                    f.set(field.name, int(raw))
                elif field.type in ("float", float):
                    f.set(field.name, float(raw))
                elif field.type in ("bool", bool):
                    f.set(field.name, raw.lower() in ("1", "true", "yes"))
                else:
                    f.set(field.name, raw)
        return f


_lock = threading.Lock()
flags = Flags.from_env()


def set_flags(**kwargs: Any) -> None:
    """Set multiple flags atomically (test-friendly)."""
    with _lock:
        for k, v in kwargs.items():
            flags.set(k, v)
