"""Closed registry of hub event and span names.

Same discipline as the faultpoint registries (``utils/faultpoint.py``)
and the flag registry (``config.py``): the set of names the telemetry
plane can emit is CLOSED, machine-checked, and therefore greppable. A
dashboard, a doctor rule, or the world-trace merger keying off
``"serving_swap"`` must be able to trust that a renamed or typo'd
emission site cannot silently fork the namespace — the pblint
``event-registry`` rule fails the tree when a literal
``monitor.event("...")`` / ``monitor.span("...")`` site names something
not listed here.

Adding a name is one line here plus the consumer that reads it (the
doctor's EVIDENCE_EVENTS, a dashboard panel, a test) — the registry is
where a reviewer sees the telemetry surface grow.
"""

from __future__ import annotations

# a span name's profiler annotation is this prefix plus the name
# (hub.annotate writes it, ``monitor.trace --device`` reads it back)
ANNOTATION_PREFIX = "pbtpu/"

# event names (monitor.event / hub.event emissions across the tree)
EVENT_NAMES: tuple[str, ...] = (
    # pass lifecycle (hub / boxps)
    "pass_begin",
    "pass_aborted",
    "flip_phase",
    "eval_pass",
    # trainer hot loop + guards
    "pack_producer_done",
    "nan_guard",
    "routed_dropped",
    "exchange_overflow",
    "exchange_overflow_retry",
    # adaptive wire controller (embedding/exchange.WireController via
    # Trainer._adapt_wire): a per-pass exchange_wire switch, carrying
    # prev/next wire, the winning streak, and the modeled wire costs
    "exchange_wire_adapted",
    "drain_snapshot",
    "drain_snapshot_skipped",
    "elastic_min_world_exit",
    # feed pass (embedding/feed_pass.py)
    "feed_pass_staged",
    "feed_pass_flush",
    # HBM replica hot tier (embedding/replica_cache.TrainerReplicaCache,
    # flags.use_replica_cache): per-boundary rebuild, carrying the
    # replica row count + the pass's flushed hit delta
    "replica_refresh",
    # data plane
    "reader_malformed_line",
    "reader_close_error",
    # resilience (distributed/resilience.py)
    "peer_lost",
    "peer_stalled",
    "resume_election",
    "reform_escalated",
    "reform_sealed",
    "world_resize",
    "world_grow",
    # self-healing runtime (runtime/remediation.py)
    "remediation_applied",
    "remediation_reverted",
    # serving (publisher + server + boxps degrade arm)
    "serving_publish",
    "serving_publish_failed",
    "serving_compaction_error",
    "serving_donefile_compacted",
    "serving_artifact_prune_error",
    "serving_swap",
    "serving_version_fallback",
    # serving observability (serving/obs.py via server.commit_window):
    # the per-window serving flight record — requests, per-version
    # p50/p99 + score stats, version lag, swap count, replica-cache hits
    "serving_window",
    # serving fleet (serving/fleet.py + serving/router.py, ISSUE 20):
    # replica supervision (restart with backoff, crash-loop quarantine),
    # the shared staging lease (expiry retake), the router's all-stale
    # degrade, and verdict-guarded auto-promotion (promote after K clean
    # windows / HOLD + version quarantine on a critical verdict). The
    # per-window fleet flight record rides fleet_window.
    "fleet_window",
    "fleet_replica_restart",
    "fleet_replica_quarantined",
    "fleet_lease_retaken",
    "fleet.serving_stale",
    "fleet_promoted",
    "fleet_promote_hold",
    "fleet_version_quarantined",
    "fleet_supervise_error",
    # fleet / donefile discipline
    "donefile_compacted",
    "donefile_repaired",
    "donefile_malformed_line",
    "fleet_base_fetch_fallback",
    # checkpoints (utils/pass_ckpt.py)
    "checkpoint_save",
    "checkpoint_resume",
    "checkpoint_remote_upload",
    "checkpoint_remote_download",
    "checkpoint_remote_fallback",
    "checkpoint_torn_fallback",
    "checkpoint_timeline_reset",
    # fs / faultpoints / dumps
    "fs_exhausted",
    "faultpoint_armed",
    "faultpoint_trip",
    "dump_fields_written",
    # doctor live mode
    "doctor.finding",
    # sink bookkeeping (JsonlSink meta lines — emitted via the writer
    # thread's _meta, read back by monitor/aggregate.py)
    "sink_rotated",
    "sink_dropped",
    # world trace (monitor/trace.py)
    "trace.flow",
    "trace.clock_probe",
    "trace.device_capture",
)

# span names (monitor.span scopes + the StageTimers "stage/<name>"
# emissions of the stages that share no span). Each is also the profiler
# annotation ``pbtpu/<name>`` (hub.annotate), which is what
# ``monitor.trace --device`` and PERF.md's layer table key off.
SPAN_NAMES: tuple[str, ...] = (
    # one pass on the training thread, in order: the root, the head's
    # stages, the step loop, the close (PERF.md section 3 names the
    # metric each is for)
    "train_pass",
    "unique_keys",
    "boundary",
    "boundary/diff",
    "boundary/wait_feed",
    "boundary/fetch",
    "boundary/h2d",
    "boundary/build",
    "boundary/writeback",
    "boundary/combine",
    "boundary/land",
    "preplan",
    "stage/read",
    "h2d_stage",
    "train_step",
    "push_apply",
    "auc_update",
    "pass_close",
    "pass_close/rebind",
    "pass_close/end_pass",
    "stage/drain",
    "pass_close/read",
    # the pack thread ("stage/extras": a model's own host stage, e.g. a
    # sequence slot's ids within its vocabulary — Trainer._pack_host)
    "stage/translate",
    "stage/extras",
    # around the pass: ingest (the caller's or the preload thread) and
    # the BoxPS lifecycle calls
    "ingest",
    "ingest/key_merge",
    "box_begin_pass",
    "box_end_pass",
    "publish",
    # the stages around a train pass that share no other span: the next
    # pass's keys handed to the feed thread (Trainer.preload_pass), an
    # eval pass as a root of its own (the stages it shares with
    # train_pass nest in it under their names), a mid-pass snapshot (the
    # loop stalls for it: the thread runs steps ahead of the device)
    "preload_pass",
    "eval_pass",
    "midpass_save",
    # the device-scope table (monitor/device_scopes.py; only under an open
    # capture): one program lowered and read on the builder thread, and
    # the training thread's wait for that thread at the pass's close
    "device_scopes",
    "pass_close/device_scopes",
    # serving request spans (serving/frontend.py + server.py, sampled by
    # flags.serving_trace_sample): batch-coalesce wait vs. score time
    "serve/wait",
    "serve/score",
)

# statistics a model's loss may declare (models/base.py ``stat_names``):
# one value a step, out of the step program, into the flight record's
# counters at the pass's close. ``*_max`` / ``*_min``: the largest /
# smallest step of the pass (a gauge); the others are sums (counters).
MODEL_STAT_NAMES: tuple[str, ...] = (
    # parallel/expert.py share layer (SmallThinker, Nemotron-H and LFM2-MoE
    # report these five; LFM2-MoE reports no other: its short convolution
    # has nothing to count that is not a constant of the shapes):
    # (token, choice) assignments routed,
    # those that fell on an expert this chip holds, and a step's busiest
    # held expert (imbalance); the rows of the sorted copies the chunks
    # took (pad share = 1 - held_assignments / route_rows) and the chunks
    # whose held load fitted no bounded rung and took the whole chunk
    "moe.assignments",
    "moe.held_assignments",
    "moe.expert_load_max",
    "moe.route_rows",
    "moe.whole_chunk_routes",
    # ops/ssm_scan.py through a state-space mixer: tokens scanned (tokens x
    # mixer blocks), chunks scanned, and the most negative cumulative
    # ``Delta A`` within a chunk — how near exp of a chunk's decay is to
    # float32's underflow (about -87; below it the chunk's first tokens no
    # longer reach its end state)
    "ssm.tokens",
    "ssm.chunks",
    "ssm.decay_log_min",
    # ops/kda.py through a KDA mixer: the least cumulative log decay of any
    # channel over one chunk (the op's chunk) in the step — how far the
    # intra-chunk exponentials reach below float32's range (about -87;
    # the kernels form them as differences that stay <= 0, so what it
    # reads is how much of a chunk's start its end still sees)
    "kda.chunk_decay_log_min",
    # and the (sequence, head block, chunk, sub-chunk) visits of the
    # kernels' forward calls, one a layer and step, that took the pair-by-
    # pair path because some channel of some head of the block fell by
    # more than ``kda.LIMIT`` across half a sub-chunk (``kda.pair_subchunks``;
    # the share's denominator: sequences x KDA layers x H / hb x T / sub)
    "kda.pair_subchunks",
)

# the host plan's counters (Trainer._host_plan, a batch at a time on the
# pack thread; a pass's sums reach the flight record as ``stats_delta``):
# tokens planned, the distinct rows among them (the dedup rate the doctor
# and the world view read), the lanes shipped for those rows (pad share =
# 1 - unique / lanes), and the times the lane count grew (each is a newly
# compiled step and apply, Trainer._plan_lane_count)
PLAN_COUNTER_NAMES: tuple[str, ...] = (
    "trainer.plan_tokens",
    "trainer.plan_unique_tokens",
    "trainer.plan_lanes",
    "trainer.plan_lane_grows",
)

# the pass's key set (SlotDataset: sorted runs, one merge): runs merged
# (counted by the load, before the pass opens: read from monitor.STATS),
# and the calls of unique_keys() answered by the set the load left or by a
# rebuild from the records as they are (counted inside train_pass: a
# pass's sums reach the flight record as ``stats_delta``)
KEY_SET_COUNTER_NAMES: tuple[str, ...] = (
    "dataset.key_runs",
    "dataset.key_set_reused",
    "dataset.key_set_rebuilt",
)

# the Pallas kernels' names, as a device trace's ``XLA Ops`` line shows
# them (a forward kernel called under differentiation as ``jvp_<name>_``;
# under its plain name it is a recomputation's call): the only names a
# device EVENT carries, so the benchmark's kernel readers find a kernel's
# seconds by them (benchmark/metrics/*_ms_per_step.py). A
# ``jax.named_scope`` reaches no event, but it reaches the compiled
# program: every instruction's ``op_name`` metadata holds it, and an event
# is named by its instruction — DEVICE_SCOPE_NAMES below
KERNEL_NAMES: tuple[str, ...] = (
    # ops/pallas_kernels.py: the sparse engines a resolver may select
    "pbtpu_gather_pool",
    "pbtpu_binned_merge_acc",
    "pbtpu_merge_update",
    "pbtpu_scatter_accumulate",
    # ops/flash_attention.py: blocked causal attention (head sizes of whole
    # lane tiles, and of 64 and 192; values may have a head size of their
    # own, as multi-head latent attention's 128 beside queries and keys of
    # 192)
    "pbtpu_attention_fwd",
    "pbtpu_attention_dq",
    "pbtpu_attention_dkv",
    # ops/ssm_scan.py: the Mamba-2 scan in chunks
    "pbtpu_ssm_fwd",
    "pbtpu_ssm_bwd",
    # ops/kda.py: Kimi Delta Attention, the gated delta rule with a decay
    # per channel, in chunks
    "pbtpu_kda_fwd",
    "pbtpu_kda_bwd",
    # ops/short_conv.py: the gated short convolution between an LFM2
    # mixer's projections
    "pbtpu_short_conv_fwd",
    "pbtpu_short_conv_bwd",
    # ops/grouped_matmul.py: the held experts' grouped products (forward
    # and the rows' cotangent; the weights' cotangent), under scope
    # ``experts``; their tile metadata is the ``route``'s, once a chunk
    "pbtpu_gmm",
    "pbtpu_tgmm",
)

# the device's stages (monitor.device_scope): a step is written under
# ``jax.named_scope("pbtpu.<name>")``, the scope is a path component of
# every instruction's ``op_name`` in the optimized HLO (it survives
# differentiation, transposition and ``jax.checkpoint``), the innermost
# registered one is the instruction's stage, and a capture's ``XLA Ops``
# events are joined with that table by instruction name
# (monitor/device_scopes.py; ``monitor.trace --device``; the benchmark's
# ``*_ms_per_step`` readers over ``metrics/_scopes.py``)
DEVICE_SCOPE_PREFIX = "pbtpu."
DEVICE_SCOPE_NAMES: tuple[str, ...] = (
    # the sparse engine around the tower (train/trainer.py, embedding/):
    # rows gathered for the batch's tokens; token gradients merged onto
    # the plan's unique lanes; the merged update written to the table
    # (inline, or the deferred ``jit_apply``)
    "pull",
    "premerge",
    "push",
    # the model's loss, forward and backward: what no finer scope claims
    # (a CTR tower whole; a token tower's norms and residual adds)
    "tower",
    # the token towers' layers (models/, parallel/expert.py): an attention
    # half (projections, q/k norms, RoPE, the kernels) and, inside it,
    # multi-head latent attention's path to its keys and values (the
    # latent's projection and norm, its expansion to the heads, the
    # rotations, the shared rotary key's broadcast: models/deepseek_v3.py);
    # a state-space or short-convolution mixer; the experts' routing (rule,
    # sort, the moves into and out of the sorted copy, the ladder's switch)
    # and their grouped products; a dense MLP or shared expert; the head
    # and loss
    "attention",
    "latent",
    "mixer",
    "route",
    "experts",
    "dense_mlp",
    "head_loss",
    # the dense optimizer's update and the dense sync of any mode
    "dense_update",
    # the AUC accumulator's two programs (metrics/auc.py)
    "auc",
    # the pass boundary's programs (embedding/feed_pass.py,
    # working_set.py: combine, fill, patch, gather, pad, split)
    "boundary",
)

ALL_NAMES: frozenset = frozenset(EVENT_NAMES) | frozenset(SPAN_NAMES)


def is_registered(name: str) -> bool:
    return name in ALL_NAMES
