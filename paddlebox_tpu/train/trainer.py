"""The trainer — BoxPSTrainer/BoxPSWorker collapsed into one jitted step.

Reference hot loop (SURVEY.md §3.1, boxps_worker.cc:542-598): one pinned
thread per GPU runs `PackBatchTask → ops → dense sync → nan check → AUC`.
On TPU the whole per-batch pipeline is ONE jitted SPMD function over the
mesh: routed embedding lookup (shard_map all_to_all), model forward/backward
(XLA-fused), dense-grad pmean (the NCCL allreduce path), sparse push with
in-table optimizer, AUC accumulation — no thread pool, no op scheduler.

Dense sync modes (trainer_desc.proto:100-108 → here):
- "allreduce": per-step pmean of dense grads — DenseKStepALL with k=1 and the
  c_mixallgather fused path; the 2D (node, dp) mesh gives the reference's
  hierarchical reduce-scatter → inter-node → all-gather automatically.
- K-step/async modes live in parallel/dense_sync.py.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from paddlebox_tpu.config import flags as config_flags
from paddlebox_tpu.data.schema import DataFeedSchema
from paddlebox_tpu.data.slot_record import PackedBatch, SparseLayout
from paddlebox_tpu.embedding import (EmbeddingConfig, HostEmbeddingStore,
                                     PassWorkingSet, exchange, sharded,
                                     tiering)
from paddlebox_tpu.embedding.feed_pass import FeedPassManager
from paddlebox_tpu.embedding.working_set import (PushOperandStager,
                                                 bucket_size)
from paddlebox_tpu.metrics import auc as auc_lib
from paddlebox_tpu.models import base as model_base
from paddlebox_tpu.ops.seqpool_cvm import PooledSlots
from paddlebox_tpu.parallel import dense_sync
from paddlebox_tpu.train import optimizers
from paddlebox_tpu.parallel import mesh as mesh_lib
from paddlebox_tpu import monitor
from paddlebox_tpu.monitor import context as mon_ctx
from paddlebox_tpu.monitor import device_scopes
from paddlebox_tpu.monitor import trace as mon_trace
from paddlebox_tpu.monitor.timers import StageTimers
from paddlebox_tpu.utils import faultpoint
from paddlebox_tpu.utils.profiler import DumpStream, dump_tree, find_nonfinite

# arity of the binned-push host plan inside a staged batch tuple:
# (idx, mask, dense, labels, *plan[PLAN_ARITY], *extras) — _pack_host,
# _host_plan, and eval_pass's extras slice all key off this.
# plan = (order, rstart, end, uniq, segend): the first three are the
# kernel's token/block grouping, the last two the dedup pre-merge's
# unique-row segment bounds (sharded.plan_premerge). Zero-length
# arrays = that half is absent (the jit static branch). `order` has one
# entry a token; `uniq` and `segend` have one a LANE: a bucket over the
# most distinct rows a batch has had where the plan carries no kernel
# windows (_host_plan, _plan_lane_count), one a token elsewhere.
PLAN_ARITY = 5


@dataclasses.dataclass
class TrainerConfig:
    dense_lr: float = 1e-3
    dense_optimizer: str = "adam"  # adam|sgd|momentum|adagrad|rmsprop|ftrl
    dense_optimizer_kwargs: dict = dataclasses.field(default_factory=dict)
    global_batch_size: int = 256
    capacity_factor: float = 2.0           # all_to_all routing slack
    auc_buckets: int = 1 << 16
    label_slot: str = "label"
    check_nan_inf: bool = False            # FLAGS_check_nan_inf
    nan_dump_dir: str | None = None        # dump-all-scope dir on nan trip
    dump_fields_path: str | None = None    # DumpField per-instance stream
    # DumpField/DumpParam config (trainer_desc.proto:39-45). dump_fields
    # names extra per-instance columns beyond (step, pred, label):
    # "ins_id", any float slot name, or any sparse slot name (ids joined
    # by ","). dump_param names dense-param path substrings; matched
    # leaves are written to the stream at the end of each pass.
    dump_fields: tuple = ()
    dump_param: tuple = ()
    scale_sparse_grad_by_global_mean: bool = True
    # Dense sync (BoxPSWorkerParameter.sync_mode, trainer_desc.proto:100-108)
    dense_sync_mode: str = "allreduce"     # allreduce | kstep | async
    param_sync_step: int = 1               # K for kstep mode
    sync_dense_moment: bool = False        # FLAGS_enable_sync_dense_moment
    async_merge_limit: int = 4             # async table grad-merge bound
    async_betas: tuple = (0.99, 0.9999)    # reference's hard-coded betas


def _mean_replicated_grad(gp, axes):
    """Global MEAN of per-device dense grads, for grads of a replicated
    (in_spec P()) shard_map input.

    shard_map's autodiff psums the cotangent of replicated inputs to keep
    them replication-invariant, so `gp` already holds the cross-device SUM
    of local-mean grads when it reaches here (a pmean would be a no-op on
    the already-replicated value — and silently scale the effective LR by
    the mesh size). Dividing by the axis size yields the true global mean.
    """
    d = 1
    for a in axes:
        d = d * lax.axis_size(a)
    return jax.tree.map(lambda g: g / d, gp)


_NO_PLAN = np.zeros(0, np.int32)   # zero-length = "no host binned plan"


def _dense_tx(cfg: TrainerConfig) -> optax.GradientTransformation:
    return optimizers.make(cfg.dense_optimizer, cfg.dense_lr,
                           **cfg.dense_optimizer_kwargs)


class Trainer:
    """Pass-oriented trainer over a (node, dp) mesh."""

    def __init__(self, model, store: HostEmbeddingStore,
                 schema: DataFeedSchema, mesh: jax.sharding.Mesh,
                 config: TrainerConfig | None = None, seed: int = 0,
                 feed_mgr: FeedPassManager | None = None):
        self.model = model
        self.store = store
        self.schema = schema
        self.mesh = mesh
        self.cfg = config or TrainerConfig()
        self.layout = SparseLayout.from_schema(schema)
        self.n_shards = mesh_lib.num_shards(mesh)
        if self.cfg.global_batch_size % self.n_shards:
            raise ValueError("global_batch_size must divide by mesh size")
        model_dim = getattr(model, "emb_dim", None)
        if model_dim is not None and model_dim != self.store.cfg.total_dim:
            raise ValueError(
                f"model emb_dim={model_dim} must equal the table's trained "
                f"vector width total_dim={self.store.cfg.total_dim} "
                f"(dim={self.store.cfg.dim} + expand_dim="
                f"{self.store.cfg.expand_dim}); zoo models consume the full "
                f"pulled vector — a model that reads the expand part "
                f"separately should split with ops.pull_box_extended_sparse")
        if self.cfg.dense_sync_mode not in ("allreduce", "kstep", "async"):
            raise ValueError(self.cfg.dense_sync_mode)
        if self.cfg.param_sync_step < 1:
            raise ValueError(
                f"param_sync_step must be >= 1, got "
                f"{self.cfg.param_sync_step}")
        # Dense params/opt state are replicated over the mesh (the reference
        # copies dense params to every GPU, boxps_worker.cc:403-480). Placing
        # them explicitly — and pinning the step's out_shardings to match —
        # keeps the fed-back step signature bit-stable: without this, XLA's
        # sharding propagation picks its own output shardings and step #2
        # recompiles (~20s on a real chip).
        repl = mesh_lib.replicated_sharding(mesh)
        init_params = model.init(jax.random.PRNGKey(seed))
        self.tx = _dense_tx(self.cfg)
        self.dense_table = None
        self._stacked_sh = jax.sharding.NamedSharding(
            mesh, P(tuple(mesh.axis_names)))
        if self.cfg.dense_sync_mode == "kstep":
            # per-device dense copies: leading shard axis, local updates
            # between parameter-averaging syncs (local SGD)
            stacked = dense_sync.stack_for_shards(init_params, self.n_shards)
            self.params = jax.device_put(stacked, self._stacked_sh)
            self.opt_state = jax.device_put(
                dense_sync.stack_for_shards(self.tx.init(init_params),
                                            self.n_shards),
                self._stacked_sh)
            self._sync_fn = self._build_param_sync()
            self._collapse_fn = jax.jit(
                lambda p: jax.tree.map(lambda a: a[0], p),
                out_shardings=repl)
        elif self.cfg.dense_sync_mode == "async":
            self.params = jax.device_put(init_params, repl)
            flat, self._unravel = dense_sync.flatten_dense(init_params)
            self.dense_table = dense_sync.AsyncDenseTable(
                flat, lr=self.cfg.dense_lr, betas=self.cfg.async_betas,
                merge_limit=self.cfg.async_merge_limit)
            # In async mode the REAL optimizer state lives in the table;
            # expose it as opt_state so the (params, opt_state) checkpoint
            # pattern captures the Adam moments (refreshed at pass end).
            self.opt_state = self.dense_table.state_dict()
        else:
            self.params = jax.device_put(init_params, repl)
            self.opt_state = jax.device_put(self.tx.init(init_params), repl)
        # Flat dense-state transport (flags.flat_dense_state): the step
        # carries (params_flat, opt_f32_flat, *aux) instead of ~30 pytree
        # leaves — each argument leaf costs host-side dispatch time
        # (dense_sync.make_dense_packer). Allreduce only; public
        # self.params/self.opt_state stay pytrees — pack/unpack at pass
        # boundaries via pack_dense/unpack_dense.
        self._dense_packer = None
        n_dense_floats = sum(int(np.prod(l.shape))
                             for l in jax.tree.leaves(init_params))
        # The transport is for a tower of many small leaves: packing
        # copies the state inside the step and keeps the public trees
        # alive beside the flat vectors through a pass, so a tower past
        # FLAT_STATE_MAX_FLOATS keeps its tree (a few large leaves,
        # donated and updated in place).
        if (self.cfg.dense_sync_mode == "allreduce"
                and config_flags.flat_dense_state
                and n_dense_floats <= dense_sync.FLAT_STATE_MAX_FLOATS):
            # self.opt_state (built above in the allreduce branch) serves
            # as the shape/dtype template — no second tx.init
            self._dense_packer = dense_sync.make_dense_packer(
                init_params, self.opt_state)
        self._n_dense_args = (self._dense_packer[2]
                              if self._dense_packer else 2)
        # "read", "translate" and "extras" (pack thread) and "drain" emit
        # as stage/<name>; the others share their scope with a span
        # (timers("train", span="train_step")). "head" (entry of
        # train_pass to the first step's dispatch) and "close" enclose
        # other stages (critical_path.NESTED_STAGES); the rest are
        # disjoint on their thread.
        self.timers = StageTimers(["read", "translate", "extras", "train",
                                   "auc", "drain", "unique_keys",
                                   "preplan", "h2d", "head", "close"])
        # incremental + overlapped pass boundaries (BoxHelper FeedPass):
        # resident device rows are reused across passes, write-back is lazy.
        # Pass a shared manager when several trainers drive one table
        # (join/update phase programs — see train/phased.py).
        self.feed_mgr = feed_mgr or FeedPassManager(store, mesh)
        # Model-extras protocol: a model may declare `batch_extras(pb,
        # n_shards)` (+ `num_extras`) — a host-side pack-pipeline stage
        # producing per-batch arrays (e.g. PVRankModel's rank_offset)
        # that the step forwards to model.apply after the standard
        # arguments. Extras shard like the batch (contiguous dim-0).
        self._extras_fn = getattr(model, "batch_extras", None)
        self._n_extras = getattr(model, "num_extras", 0)
        if self._extras_fn is not None and self.cfg.dense_sync_mode != \
                "allreduce":
            raise NotImplementedError(
                "models with batch_extras support the allreduce "
                "dense-sync mode only")
        # What the model declares beside its loss (models/base.py):
        # whether it makes a prediction (the AUC accumulator and the
        # metric registry get it, or nothing), and the statistics its
        # loss returns each step (counters of the flight record).
        self._feeds_auc = model_base.predicts(model)
        self._n_stats = len(model_base.stat_names(model))
        if self._n_stats and self.cfg.dense_sync_mode != "allreduce":
            raise NotImplementedError(
                "models that declare stat_names support the allreduce "
                "dense-sync mode only")
        # Table-layout engine (flags.table_layout): which embedding
        # exchange the step programs compile with. "sharded" routes the
        # dedup plan's unique rows through embedding/exchange.py (wire-
        # compressed push payload, per-shard fused pull after routing);
        # "single" keeps the legacy token-level routed path. Trace-time
        # static; on engines() and in the flight record, like
        # pull_engine.
        self.table_layout = self._select_table_layout()
        self.exchange_wire = (exchange.select_wire(self.store.cfg)
                              if self.table_layout == "sharded" else None)
        # All_to_all decomposition for the push exchange: "hier" = the
        # two-stage intra-host/inter-host exchange on a (node, dp) mesh
        # (host-merged unique lanes cross the inter-host leg once),
        # "flat" = the one-stage global a2a (flags.exchange_topology).
        self.exchange_topology = (
            exchange.select_topology(self.mesh.devices.shape)
            if self.table_layout == "sharded" else None)
        # Per-pass wire adaptation (flags.exchange_adaptive): the
        # controller re-costs the wires at every owned pass boundary
        # from the pass's exchange counter deltas (+ any fed flow-edge
        # attribution, note_flow_attribution) and switches
        # self.exchange_wire for the NEXT pass — a switch recompiles
        # the steps like the adaptive capacity doubling.
        self._wire_controller = (
            exchange.WireController(self.store.cfg, self.exchange_wire)
            if self.table_layout == "sharded"
            and config_flags.exchange_adaptive else None)
        self._flow_attribution: tuple | None = None
        self._last_wire_decision: dict | None = None
        self._wire_stats0: dict | None = None
        # Self-healing runtime (flags.self_healing, runtime/remediation):
        # bound by enable_self_healing(); remediation_boundary() runs it
        # at every pass boundary before the flight-record commit.
        self._remediation = None
        # Storage-tier identity of the host table ("spill" /
        # "sharded+spill" / None for the in-RAM store) — flight-record
        # extra, like table_layout; the tier is a storage choice, never
        # a math change (embedding/tiering.py)
        self.table_tiering = tiering.describe(store)
        # HBM replica hot tier (flags.use_replica_cache): the top of the
        # SSD→RAM→HBM hierarchy — a device-resident plane of the rows
        # the TierManager ranks hottest, rebuilt at every owned pass
        # boundary (refresh_replica_boundary), serving the stager's
        # fresh-key pulls without touching the RAM/SSD path. Placement
        # only: bit-identical on or off.
        self.replica_cache = None
        if config_flags.use_replica_cache:
            from paddlebox_tpu.embedding.replica_cache import \
                TrainerReplicaCache
            self.replica_cache = TrainerReplicaCache(store, mesh=mesh)
            self.feed_mgr.set_replica(self.replica_cache)
        if (self.table_layout == "sharded"
                and config_flags.exchange_capacity_factor > 0):
            # operator-set starting capacity for the exchange lanes (the
            # overflow policy still preplans/grows — never-silent drops)
            self.cfg.capacity_factor = max(
                self.cfg.capacity_factor,
                float(config_flags.exchange_capacity_factor))
        # Pull engine: multi-hot/wide-dim layouts pool the pulled rows
        # per (example, slot) INSIDE the pull (fused gather-pool) so the
        # (B*T, pull_width) token matrix never crosses the model; the
        # heuristic is trace-time static, like the push engine.
        self.pull_engine = self._select_pull_engine()
        # Host-side binned-push plan (native counting sort in the pack
        # pipeline) replaces the on-device argsort of the scatter-free
        # push — single-shard TPU tables, plus the sharded exchange
        # engine, whose all_to_all is KEYED off the plan's dedup bounds
        # (unique lanes premerge before routing; post-a2a tokens carry
        # no kernel windows), plus a FORCED fused push engine on any
        # backend (scatter_accumulate consumes the plan's premerged
        # unique lanes; off-TPU it runs the identical jnp math — the
        # CPU-parity/A/B knob). Read at trace time like the kernels.
        fused_forced = config_flags.push_engine == "scatter_accumulate"
        self._use_plan = (
            (self.n_shards == 1
             and ((config_flags.binned_push
                   and jax.default_backend() == "tpu") or fused_forced))
            or (self.table_layout == "sharded"
                and config_flags.pullpush_dedup_keys))
        # eval capacity can grow past the train factor (skewed eval-only
        # datasets) without ever touching the train step's compilation
        self._eval_capacity = self.cfg.capacity_factor
        # lanes of the windowless dedup plan (_plan_lane_count): grow-only
        # for the trainer's life, so a day compiles one step and one
        # apply per rung of working_set.bucket_size reached
        self._plan_lanes = 0
        # Deferred sparse-push pipeline (flags.push_overlap): the step
        # returns packed push operands off the loss-producing path; the
        # apply program for step N dispatches while step N+1's pack and
        # plan-H2D run. Operands ride a double-buffered stager (bounded
        # staleness: ONE unapplied step, enforced there); flushed at
        # pass boundaries and before eval/save (feed-manager pre-flush
        # hook). Bit-identical to the inline push — the apply is always
        # sequenced before the next step consumes the table.
        self.push_overlap = self._select_push_overlap()
        self._push_stager = PushOperandStager()
        self.push_applies = 0       # deferred applies dispatched (tests)
        self._overlap_ws = None
        # mid-pass snapshot hook (enable_midpass_snapshots): (checkpointer,
        # every_steps, box, metrics). midpass_cursor_extra carries
        # driver-supplied cursor fields — notably the shuffle RNG state
        # captured BEFORE the pass's permutation draw, so a mid-pass
        # resume replays the identical pass order.
        self._midpass: tuple | None = None
        self.midpass_cursor_extra: dict = {}
        # elastic peer liveness hook (distributed/resilience.ElasticWorld
        # .check): polled once per step so a dead/stalled peer aborts the
        # step loop at a safe boundary (the finally below drains the
        # push-overlap stager and rebinds live state) instead of training
        # on until the next pass barrier. None = no watchdog attached.
        self.peer_check: Callable[[], None] | None = None
        # post-pass cursor crumbs for the elastic drain snapshot: how far
        # the (possibly aborted) last pass got, its working set, and
        # whether it ended by exception
        self.last_pass_steps = 0
        self._last_ws = None
        self._last_dense: tuple | None = None
        self._pass_aborted = False
        self.feed_mgr.register_pre_flush(self.flush_push)
        # the programs the last pass under a profiler capture ran, each
        # with its call's argument specs (device_scope_table)
        self._scope_programs: dict = {}
        self._rebuild_steps()
        self._auc_fn = jax.jit(auc_lib.auc_update)
        self._auc_masked_fn = jax.jit(
            lambda s, p, y, m: auc_lib.auc_update(s, p, y, mask=m))
        self.global_step = 0

    # ------------------------------------------------------------------
    def pack_dense(self, params=None, opt_state=None) -> tuple:
        """(params, opt_state) → the dense-state tuple `_step_fn`
        consumes (identity pair when the flat path is off). Callers use
        `tr._step_fn(table, *tr.pack_dense(...), idx, ...)` uniformly."""
        params = self.params if params is None else params
        opt_state = self.opt_state if opt_state is None else opt_state
        if self._dense_packer is None:
            return (params, opt_state)
        return self._dense_packer[0](params, opt_state)

    def unpack_dense(self, state: tuple):
        """Inverse of pack_dense → (params, opt_state) pytrees."""
        if self._dense_packer is None:
            return state[0], state[1]
        return self._dense_packer[1](state)

    # zero-length plan arrays = "no host binned-push plan" (the step's
    # trace-time static branch); external _step_fn callers pass three of
    # these when they have no plan
    NO_PLAN = _NO_PLAN

    def split_step_out(self, out: tuple):
        """Step output tuple → (table, dense_state, loss, preds, dropped).

        The step returns (table, *dense_state, loss, preds, dropped);
        dense_state length varies with the flat-transport mode — every
        caller must slice through THIS helper, not by hand."""
        nd = self._n_dense_args
        return out[0], out[1:1 + nd], out[-3], out[-2], out[-1]

    # ------------------------------------------------------------------
    def _float_split(self) -> tuple[int, int, int]:
        """(label_col_start, label_width, total_float_width)."""
        label_col, label_w, col = self.schema.float_split_cols(
            self.cfg.label_slot)
        if label_col < 0:
            raise ValueError(f"label slot {self.cfg.label_slot!r} not found")
        return label_col, label_w, col

    def split_floats(self, floats: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        lc, lw, total = self._float_split()
        labels = floats[:, lc:lc + lw].reshape(-1)
        dense = np.concatenate([floats[:, :lc], floats[:, lc + lw:]], axis=1)
        return labels, dense

    # ------------------------------------------------------------------
    def _fwd_bwd_push(self, defer: bool = False):
        """Shared shard_map core: routed pull → fwd/bwd → routed push.

        Returns a fn(tshard, idx_l, mask_l, dense_l, labels_l, params_local)
        → (new_shard, local_dense_grads, local_loss, preds).

        defer: the push stage returns its packed operands
        (sharded.deferred_push_operands — premerged in-step when the host
        plan carries dedup bounds) INSTEAD of applying them; the first
        element of the core's return is then the uniform-arity operand
        triple, not the updated shard (flags.push_overlap)."""
        cfg = self.cfg
        emb_cfg = self.store.cfg
        axes = tuple(self.mesh.axis_names)
        seg = self.layout.segment_ids
        T = self.layout.total_len
        D = self.n_shards
        model = self.model
        capf = cfg.capacity_factor
        num_slots = self.layout.num_slots
        # the loss and the prediction are the model's to declare
        # (models/base.py): (loss, (preds, stats)) of one local batch
        model_loss = model_base.declared_loss(model, seg, num_slots)

        # FLAGS_enable_pullpush_dedup_keys (flags.cc:603): merge duplicate
        # tokens before the all_to_all so routed traffic carries each key
        # once. The dedup sort costs ~6ms at 213k tokens on one v5e —
        # far more than a single-chip step — so it only engages on
        # multi-shard meshes where ICI volume is what it buys down.
        dedup = config_flags.pullpush_dedup_keys and self.n_shards > 1
        fused_pull = self.pull_engine == "fused_gather_pool"
        L_hot = T // num_slots if fused_pull else 0
        # sharded exchange engine (flags.table_layout): plan-keyed a2a
        # with the wire-compressed push payload (embedding/exchange.py)
        sharded_x = self.table_layout == "sharded"
        wire = self.exchange_wire
        topo = self.exchange_topology or "flat"

        @monitor.device_scope("push")
        def push_tail(tshard, flat_idx, sgrad, mask_l, labels_l, plan):
            """Push stage tail: deferred operands, or the inline routed
            merge-update. Deferred: the apply program
            replays the same inputs one step later (Trainer._apply_fn)."""
            if defer:
                # the step's half of a deferred push is its operands: the
                # premerge's, with the counters' increments it merges
                with monitor.device_scope("premerge"):
                    show_inc = mask_l.reshape(-1).astype(jnp.float32)
                    clk_inc = (mask_l.astype(jnp.float32)
                               * labels_l[:, None]).reshape(-1)
                    return sharded.deferred_push_operands(
                        flat_idx, sgrad, show_inc, clk_inc, plan)
            show_inc = mask_l.reshape(-1).astype(jnp.float32)
            clk_inc = (mask_l.astype(jnp.float32)
                       * labels_l[:, None]).reshape(-1)
            if sharded_x:
                return exchange.routed_push(tshard, flat_idx, sgrad,
                                            show_inc, clk_inc, emb_cfg,
                                            axes, capf, wire=wire,
                                            plan=plan, topology=topo)
            return sharded.routed_push(tshard, flat_idx, sgrad, show_inc,
                                       clk_inc, emb_cfg, axes, capf,
                                       dedup=dedup, plan=plan)

        def core(tshard, idx_l, mask_l, dense_l, labels_l, params,
                 order, rstart, endb, uniq, segb, *extras_l):
            # zero-length arrays == "no host plan" (static shape branch)
            plan = ((order, rstart, endb, uniq, segb)
                    if order.shape[0] or uniq.shape[0] else None)
            B_l = idx_l.shape[0]
            flat_idx = idx_l.reshape(-1)
            if fused_pull:
                # fused gather-pool pull (single-shard by the heuristic):
                # rows pool per (example, slot) inside the pull and the
                # model consumes the (B, S, P) sums via PooledSlots — the
                # (B*T, P) token matrix exists in neither direction
                # (backward expands the pooled cotangent per token
                # straight into the premerge/binned push).
                with monitor.device_scope("pull"):
                    if sharded_x:
                        # route the unique rows once, pool per shard from
                        # the received lanes (gather_pool after routing)
                        pooled, dropped = exchange.routed_pull_pooled(
                            tshard, idx_l, emb_cfg, axes, num_slots, L_hot,
                            capf, plan=plan, return_dropped=True)
                    else:
                        pooled = sharded.fused_pull_pool(
                            tshard, idx_l, emb_cfg, num_slots, L_hot)
                        dropped = jnp.zeros((), jnp.int32)

                def loss_fn(p, pooled_in):
                    return model_loss(p, PooledSlots(pooled_in), mask_l,
                                      dense_l, labels_l, *extras_l)

                # the tower's scope reaches its backward pass, and the
                # token gradients that pass ends in
                with monitor.device_scope("tower"):
                    grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1),
                                                 has_aux=True)
                    (loss, (preds, stats)), (gp, gpooled) = grad_fn(
                        params, pooled)
                    sgrad = sharded.pooled_grad_tokens(gpooled, mask_l,
                                                       seg, num_slots)
                    if cfg.scale_sparse_grad_by_global_mean:
                        sgrad = sgrad / D
                new_shard = push_tail(tshard, flat_idx, sgrad, mask_l,
                                      labels_l, plan)
                return (new_shard, gp, loss, preds, lax.psum(dropped, axes),
                        *stats)
            with monitor.device_scope("pull"):
                if sharded_x:
                    pulled, dropped = exchange.routed_pull(
                        tshard, flat_idx, emb_cfg, axes, capf, plan=plan,
                        dedup=dedup, return_dropped=True)
                else:
                    pulled, dropped = sharded.routed_lookup(
                        tshard, flat_idx, emb_cfg, axes, capf, dedup=dedup,
                        return_dropped=True)
                pulled = pulled.reshape(B_l, T, emb_cfg.pull_width)

            def loss_fn(p, pulled_in):
                return model_loss(p, pulled_in, mask_l, dense_l, labels_l,
                                  *extras_l)

            with monitor.device_scope("tower"):
                grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1),
                                             has_aux=True)
                (loss, (preds, stats)), (gp, gpull) = grad_fn(params,
                                                              pulled)
                # sparse grads: only (w, embedx) columns train; show/clk
                # are counters (CVM grads dropped, like cvm_op's grad)
                sgrad = gpull[..., 2:].reshape(B_l * T, emb_cfg.grad_width)
                if cfg.scale_sparse_grad_by_global_mean:
                    sgrad = sgrad / D
            new_shard = push_tail(tshard, flat_idx, sgrad, mask_l,
                                  labels_l, plan)
            # capacity-drop monitor: global count of tokens the fixed-size
            # all_to_all lanes could not carry this step (push routes the
            # same tokens at the same capacity, so one count covers both)
            dropped_g = lax.psum(dropped, axes)
            return new_shard, gp, loss, preds, dropped_g, *stats

        return core

    def _build_train_step(self, defer: bool = False) -> Callable:
        cfg = self.cfg
        axes = tuple(self.mesh.axis_names)
        tx = self.tx
        # deferred push (flags.push_overlap): allreduce programs only
        assert not defer or cfg.dense_sync_mode == "allreduce"
        core = self._fwd_bwd_push(defer=defer)
        batch_spec = P(axes)
        repl = mesh_lib.replicated_sharding(self.mesh)
        tbl_sh = mesh_lib.table_sharding(self.mesh)
        bat_sh = mesh_lib.batch_sharding(self.mesh)
        mode = cfg.dense_sync_mode

        if mode == "kstep":
            # local dense update inside shard_map; params carry a leading
            # shard axis (each device trains its own copy between syncs)
            def body(tshard, idx_l, mask_l, dense_l, labels_l, p_st, o_st,
                     order, rstart, endb, uniq, segb):
                p = jax.tree.map(lambda a: a[0], p_st)
                o = jax.tree.map(lambda a: a[0], o_st)
                new_shard, gp, loss, preds, drop_g = core(
                    tshard, idx_l, mask_l, dense_l, labels_l, p,
                    order, rstart, endb, uniq, segb)
                with monitor.device_scope("dense_update"):
                    updates, new_o = tx.update(gp, o, p)
                    new_p = optax.apply_updates(p, updates)
                loss_g = lax.pmean(loss, axes)
                lift = lambda t: jax.tree.map(lambda a: a[None], t)
                return (new_shard, lift(new_p), lift(new_o), loss_g, preds,
                        drop_g)

            def step(table, params, opt_state, idx, mask, dense, labels,
                     order=_NO_PLAN, rstart=_NO_PLAN, endb=_NO_PLAN,
                     uniq=_NO_PLAN, segb=_NO_PLAN):
                return jax.shard_map(
                    body, mesh=self.mesh,
                    in_specs=(batch_spec, batch_spec, batch_spec, batch_spec,
                              batch_spec, batch_spec, batch_spec, batch_spec,
                              batch_spec, batch_spec, batch_spec,
                              batch_spec),
                    out_specs=(batch_spec, batch_spec, batch_spec, P(),
                               batch_spec, P()),
                )(table, idx, mask, dense, labels, params, opt_state,
                  order, rstart, endb, uniq, segb)

            return jax.jit(step, donate_argnums=(0, 1, 2),
                           out_shardings=(tbl_sh, self._stacked_sh,
                                          self._stacked_sh, repl, bat_sh,
                                          repl))

        if mode == "async":
            # grads are globally averaged and returned flat; the host-side
            # AsyncDenseTable owns the optimizer (BoxPSAsynDenseTable)
            from jax.flatten_util import ravel_pytree

            def body(tshard, idx_l, mask_l, dense_l, labels_l, params,
                     order, rstart, endb, uniq, segb):
                new_shard, gp, loss, preds, drop_g = core(
                    tshard, idx_l, mask_l, dense_l, labels_l, params,
                    order, rstart, endb, uniq, segb)
                with monitor.device_scope("dense_update"):
                    gp = _mean_replicated_grad(gp, axes)
                loss_g = lax.pmean(loss, axes)
                return new_shard, gp, loss_g, preds, drop_g

            def step(table, params, idx, mask, dense, labels,
                     order=_NO_PLAN, rstart=_NO_PLAN, endb=_NO_PLAN,
                     uniq=_NO_PLAN, segb=_NO_PLAN):
                new_table, gp, loss, preds, drop_g = jax.shard_map(
                    body, mesh=self.mesh,
                    in_specs=(batch_spec, batch_spec, batch_spec, batch_spec,
                              batch_spec, P(), batch_spec, batch_spec,
                              batch_spec, batch_spec, batch_spec),
                    out_specs=(batch_spec, P(), P(), batch_spec, P()),
                )(table, idx, mask, dense, labels, params,
                  order, rstart, endb, uniq, segb)
                with monitor.device_scope("dense_update"):
                    gp_flat = ravel_pytree(gp)[0]
                return new_table, gp_flat, loss, preds, drop_g

            return jax.jit(step, donate_argnums=(0,),
                           out_shardings=(tbl_sh, repl, repl, bat_sh, repl))

        n_extras = self._n_extras
        # head of the step output: the updated table (inline push) or the
        # uniform-arity deferred push operand triple (flags.push_overlap)
        n_head = 3 if defer else 1

        n_st = 1 if self._n_stats else 0
        stat_names = model_base.stat_names(self.model)

        def body(tshard, idx_l, mask_l, dense_l, labels_l, params,
                 order, rstart, endb, uniq, segb, *extras_l):
            head, gp, loss, preds, drop_g, *stats = core(
                tshard, idx_l, mask_l, dense_l, labels_l, params,
                order, rstart, endb, uniq, segb, *extras_l)
            with monitor.device_scope("dense_update"):
                gp = _mean_replicated_grad(gp, axes)
            loss_g = lax.pmean(loss, axes)
            head = head if defer else (head,)
            stats = [model_base.reduce_stats(stat_names, st, axes)
                     for st in stats]
            return (*head, gp, *stats, loss_g, preds, drop_g)

        def run_body(table, params, opt_state, idx, mask, dense, labels,
                     order, rstart, endb, uniq, segb, *extras):
            out = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(batch_spec, batch_spec, batch_spec, batch_spec,
                          batch_spec, P(), batch_spec, batch_spec,
                          batch_spec, batch_spec, batch_spec)
                + (batch_spec,) * n_extras,
                out_specs=(batch_spec,) * n_head
                + (P(),) * (1 + n_st) + (P(), batch_spec, P()),
            )(table, idx, mask, dense, labels, params,
              order, rstart, endb, uniq, segb, *extras)
            head, gp, tail = out[:n_head], out[n_head], out[n_head + 1:]
            with monitor.device_scope("dense_update"):
                updates, new_opt = tx.update(gp, opt_state, params)
                new_params = optax.apply_updates(params, updates)
            # tail: (*stats, loss, preds, drop_g)
            return head, new_params, new_opt, tail

        if self._dense_packer is not None:
            pack_fn, unpack_fn, n_dense = self._dense_packer

            def step_flat(table, *args):
                dstate = args[:n_dense]
                (idx, mask, dense, labels, order, rstart,
                 endb, uniq, segb, *extras) = args[n_dense:]
                # the flat transport's copies are the dense update's
                with monitor.device_scope("dense_update"):
                    params, opt_state = unpack_fn(dstate)
                head, new_params, new_opt, tail = \
                    run_body(table, params, opt_state, idx, mask, dense,
                             labels, order, rstart, endb, uniq, segb,
                             *extras)
                with monitor.device_scope("dense_update"):
                    packed = pack_fn(new_params, new_opt)
                if defer:
                    # (*dstate, g0, g1, g2, [stats,] loss, preds,
                    # dropped): the table is read, never written — the
                    # apply program owns the update (split_defer_out)
                    return (*packed, *head, *tail)
                return (head[0], *packed, *tail)

            if defer:
                return jax.jit(
                    step_flat, donate_argnums=tuple(range(1, 1 + n_dense)),
                    out_shardings=(repl,) * n_dense + (bat_sh,) * 3
                    + (repl,) * n_st + (repl, bat_sh, repl))

            return jax.jit(step_flat, donate_argnums=(0, 1, 2),
                           out_shardings=(tbl_sh,) + (repl,) * n_dense
                           + (repl,) * n_st + (repl, bat_sh, repl))

        def step(table, params, opt_state, idx, mask, dense, labels,
                 order=_NO_PLAN, rstart=_NO_PLAN, endb=_NO_PLAN,
                 uniq=_NO_PLAN, segb=_NO_PLAN, *extras):
            head, new_params, new_opt, tail = run_body(
                table, params, opt_state, idx, mask, dense, labels,
                order, rstart, endb, uniq, segb, *extras)
            if defer:
                return (new_params, new_opt, *head, *tail)
            return (head[0], new_params, new_opt, *tail)

        if defer:
            return jax.jit(step, donate_argnums=(1, 2),
                           out_shardings=(repl, repl) + (bat_sh,) * 3
                           + (repl,) * n_st + (repl, bat_sh, repl))
        # Donation aliases the (large) table and the dense state in place;
        # pinned out_shardings make output signatures identical to the inputs
        # so the train_pass feedback loop never retraces.
        return jax.jit(step, donate_argnums=(0, 1, 2),
                       out_shardings=(tbl_sh, repl, repl)
                       + (repl,) * n_st + (repl, bat_sh, repl))

    def _build_apply_fn(self) -> Callable:
        """The deferred table-apply program (flags.push_overlap): consumes
        the previous step's staged batch operands + the step's packed push
        operands and runs EXACTLY the merge-update the inline step would
        have — same functions, same inputs, so the result is bit-identical;
        only the program boundary moved. Donates the table; dispatched by
        the trainer while the next batch's pack/plan-H2D proceeds, and
        always sequenced before the next step consumes its output."""
        cfg = self.cfg
        emb_cfg = self.store.cfg
        axes = tuple(self.mesh.axis_names)
        capf = cfg.capacity_factor
        dedup = config_flags.pullpush_dedup_keys and self.n_shards > 1
        sharded_x = self.table_layout == "sharded"
        wire = self.exchange_wire
        topo = self.exchange_topology or "flat"
        batch_spec = P(axes)
        tbl_sh = mesh_lib.table_sharding(self.mesh)

        @monitor.device_scope("push")       # the whole program is the push
        def body(tshard, idx_l, mask_l, labels_l, order, rstart, endb,
                 uniq, segb, g0, g1, g2):
            if uniq.shape[0] and g1.shape[0]:
                # the step already premerged onto the plan's unique lanes
                # (deferred_push_operands); replay only the engine —
                # through the exchange's wire-compressed route on the
                # sharded engine, the local merge-update otherwise
                if sharded_x:
                    return exchange.routed_push(tshard, uniq, g0, g1, g2,
                                                emb_cfg, axes, capf,
                                                wire=wire, premerged=True,
                                                topology=topo)
                kplan = ((None, rstart, endb) if rstart.shape[0]
                         else None)
                return sharded.push(tshard, uniq, g0, g1, g2, emb_cfg,
                                    plan=kplan, premerged=True)
            flat_idx = idx_l.reshape(-1)
            show_inc = mask_l.reshape(-1).astype(jnp.float32)
            clk_inc = (mask_l.astype(jnp.float32)
                       * labels_l[:, None]).reshape(-1)
            plan = ((order, rstart, endb, uniq, segb)
                    if order.shape[0] or uniq.shape[0] else None)
            if sharded_x:
                return exchange.routed_push(tshard, flat_idx, g0,
                                            show_inc, clk_inc, emb_cfg,
                                            axes, capf, wire=wire,
                                            plan=plan, topology=topo)
            return sharded.routed_push(tshard, flat_idx, g0, show_inc,
                                       clk_inc, emb_cfg, axes, capf,
                                       dedup=dedup, plan=plan)

        def apply(table, idx, mask, labels, order, rstart, endb, uniq,
                  segb, g0, g1, g2):
            return jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(batch_spec,) * 12,
                out_specs=batch_spec,
            )(table, idx, mask, labels, order, rstart, endb, uniq, segb,
              g0, g1, g2)

        return jax.jit(apply, donate_argnums=(0,), out_shardings=tbl_sh)

    def _select_push_overlap(self) -> bool:
        """Whether training runs the deferred sparse-push pipeline
        (flags.push_overlap, read at construction — trace-time static,
        like the engine heuristics). "auto" = on where dense sync
        permits: the allreduce mode (kstep trains per-shard dense copies
        inside the step and async already decouples dense through the
        host table — both need the inline apply). Mirrors AsyncDenseTable's dispatch-decoupling semantics
        on the sparse side with a hard one-step staleness bound."""
        po = config_flags.push_overlap
        if po not in ("auto", "on", "off"):
            raise ValueError(f"push_overlap={po!r}")
        if po == "off":
            return False
        ok = self.cfg.dense_sync_mode == "allreduce"
        if po == "on" and not ok:
            raise ValueError(
                "flags.push_overlap='on' needs the allreduce dense-sync "
                "mode (the deferred apply is sequenced between its step "
                "programs)")
        return ok

    def split_defer_out(self, out: tuple):
        """Deferred step output tuple → (dense_state, push_ops, loss,
        preds, dropped). The deferred step returns (*dense_state, g0, g1,
        g2, loss, preds, dropped) — no table; the apply program owns the
        update. Callers must slice through THIS helper (dense_state
        length varies with the flat-transport mode)."""
        nd = self._n_dense_args
        return (out[:nd], out[nd:nd + 3], out[-3], out[-2], out[-1])

    def _build_param_sync(self) -> Callable:
        """K-step parameter averaging (SyncParam, boxps_worker.cc:481-521).

        One pmean over every mesh axis — XLA decomposes it into the
        reference's intra-node reduce-scatter → inter-node → all-gather
        hierarchy on a 2D (node, dp) mesh."""
        axes = tuple(self.mesh.axis_names)
        batch_spec = P(axes)
        sync_moment = self.cfg.sync_dense_moment

        def body(p_st, o_st):
            with monitor.device_scope("dense_update"):
                avg = jax.tree.map(lambda a: lax.pmean(a, axes), p_st)
                if sync_moment:  # FLAGS_enable_sync_dense_moment
                    o_st = jax.tree.map(lambda a: lax.pmean(a, axes), o_st)
            return avg, o_st

        def sync(params, opt_state):
            return jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(batch_spec, batch_spec),
                out_specs=(batch_spec, batch_spec),
            )(params, opt_state)

        return jax.jit(sync, donate_argnums=(0, 1),
                       out_shardings=(self._stacked_sh, self._stacked_sh))

    def _build_eval_step(self) -> Callable:
        emb_cfg = self.store.cfg
        axes = tuple(self.mesh.axis_names)
        seg = self.layout.segment_ids
        T = self.layout.total_len
        model = self.model
        capf = max(self.cfg.capacity_factor, self._eval_capacity)
        dedup = config_flags.pullpush_dedup_keys and self.n_shards > 1

        num_slots = self.layout.num_slots
        n_extras = self._n_extras
        fused_pull = self.pull_engine == "fused_gather_pool"
        L_hot = T // num_slots if fused_pull else 0
        sharded_x = self.table_layout == "sharded"

        def body(tshard, idx_l, mask_l, dense_l, params, *extras_l):
            B_l = idx_l.shape[0]
            if fused_pull:
                with monitor.device_scope("pull"):
                    if sharded_x:
                        # eval packs no plan: the pooled route dedups on
                        # device, pools per shard from the received lanes
                        pooled, fdrop = exchange.routed_pull_pooled(
                            tshard, idx_l, emb_cfg, axes, num_slots, L_hot,
                            capf, return_dropped=True)
                    else:
                        pooled = sharded.fused_pull_pool(tshard, idx_l,
                                                         emb_cfg, num_slots,
                                                         L_hot)
                        fdrop = jnp.zeros((), jnp.int32)
                with monitor.device_scope("tower"):
                    logits = model.apply(params, PooledSlots(pooled),
                                         mask_l, dense_l, seg, num_slots,
                                         *extras_l)
                    return jax.nn.sigmoid(logits), lax.psum(fdrop, axes)
            with monitor.device_scope("pull"):
                pulled, dropped = (
                    exchange.routed_pull(tshard, idx_l.reshape(-1), emb_cfg,
                                         axes, capf, dedup=dedup,
                                         return_dropped=True)
                    if sharded_x else
                    sharded.routed_lookup(tshard, idx_l.reshape(-1),
                                          emb_cfg, axes, capf, dedup=dedup,
                                          return_dropped=True))
                pulled = pulled.reshape(B_l, T, emb_cfg.pull_width)
            with monitor.device_scope("tower"):
                logits = model.apply(params, pulled, mask_l, dense_l, seg,
                                     num_slots, *extras_l)
                return jax.nn.sigmoid(logits), lax.psum(dropped, axes)

        batch_spec = P(axes)

        @jax.jit
        def step(table, params, idx, mask, dense, *extras):
            return jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(batch_spec, batch_spec, batch_spec, batch_spec,
                          P()) + (batch_spec,) * n_extras,
                out_specs=(batch_spec, P()),
            )(table, idx, mask, dense, params, *extras)

        return step

    # ------------------------------------------------------------------
    def _pack_host(self, ws: PassWorkingSet, pb: PackedBatch,
                   with_plan: bool = True) -> tuple:
        """Host half of the pack: translate + host plan + extras. Safe on
        the pack thread — it touches no device API (the in-process CPU
        backend deadlocks its collective rendezvous when another thread
        dispatches transfers mid-step, and single-dispatcher discipline
        costs nothing: the put itself is an async dispatch)."""
        faultpoint.hit("trainer.pack.pre")
        with self.timers("translate"):
            idx = ws.translate(pb.ids, pb.mask)
            labels, dense = self.split_floats(pb.floats)
            plan = (self._host_plan(ws, idx) if with_plan
                    else (np.zeros(0, np.int32),) * PLAN_ARITY)
            # embedding-plane traffic counters (flight-record deltas):
            # pull = tokens * pull_width rows out, push = grad + show/clk
            # lanes back (approximate routed volume; benchmark/work.py
            # counts what the step must move, from the shapes)
            ecfg = self.store.cfg
            monitor.counter_add("trainer.tokens", idx.size)
            monitor.counter_add("trainer.pull_bytes",
                                idx.size * 4 * ecfg.pull_width)
            if with_plan:
                monitor.counter_add("trainer.push_bytes",
                                    idx.size * 4 * (ecfg.grad_width + 2))
        extras = ()
        if self._extras_fn is not None:
            # what the model's own host stage adds to a batch (a sequence
            # slot's ids within its vocabulary, a PV batch's rank_offset)
            with self.timers("extras"):
                extras = self._extras_fn(pb, self.n_shards)
        return (idx, pb.mask, dense.astype(np.float32),
                labels.astype(np.float32), *plan, *extras)

    def _stage_device(self, host_tuple: tuple):
        # ONE device_put for all arrays: each put is its own
        # host->device dispatch
        with self.timers("h2d", span="h2d_stage"):
            return jax.device_put(
                host_tuple, mesh_lib.batch_sharding(self.mesh))

    def _put_batch(self, ws: PassWorkingSet, pb: PackedBatch,
                   with_plan: bool = True):
        return self._stage_device(self._pack_host(ws, pb, with_plan))

    def _pack_iter(self, dataset, ws: PassWorkingSet, batch_size: int,
                   with_plan: bool = True, drop_last: bool = True):
        """Yield (pb, staged) batches with translate + host plan + H2D
        dispatched on a background thread, `flags.prefetch_batches`
        batches ahead of the training loop — the MiniBatchGpuPack
        pipeline (data_feed.h:1372-1535). The main thread's queue wait
        is timed as the "read" stage (starvation = host-bound pass).

        drop_last=False pads the tail batch instead (eval passes score
        every example; pb.num keeps the pre-pad valid count)."""
        def batch_source():
            for pb in dataset.batches(batch_size, drop_last=drop_last):
                if len(pb.floats) < batch_size:
                    pb = pb.pad_to(batch_size)
                yield pb

        def raw_iter():
            depth = config_flags.prefetch_batches
            if depth <= 0:
                for pb in batch_source():
                    yield pb, self._pack_host(ws, pb, with_plan=with_plan)
                return
            import queue as queue_mod
            q: Any = queue_mod.Queue(maxsize=depth)
            done = object()
            cancel = threading.Event()

            def producer():
                n_packed = 0
                try:
                    for pb in batch_source():
                        if cancel.is_set():
                            return      # abandoned consumer: stop packing
                        # host work only — the device_put happens on the
                        # consumer thread (single-dispatcher discipline,
                        # see _pack_host)
                        q.put((pb, self._pack_host(ws, pb,
                                                   with_plan=with_plan)))
                        n_packed += 1
                    # emitted from THIS worker thread: inherits the pass/
                    # step context (monitor.context.spawn below)
                    monitor.event("pack_producer_done", batches=n_packed)
                    q.put(done)
                except BaseException as e:  # re-raised on the main thread
                    q.put(("__pack_error__", e))

            t = mon_ctx.spawn(producer, name="pbtpu-pack")
            t.start()
            try:
                while True:
                    with self.timers("read"):
                        item = q.get()
                    if item is done:
                        break
                    if (isinstance(item, tuple) and len(item) == 2
                            and item[0] == "__pack_error__"):
                        raise item[1]
                    yield item
            finally:
                # consumer abandoned mid-pass (nan trip, exception):
                # signal the producer to stop after its current batch —
                # without the event it would translate the entire
                # remaining dataset before the exception could propagate
                # — and drain the queue so a blocked put() wakes up to
                # see the event
                cancel.set()
                while t.is_alive():
                    try:
                        q.get_nowait()
                    except queue_mod.Empty:
                        t.join(timeout=0.1)
                t.join()

        raw = raw_iter()
        try:
            for pb, host_tuple in raw:
                yield pb, self._stage_device(host_tuple)
        finally:
            # closing this generator must shut the producer down NOW
            # (GeneratorExit propagates here, not into the suspended
            # inner frame)
            raw.close()

    def _host_plan(self, ws: PassWorkingSet, idx: np.ndarray):
        """Binned-push token grouping + optional dedup pre-merge bounds,
        on the host pack pipeline (pallas_kernels.binned_push's `plan` /
        sharded.plan_premerge). Zero-length arrays mean "that half is
        absent" — the step's static-shape branch then keeps the
        on-device grouping (or the XLA scatter path off-TPU). The dedup
        bounds have one lane a token, except on one shard with no kernel
        windows (what a plane table takes): there a bucket over the most
        distinct rows a batch has had (_plan_lane_count)."""
        Z = np.zeros(0, np.int32)
        empty = (Z,) * PLAN_ARITY
        if not self._use_plan:
            return empty
        if self.table_layout == "sharded" and self.n_shards > 1:
            # sharded exchange: the plan's dedup bounds key the a2a —
            # unique lanes premerge before routing and each row crosses
            # the wire once. The counting sort runs PER DEVICE over each
            # device's contiguous batch slice (shard_map splits every
            # plan array along dim 0, so lane positions must be local);
            # no kernel windows — post-a2a tokens have no host plan.
            from paddlebox_tpu.native.key_index import dedup_plan
            D = self.n_shards
            flat = idx.reshape(D, -1)
            parts = [dedup_plan(flat[d], ws.padded_rows,
                                ws.padded_rows, 1) for d in range(D)]
            o = np.concatenate([p[0] for p in parts])
            u = np.concatenate([p[1] for p in parts])
            s = np.concatenate([p[2] for p in parts])
            # uniq is ascending with out-of-range pads per device: the
            # valid count is one searchsorted each, MINUS the NULL row's
            # lane when present (index 0 sorts first; _route never sends
            # it, so it must not count as wire traffic) — the dedup-
            # ratio / wire accounting the flight record surfaces
            # (exchange.* counter deltas)
            u_count = int(sum(np.searchsorted(p[1], ws.padded_rows)
                              - (1 if len(p[1]) and p[1][0] == 0 else 0)
                              for p in parts))
            ecfg = self.store.cfg
            monitor.counter_add("exchange.tokens", idx.size)
            monitor.counter_add("exchange.unique_lanes", u_count)
            monitor.counter_add("exchange.pull_bytes",
                                exchange.pull_wire_bytes(ecfg, u_count))
            monitor.counter_add(
                "exchange.push_bytes",
                exchange.push_wire_bytes(ecfg, u_count,
                                         self.exchange_wire))
            monitor.counter_add("trainer.plan_tokens", idx.size)
            monitor.counter_add("trainer.plan_unique_tokens", u_count)
            monitor.counter_add("trainer.plan_lanes", len(u))
            return (o, Z, Z, u, s)
        from paddlebox_tpu.ops import pallas_kernels
        geom = pallas_kernels.binned_push_geometry(
            self.store.cfg, ws.padded_rows)
        if not self._dedup_premerge(ws):
            if geom is None:
                return empty
            from paddlebox_tpu.native.key_index import block_plan
            o, r, e = block_plan(idx.reshape(-1), geom[0], geom[1])
            return (o, r, e, Z, Z)
        from paddlebox_tpu.native.key_index import dedup_plan_counted
        # scatter-engine widths carry no kernel windows; the counting
        # sort still needs a block granularity — one whole-table block
        SB, NB = geom if geom is not None else (ws.padded_rows, 1)
        (o, u, s, r, e), n_uniq = dedup_plan_counted(
            idx.reshape(-1), ws.padded_rows, SB, NB)
        if geom is None:
            # every lane-shaped op of the premerge and of the touched-
            # rows push costs a pad what it costs a row: ship a bucket
            # over the batch's distinct rows, not a lane a token
            lanes = self._plan_lane_count(n_uniq, idx.size)
            u, s = u[:lanes], s[:lanes]
        # per-pass dedup rate: distinct rows vs routed tokens (the
        # Parallax-style per-slot skew signal rolls up from these), and
        # the lanes shipped for them (pad share = 1 - unique / lanes)
        monitor.counter_add("trainer.plan_tokens", idx.size)
        monitor.counter_add("trainer.plan_unique_tokens", n_uniq)
        monitor.counter_add("trainer.plan_lanes", len(u))
        return (o, r, e, u, s) if geom is not None else (o, Z, Z, u, s)

    def _plan_lane_count(self, n_uniq: int, n_tokens: int) -> int:
        """Lanes of a windowless dedup plan for a batch of `n_uniq`
        distinct rows: working_set.bucket_size over the most distinct
        rows any batch of this trainer has had, never more than a lane a
        token. Grow-only — a batch past the rung moves every later batch
        to the next, none moves back — so the step and the apply compile
        once a rung reached (`trainer.plan_lane_grows` counts them) and
        pass sets that alternate repeat their shapes. Any count >= n_uniq
        gives the same merged values and table to the bit: pads are
        zero-width segments on rows the scatter drops."""
        # at least one rung: a zero-length `uniq` reads as "no dedup half"
        need = min(n_tokens, bucket_size(max(n_uniq, 1)))
        if need > self._plan_lanes:
            self._plan_lanes = need
            monitor.counter_add("trainer.plan_lane_grows")
        return min(n_tokens, self._plan_lanes)

    def _select_table_layout(self) -> str:
        """Which embedding exchange the step programs compile with
        (flags.table_layout; trace-time static, reported by engines()
        as ``table_layout`` — same discipline as pull_engine).

        "sharded" — the embedding/exchange.py subsystem over the mesh-
        partitioned table: the host dedup plan keys the all_to_all
        (each unique row crosses the wire once, its push payload
        premerged BEFORE routing), the push grad plane crosses in
        ``flags.exchange_wire`` format, and the fused gather-pool pull
        runs per shard after routing. "auto" selects it on multi-device
        TPU meshes; CPU test meshes keep the legacy token-level routed
        path ("single") — its numerics are pinned by existing golden
        trajectories — unless a test forces the engine.
        """
        tl = config_flags.table_layout
        if tl not in ("auto", "single", "sharded"):
            raise ValueError(f"table_layout={tl!r}")
        if tl == "sharded":
            if self.n_shards == 1:
                raise ValueError(
                    "flags.table_layout='sharded' needs a multi-device "
                    "mesh — on one shard there is nothing to exchange")
            return "sharded"
        if tl == "single":
            return "single"
        return ("sharded" if (self.n_shards > 1
                              and jax.default_backend() == "tpu")
                else "single")

    def _select_pull_engine(self) -> str:
        """Which pull engine the step programs compile with (trace-time
        static, reported by engines() like push_engine).

        "fused_gather_pool" — rows pool per (example, slot) inside the
        pull (sharded.fused_pull_pool; the Pallas gather_pool kernel on
        a TPU, identical jnp math elsewhere) and the model consumes the
        (B, S, P) sums via PooledSlots; the pooled cotangent expands per
        token into the dedup premerge + binned push.
        flags.fused_gather_pool "auto" selects it for multi-hot layouts
        and wide rows (total_dim >= 64), given a uniform slot layout, a
        pooled-pull-capable model (pulled consumed only through
        fused_seqpool_cvm*), no create-threshold pull gating
        (fused_pull_supported) and, on a TPU, a table the kernel's
        geometry accepts.

        "gather_seqpool" — the unfused lookup + in-model seqpool path.
        """
        fg = config_flags.fused_gather_pool
        if fg not in ("auto", "on", "off"):
            # a typo'd forced engine must fail loudly, not silently
            # measure the auto heuristic (same guard as pack_engine/
            # push_overlap/push_engine)
            raise ValueError(f"fused_gather_pool={fg!r}")
        if fg == "off":
            return "gather_seqpool"
        lay = self.layout
        cfg = self.store.cfg
        if self.schema.has_sequence:
            # ordered tokens reach the model unpooled, in file order, on
            # every engine: the slot's declaration decides, not a flag
            if fg == "on":
                raise ValueError(
                    "flags.fused_gather_pool='on' pools a slot's tokens "
                    "inside the pull; this schema declares a sequence "
                    "slot (Slot.sequence), whose tokens stay in order")
            return "gather_seqpool"
        uniform = (lay.num_slots > 0
                   and len(lay.slot_lens)
                   and np.all(lay.slot_lens == lay.slot_lens[0]))
        # multi-shard meshes support the fused engine through the
        # sharded exchange only: the unique rows route once and the pool
        # gathers from the received lanes (exchange.routed_pull_pooled —
        # per-shard gather_pool after routing)
        compatible = (uniform
                      and (self.n_shards == 1
                           or self.table_layout == "sharded")
                      and getattr(self.model, "pooled_pull_ok", False)
                      and sharded.fused_pull_supported(cfg))
        if compatible and jax.default_backend() == "tpu":
            # on a TPU the engine IS the Pallas gather_pool kernel: where
            # its geometry refuses the table (a width that is not whole
            # 128-lane tiles, a quantized plane, the routed path's
            # received lanes) the record names the unfused engine
            # instead of running jnp pooling under the kernel's name
            from paddlebox_tpu.embedding.working_set import device_width
            from paddlebox_tpu.ops import pallas_kernels
            compatible = (
                self.n_shards == 1
                and pallas_kernels.gather_pool_supported(
                    cfg, self.cfg.global_batch_size, lay.num_slots,
                    lay.total_len // lay.num_slots, device_width(cfg)))
        if not compatible:
            if fg == "on":
                raise ValueError(
                    "flags.fused_gather_pool='on' needs a single-shard "
                    "mesh (or, off-TPU, the sharded exchange engine), a "
                    "uniform slot layout, a pooled-pull-capable model "
                    "(pooled_pull_ok), no create-threshold pull gating, "
                    "and on a TPU an f32 device table of whole 128-lane "
                    "tiles (flags.table_pad_width)")
            return "gather_seqpool"
        if fg == "on":
            return "fused_gather_pool"
        multi_hot = lay.total_len > lay.num_slots
        wide = cfg.total_dim >= 64
        return ("fused_gather_pool" if (multi_hot or wide)
                else "gather_seqpool")

    def _dedup_premerge(self, ws: PassWorkingSet) -> bool:
        """Whether the host plan carries dedup pre-merge bounds
        (flags.push_dedup_premerge). "auto" = the geometries where the
        round-5 in-step A/B on one v5e measured a win: multi-hot
        batches (duplicate-heavy: 852k tokens -> ~330k unique at 4 ids
        a slot) and wide scatter-engine rows (G=1,
        where the per-token scatter is the bound). Single-hot
        narrow-row batches measured neutral-to-slower (the premerge's
        cumsum + boundary gathers cost more than the kernel saves at
        ~1.2x duplication)."""
        dd = config_flags.push_dedup_premerge
        if dd != "auto":
            return dd == "on"
        if config_flags.push_engine == "scatter_accumulate":
            # the forced fused engine consumes premerged unique lanes —
            # without the premerge it would silently fall back to the
            # scatter and the A/B would measure nothing
            return True
        from paddlebox_tpu.ops import pallas_kernels
        multi_hot = self.layout.total_len > self.layout.num_slots
        wide = pallas_kernels.lane_groups(
            self.store.cfg, ws.padded_rows) == 1
        return multi_hot or wide

    def push_premerged(self, ws: PassWorkingSet) -> bool:
        """Whether the push merge engine sees one-lane-per-unique-row
        operands for this working set: the sharded exchange always
        premerges at the engine (per-source premerge before routing +
        the apply tail's cross-device lane merge), the single-shard
        path iff the host plan carries dedup bounds."""
        return (self.table_layout == "sharded"
                or (self._use_plan and self._dedup_premerge(ws)))

    def resolved_push_engine(self, ws: PassWorkingSet) -> str:
        """Which push merge engine the step programs compile with for
        this working set — THE resolver's verdict at the per-shard
        geometry (the engine dispatches on rows_per_shard after
        routing). Trace-time static; on engines() and in the flight
        record, like pull_engine."""
        from paddlebox_tpu.embedding import quant
        from paddlebox_tpu.ops import pallas_kernels
        return pallas_kernels.resolve_push_engine(
            self.store.cfg, ws.rows_per_shard,
            premerged=self.push_premerged(ws),
            storage_f32=self.store.cfg.storage == "f32",
            table_width=quant.row_engine_width(ws.table))

    def engines(self) -> dict:
        """What the resolvers chose, in one place: the table's layout and
        wire, the pull and push engines, whether the push is deferred and
        planned on the host, and the live table's shape — the logical
        (rows, row_width), and beside it the shape of each array the
        device holds: one, or the two planes of a plane table (the
        engines and the shapes are those of the last pass's working set;
        None before the first pass)."""
        ws = self._last_ws
        live = ws is not None and ws.table is not None
        return {
            "table_layout": self.table_layout,
            "pull_engine": self.pull_engine,
            "push_engine": (self.resolved_push_engine(ws)
                            if ws is not None else None),
            "exchange_wire": self.exchange_wire,
            "push_overlap": bool(self.push_overlap),
            "host_plan": bool(self._use_plan),
            "table_shape": list(ws.table.shape) if live else None,
            "plane_shapes": ([list(p.shape)
                              for p in jax.tree.leaves(ws.table)]
                             if live else None)}

    def device_scope_table(self) -> dict[str, dict]:
        """``{module name: {instruction: {"result", "scope"}}}`` of every
        program the last pass under a profiler capture ran — the step,
        the apply, the AUC pair, the boundary's — lowered and compiled
        again from that call's shapes, dtypes and shardings (a hit in
        jit's own cache) and read by ``device_scopes.scopes_of_hlo``;
        also kept in ``device_scopes.TABLE``. Empty where no pass ran
        under a capture."""
        out: dict[str, dict] = {}
        for fn, specs in self._scope_programs.items():
            if specs is not None:
                out.update(device_scopes.table_of(fn, specs))
        device_scopes.TABLE.update(out)
        return out

    def block_until_ready(self) -> None:
        """Wait for everything the loop has dispatched: the table of the
        live working set (the last deferred apply lands after the last
        loss is read), the dense params and the optimizer state."""
        ws = self._last_ws
        jax.block_until_ready((ws.table if ws is not None else None,
                               self.params, self.opt_state))

    def train_pass(self, dataset, metrics: Any = None,
                   preload_keys: np.ndarray | None = None,
                   skip_steps: int = 0) -> dict[str, float]:
        """One pass over the dataset (§3.1 hot loop + §3.4 lifecycle).

        `metrics`: optional MetricRegistry; every registered metric gets
        this pass's (pred, label, cmatch, rank) per batch — the
        AddAucMonitor hook (boxps_worker.cc:582).
        `preload_keys`: the NEXT pass's keys; when given, the next
        working set's key diff + host fetch + H2D staging run on the
        feed thread WHILE this pass trains (the PreLoadIntoMemory +
        BeginFeedPass pairing, data_set.cc:1712 / box_wrapper.h:994) —
        the next ``train_pass`` consumes the staging at its boundary.
        `skip_steps`: mid-pass crash recovery — the first `skip_steps`
        batches of the pass are packed but NOT trained (their effects are
        already in the restored state; the resume cursor's ``mid_steps``),
        so the pass continues exactly where the killed run stopped.
        Reported stats (steps/loss/auc) cover only the executed tail.

        Telemetry: runs inside the hub's pass scope (opened here when no
        BoxPS lifecycle already did) so every event/span — including ones
        from the pack/feed/dump worker threads — carries pass_id/step;
        contributes the stage-time split + throughput to the pass flight
        record, committed at ``hub.end_pass`` (BoxPS.end_pass, or here for
        a trainer-owned scope).
        """
        hub = monitor.hub()
        owned_pass = hub.open_pass_auto()
        pass_t0 = time.perf_counter()
        stage0 = self.timers.snapshot()
        applies0 = self.push_applies
        if self._wire_controller is not None and self._wire_stats0 is None:
            # counter baseline for this PASS (kept across the phases of
            # a phased lifecycle — the controller observes whole passes)
            self._wire_stats0 = monitor.STATS.snapshot()
        try:
            # the root of the pass's timeline: every span of the training
            # thread nests in it (entered after open_pass_auto, inside
            # which a flags.trace_device capture starts)
            with monitor.span("train_pass"):
                out = self._train_pass_impl(dataset, metrics, preload_keys,
                                            skip_steps=skip_steps,
                                            pass_t0=pass_t0)
        except BaseException as e:
            if owned_pass:
                hub.abort_pass(reason=repr(e))
            raise
        stage_delta = {k: self.timers.total.get(k, 0.0) - stage0.get(k, 0.0)
                       for k in self.timers.total}
        fm = self.feed_mgr
        hub.record_train(
            stage_seconds=stage_delta, steps=out["steps"],
            examples=out["steps"] * self.cfg.global_batch_size,
            seconds=time.perf_counter() - pass_t0,
            loss_mean=out.get("loss_mean"), auc=out.get("auc"),
            routed_dropped=out.get("routed_dropped"),
            # the model's declared statistics (None for a model with none)
            model_stats=out.get("model_stats"),
            push_applies=(self.push_applies - applies0) or None,
            pull_engine=self.pull_engine,
            # which push merge engine this pass's steps compiled with
            # (THE resolver's verdict)
            push_engine=self.engines()["push_engine"],
            # pass-boundary cost (this pass's working-set build) + its
            # split — the run doctor's boundary-wall rule reads both
            boundary_seconds=round(fm.last_boundary_seconds, 6),
            boundary_split={k: round(v, 6) for k, v
                            in fm.last_boundary_split.items()},
            # sharded exchange identity (the per-pass exchange traffic —
            # bytes, dedup ratio, overflow drops — rides the flight
            # record's stats_delta as exchange.* counter deltas)
            table_layout=self.table_layout,
            exchange_wire=self.exchange_wire,
            exchange_topology=self.exchange_topology,
            # storage-tier identity (None filtered out for in-RAM
            # stores); the tiering.* counter deltas ride stats_delta
            table_tiering=self.table_tiering)
        if owned_pass:
            # trainer-owned scope: the BoxPS lifecycle is not driving, so
            # the pass-boundary tier re-evaluation, the replica-tier
            # refresh, and the adaptive exchange-wire re-cost run here
            # instead (BoxPS.end_pass drives all three for fleet-owned
            # scopes)
            tiering.end_pass_rebalance(self.store)
            self.refresh_replica_boundary()
            self.adapt_wire_boundary()
            self.remediation_boundary()
            hub.end_pass(metrics=metrics)
        return out

    # ------------------------------------------------------------------
    def enable_self_healing(self, controller=None):
        """Bind the doctor-driven remediation loop (ISSUE 18): with
        ``flags.self_healing`` on, every pass boundary consumes the live
        doctor findings and applies at most one action under the parity
        guard (runtime/remediation.py). Pass ``controller`` to inject a
        pre-built/customized one; returns the bound controller."""
        if controller is None:
            from paddlebox_tpu.runtime.remediation import \
                RemediationController
            controller = RemediationController(self)
        self._remediation = controller
        return controller

    def remediation_boundary(self, findings=None):
        """Run the bound RemediationController's pass-boundary step —
        called once per pass BEFORE the flight-record commit (by
        ``train_pass`` for trainer-owned scopes, by ``BoxPS.end_pass``
        for fleet-driven ones), so the remediation record lands in the
        ending pass's flight record. Safe no-op (None) when no
        controller is bound or ``flags.self_healing`` is off; the loop
        must never take down the training it heals."""
        ctl = self._remediation
        if ctl is None or not config_flags.self_healing:
            return None
        try:
            return ctl.boundary(findings=findings)
        # pblint: disable=silent-except -- the healing loop is an
        # observer with side effects: a broken controller is counted
        # (remediation.errors) but must never abort the pass boundary
        except Exception:
            monitor.counter_add("remediation.errors")
            return None

    def note_flow_attribution(self, attribution: dict | None,
                              wall_seconds: float | None = None) -> None:
        """Feed the adaptive wire controller a clock-corrected flow-edge
        attribution (``critical_path.attribute_flow_edges`` over a merged
        world trace) plus the wall it attributes against. In-process
        records can't form cross-rank exchange edges, so this evidence
        arrives from the driver that holds the merged timeline; the
        controller uses it as a veto — when the exchange edge is not the
        limiter, the wire holds."""
        self._flow_attribution = (
            (attribution, wall_seconds) if attribution else None)

    def refresh_replica_boundary(self) -> int | None:
        """Pass-boundary rebuild of the HBM replica hot tier
        (flags.use_replica_cache): harvest the tier manager's current
        hottest rows into the device-resident plane the NEXT pass's
        staging serves from, and flush the ending pass's batched
        replica-hit delta so it lands in that pass's flight record.
        Called once per pass AFTER ``tiering.end_pass_rebalance`` (the
        refresh reads the re-scored ranking) and BEFORE the hub's
        end-of-pass commit — by ``train_pass`` for trainer-owned scopes,
        by ``BoxPS.end_pass`` for fleet-driven ones. Safe no-op (None)
        when the tier is off."""
        if self.replica_cache is None:
            return None
        return self.replica_cache.refresh()

    def adapt_wire_boundary(self):
        """Pass-boundary wire adaptation (flags.exchange_adaptive): run
        the controller on this pass's OWN exchange counter deltas; on a
        switch, rebind self.exchange_wire and recompile the steps (the
        same contract as the adaptive capacity doubling). Called once
        per pass — by ``train_pass`` for trainer-owned scopes, by
        ``BoxPS.end_pass`` for fleet-driven ones (phased lifecycles
        adapt once per WHOLE pass, never between phases). Safe no-op
        when the controller is inactive or no pass was observed.
        Returns the wire the NEXT pass will run with."""
        ctl = self._wire_controller
        stats0, self._wire_stats0 = self._wire_stats0, None
        if ctl is None or stats0 is None:
            return None
        now = monitor.STATS.snapshot()

        def delta(name):
            return int(now.get(name, 0.0) - stats0.get(name, 0.0))

        flow, wall = self._flow_attribution or (None, None)
        decision = ctl.observe(
            tokens=delta("exchange.tokens"),
            unique_lanes=delta("exchange.unique_lanes"),
            overflow_retries=(delta("exchange.overflow_retries")
                              + delta("exchange.overflow_dropped")),
            flow=flow, wall_seconds=wall)
        self._last_wire_decision = decision
        if decision["switched"]:
            monitor.event(
                "exchange_wire_adapted", type="exchange",
                prev=decision["prev_wire"], wire=decision["wire"],
                streak=decision["streak"], reason=decision["reason"],
                costs={w: round(c, 1)
                       for w, c in decision["costs"].items()})
            monitor.counter_add("exchange.wire_switches")
            self.exchange_wire = decision["wire"]
            self._rebuild_steps()
        monitor.hub().record_train(exchange_wire_next=decision["wire"])
        return decision["wire"]

    def _train_pass_impl(self, dataset, metrics: Any = None,
                         preload_keys: np.ndarray | None = None,
                         skip_steps: int = 0, *,
                         pass_t0: float) -> dict[str, float]:
        cfg = self.cfg
        # the device-scope table (monitor/device_scopes.py): only where a
        # profiler capture is open — the benchmark's own, or
        # flags.trace_device's, started in open_pass_auto — are this
        # pass's programs noted at their first call, and read at the
        # close; with none open the pass pays this one static call
        build = (device_scopes.open_build()
                 if jax.profiler.TraceAnnotation.is_enabled() else None)
        with self.timers("unique_keys", span="unique_keys"):
            keys = dataset.unique_keys()
        ws = self.feed_mgr.begin_pass(keys)
        self.feed_mgr.pass_opened()
        self._overlap_ws = ws if self.push_overlap else None
        if preload_keys is not None:
            self.preload_pass(preload_keys)
        with self.timers("preplan", span="preplan"):
            self._preplan_capacity(dataset, ws)
        table = ws.table
        params, opt_state = self.params, self.opt_state
        # flat dense-state transport (see pack_dense); identity when off
        dstate = (self.pack_dense(params, opt_state)
                  if self._dense_packer is not None else None)
        auc_acc = auc_lib.AucAccumulator(cfg.auc_buckets)
        # device arrays collected without per-step host sync (the hot loop
        # must stay dispatch-async to overlap host pack with device compute)
        mode = cfg.dense_sync_mode
        if mode == "async":
            assert self.dense_table is not None
            self.dense_table.start()
        repl = mesh_lib.replicated_sharding(self.mesh)
        pass_step = 0
        dev_losses: list[Any] = []
        dev_dropped: list[Any] = []
        dev_stats: list[Any] = []     # the model's declared statistics
        # DumpField stream: the PREVIOUS batch's (step, preds, labels) is
        # written each iteration — by then those arrays are ready, so the
        # D2H copy doesn't stall the freshly-dispatched step — and the
        # writer thread does the file IO (dump threads,
        # boxps_trainer.cc:96-108)
        dump_stream = (DumpStream(cfg.dump_fields_path, mode="a")
                       if cfg.dump_fields_path else None)
        dump_pending: tuple[int, Any, Any] | None = None
        skip_remaining = int(skip_steps)
        head_open = True
        completed = False
        pack_it = self._pack_iter(dataset, ws, cfg.global_batch_size)
        try:
            for pb, staged in pack_it:
                if skip_remaining > 0:
                    # mid-pass resume: these batches' effects already live
                    # in the restored planes — consume them (keeps the
                    # batch stream and step cadence aligned) but train
                    # nothing.
                    skip_remaining -= 1
                    pass_step += 1
                    continue
                mon_ctx.set_step(self.global_step)
                if self.peer_check is not None:
                    # elastic watchdog: a dead/stalled peer aborts HERE —
                    # a step boundary, before this batch dispatches — and
                    # the finally below drains in-flight work
                    self.peer_check()
                faultpoint.hit("trainer.step.pre")
                idx, mask, dense, labels, *plan = staged
                if mon_trace._ACTIVE and self.table_layout == "sharded":
                    # world-trace flow point for this step's all_to_all:
                    # every rank stamps the SAME deterministic key (all
                    # ranks run the step in lockstep), so the merger can
                    # draw the cross-rank exchange edge without a single
                    # byte of trace context crossing the wire
                    mon_trace.flow(
                        "exchange",
                        f"p{mon_ctx.current().pass_id}"
                        f".s{self.global_step}",
                        **exchange.flow_fields(self.store.cfg,
                                               self.exchange_wire,
                                               int(idx.size)))
                if head_open:
                    # the head of the pass ends where its first step is
                    # dispatched: unique_keys, the boundary, preplan, the
                    # first batch's read wait and H2D are all inside
                    self.timers.add("head", time.perf_counter() - pass_t0)
                    head_open = False
                with self.timers("train", span="train_step"):
                    if mode == "async":
                        params = jax.device_put(
                            self._unravel(self.dense_table.pull()), repl)
                        table, gp_flat, loss, preds, dropped = \
                            device_scopes.run(self._step_fn, table, params,
                                              idx, mask, dense, labels,
                                              *plan)
                        self.dense_table.push(np.asarray(gp_flat))
                    elif self.push_overlap:
                        # deferred push pipeline: dispatch step N-1's
                        # pending table apply FIRST (the next step's pull
                        # must consume the applied table — that data
                        # dependence is what keeps overlap-on bit-
                        # identical), then the loss-path program, then
                        # queue this step's packed operands; their apply
                        # runs while batch N+1's pack/plan-H2D proceeds
                        table = self._dispatch_pending_apply(table)
                        dst = (dstate if dstate is not None
                               else (params, opt_state))
                        out = device_scopes.run(
                            self._defer_step_fn, table, *dst, idx, mask,
                            dense, labels, *plan)
                        (dst, push_ops, loss, preds,
                         dropped) = self.split_defer_out(out)
                        if dstate is not None:
                            dstate = dst
                        else:
                            params, opt_state = dst
                        self._push_stager.put(
                            (idx, mask, labels,
                             tuple(plan[:PLAN_ARITY]), push_ops))
                    elif dstate is not None:
                        out = device_scopes.run(
                            self._step_fn, table, *dstate, idx, mask,
                            dense, labels, *plan)
                        (table, dstate, loss, preds,
                         dropped) = self.split_step_out(out)
                    else:
                        out = device_scopes.run(
                            self._step_fn, table, params, opt_state, idx,
                            mask, dense, labels, *plan)
                        (table, (params, opt_state), loss, preds,
                         dropped) = self.split_step_out(out)
                    pass_step += 1
                    if (mode == "kstep"
                            and pass_step % cfg.param_sync_step == 0):
                        params, opt_state = device_scopes.run(
                            self._sync_fn, params, opt_state)
                if self._n_stats:
                    # the model's statistics sit just before the loss in
                    # every allreduce step program's output
                    dev_stats.append(out[-4])
                # keep the ws pointing at the live buffer: the step donates
                # its input table, and a concurrent flush (store read/save
                # from another thread) must never gather from a dead buffer
                ws.table = table
                with self.timers("auc", span="auc_update"):
                    # a model that declares no prediction feeds nothing
                    if self._feeds_auc:
                        auc_acc.update(self._auc_fn, preds, labels)
                        if metrics is not None:
                            metrics.add_batch(preds, labels,
                                              cmatch=pb.cmatch,
                                              rank=pb.rank)
                if dump_stream is not None:
                    if dump_pending is not None:
                        s, p, y, ex = dump_pending
                        dump_stream.write_fields(s, p, y, ex)
                    dump_pending = (self.global_step, preds, labels,
                                    self._dump_extra_fields(pb))
                if cfg.check_nan_inf or config_flags.check_nan_inf:
                    lv = np.asarray(loss)
                    if not np.isfinite(lv).all():
                        # FLAGS_check_nan_inf trip (nan_inf_utils,
                        # boxps_worker.cc:575-580): walk the step outputs
                        # for the offending leaves, tell telemetry WHICH
                        # paths went non-finite, dump the whole scope,
                        # then raise
                        # flat transport: the live params are inside
                        # dstate, not the pass-start `params` binding
                        live_params = (self.unpack_dense(dstate)[0]
                                       if dstate is not None else params)
                        scope = {"params": live_params, "loss": loss,
                                 "preds": preds, "labels": labels}
                        bad = find_nonfinite(scope)
                        monitor.counter_add("trainer.nan_trips")
                        monitor.event("nan_guard",
                                      step=int(self.global_step),
                                      paths=bad[:32], n_bad=len(bad))
                        dumped = None
                        if cfg.nan_dump_dir:
                            dumped = dump_tree(
                                f"{cfg.nan_dump_dir}/nan_step"
                                f"{self.global_step}", scope)
                        raise FloatingPointError(
                            f"nan/inf loss at step {self.global_step}; "
                            f"non-finite leaves: {bad[:8]}"
                            + (f" (scope dumped to {dumped})"
                               if dumped else ""))
                dev_losses.append(loss)
                dev_dropped.append(dropped)
                self.global_step += 1
                mp = self._midpass
                if (mp is not None and mp[1] > 0
                        and pass_step % mp[1] == 0):
                    table = self._midpass_save(table, ws, dstate, params,
                                               opt_state, pass_step)
            completed = True
        finally:
            # pass_close: from the loop's end to the pass's numbers — the
            # pending apply, the dense state's rebind, end_pass, the
            # drain and the AUC read, in one scope; a pass that raised
            # leaves it after the rebind.
            with self.timers("close", span="pass_close"):
                # elastic drain crumbs: how far this pass got and whether it
                # aborted (a peer failure unwinding through here) — the
                # drain snapshot reads these after the exception lands
                self.last_pass_steps = pass_step
                self._last_ws = ws
                self._pass_aborted = not completed
                # close the pack generator explicitly so its finally (cancel
                # event + producer join) runs NOW, not whenever GC finalizes
                # the suspended frame — on a non-refcounting interpreter the
                # daemon producer would otherwise keep translating and
                # touching ws for the rest of the dataset
                pack_it.close()
                # The step donates table/params/opt_state, so the objects
                # bound before the loop are dead buffers; rebind to the last
                # good step even when a batch raised (the pass/day
                # crash-recovery flow catches and resumes from checkpoint —
                # the Trainer must stay usable).
                if self.push_overlap:
                    # pass-boundary flush: the last step's table apply is
                    # still pending (bounded staleness of one) — land it
                    # before anything reads or persists the table
                    table = self._dispatch_pending_apply(table)
                ws.table = table
                if build is not None:
                    # the pass's last dispatch is out: the table of the
                    # programs it ran is read on a thread of its own
                    # while this one closes the pass and drains
                    build.start()
                self.feed_mgr.pass_closed()
                with monitor.span("pass_close/rebind"):
                    if mode == "async":
                        self.dense_table.flush()
                        self.params = jax.device_put(
                            self._unravel(self.dense_table.pull()), repl)
                        self.opt_state = self.dense_table.state_dict()
                        self._last_dense = None   # state dict IS the state
                    else:
                        # elastic drain crumb: the LIVE loop planes exactly
                        # as _midpass_save would store them — for kstep,
                        # BEFORE the finalize pmean below (k·x/k can round
                        # for non-power-of-2 shard counts, and the drain
                        # snapshot must stay bit-identical to the per-shard
                        # loop state the uninterrupted run continues from)
                        self._last_dense = (self.unpack_dense(dstate)
                                            if dstate is not None
                                            else (params, opt_state))
                        if mode == "kstep":
                            # end-of-pass sync (trainer Finalize)
                            params, opt_state = device_scopes.run(
                                self._sync_fn, params, opt_state)
                        if dstate is not None:
                            params, opt_state = self.unpack_dense(dstate)
                        self.params, self.opt_state = params, opt_state
                if dump_stream is not None:
                    # flush the tail batch even when the pass raised — a
                    # nan trip must keep the debug stream it exists for. A
                    # dump IO failure is reported but never masks the
                    # training exception.
                    try:
                        if dump_pending is not None:
                            s, p, y, ex = dump_pending
                            dump_stream.write_fields(s, p, y, ex)
                        if cfg.dump_param:
                            self._dump_params(dump_stream)
                        dump_stream.close()
                    except Exception as e:
                        import warnings
                        warnings.warn(f"dump stream failed: {e}")
                try:
                    if completed:
                        out = self._read_pass(ws, table, dev_losses,
                                              dev_dropped, auc_acc,
                                              dev_stats)
                finally:
                    if build is not None:
                        # after the drain and the read: what is left of
                        # the builder thread's work
                        with monitor.span("pass_close/device_scopes"):
                            device_scopes.close_build(build)
                        self._scope_programs = build.seen
        return out

    def _read_pass(self, ws: PassWorkingSet, table, dev_losses: list,
                   dev_dropped: list, auc_acc,
                   dev_stats: list = ()) -> dict[str, float]:
        """The end of a pass that ran through: end_pass, then the drain
        of the loop's losses and the AUC read (inside ``pass_close``)."""
        with monitor.span("pass_close/end_pass"):
            self.feed_mgr.end_pass(ws, table)
        with self.timers("drain"):
            # one sync, post-loop: every queued step completes here, so
            # this is where async-dispatch wall time actually lands.
            losses = [float(l) for l in dev_losses]
        # every dispatched apply has drained; release the stager's
        # retired-slot buffer refs (the pipeline's leak invariant:
        # live() == 0 between passes)
        self._push_stager.clear()
        with monitor.span("pass_close/read"):
            out = auc_acc.compute()
            out["loss_first"] = losses[0] if losses else float("nan")
            out["loss_last"] = losses[-1] if losses else float("nan")
            out["loss_mean"] = (float(np.mean(losses)) if losses
                                else float("nan"))
            out["losses"] = losses
            out["steps"] = len(losses)
            out["routed_dropped"] = self._check_dropped(dev_dropped)
            if dev_stats:
                # the model's declared statistics, one vector a step:
                # into the flight record's counters (``*_max`` names are
                # the pass's largest step, the others sums)
                out["model_stats"] = model_base.publish_stats(
                    self.model, np.stack([np.asarray(v)
                                          for v in dev_stats]))
        return out

    def _preplan_capacity(self, dataset, ws: PassWorkingSet,
                          drop_last: bool = True,
                          for_eval: bool = False) -> None:
        """Proactive all_to_all capacity sizing: scan the pass's batches
        once on the host (the same vectorized translate the pack thread
        runs later — idempotent touch marks), histogram real tokens per
        (source device, destination shard), and GROW capacity_factor
        before the first step compiles if the measured max would drop
        tokens. Makes lossy first passes impossible instead of merely
        visible (VERDICT r3 weak #4); the adaptive doubling in
        _check_dropped stays as backstop. Factors bucket to 0.25 steps
        so near-identical passes reuse compiled steps; never shrinks
        (a smaller pass must not force a recompile).

        Matches the reference's dynamic per-pass buffer sizing
        (box_wrapper_impl.h:44-81) under the static-shape constraint.
        """
        n_dev = self.n_shards
        if n_dev <= 1 or not config_flags.routed_capacity_preplan:
            return
        bs = self.cfg.global_batch_size
        # per-dataset memo: an AUC-runner ablation sweep re-evals the
        # baseline dataset repeatedly and must not pay the scan each
        # time (each ABLATED dataset is a new object with new routing
        # and scans once). A dataset mutated in place to the same
        # length would go stale — the adaptive-doubling backstop in
        # _check_dropped still catches that.
        # drop_last is part of the key: a train-pass scan (tail dropped)
        # must not satisfy an eval pass that scores the padded tail.
        # the dataset's records version is too: records swapped in place
        # behind an unchanged num_examples (the auc_runner rebinds
        # ds.records per ablation) change routing, and the "lossy first
        # pass impossible" guarantee must survive that. The ws itself
        # needs no stamp — row assignment is by sorted-key rank, so an
        # unchanged dataset always translates identically.
        # Duck-typed: a dataset without num_examples just rescans.
        # dedup routing (the sharded exchange's plan-keyed a2a, or the
        # legacy device dedup) routes each UNIQUE token once per device:
        # counting unique tokens sizes the lanes the wire actually
        # carries — the factor (and the static buffers) shrink by the
        # batch's duplication rate
        dedup_route = (config_flags.pullpush_dedup_keys and n_dev > 1)
        n_ex = getattr(dataset, "num_examples", None)
        memo_key = (n_ex, ws.padded_rows, drop_last, dedup_route,
                    getattr(dataset, "_records_version", None))
        memo = (getattr(dataset, "_pbtpu_preplan_need", None)
                if n_ex is not None else None)
        if memo is not None and memo[0] == memo_key:
            capf = memo[1]
        else:
            bpd = bs // n_dev
            T = self.layout.total_len
            n_local = bpd * T
            max_c = 0
            dev_off = np.arange(n_dev)[:, None] * (n_dev + 1)
            for pb in dataset.batches(bs, drop_last=drop_last):
                if len(pb.floats) < bs:   # eval tail: padded, not dropped
                    pb = pb.pad_to(bs)
                idx = ws.translate(pb.ids, pb.mask)
                if dedup_route:
                    per_dev = idx.reshape(n_dev, bpd * T)
                    for d in range(n_dev):
                        u = np.unique(per_dev[d])
                        u = u[u != 0]       # NULL tokens are never routed
                        if len(u):
                            c = np.bincount(ws.shard_of(u),
                                            minlength=n_dev)
                            max_c = max(max_c, int(c[:n_dev].max()))
                    continue
                # NULL tokens are never routed (_route); bucket them at
                # n_dev so they fall out of the per-destination counts
                owner = np.where(idx == 0, n_dev, ws.shard_of(idx))
                flat = (owner.reshape(n_dev, bpd * T) + dev_off).ravel()
                counts = np.bincount(
                    flat, minlength=n_dev * (n_dev + 1)
                ).reshape(n_dev, n_dev + 1)[:, :n_dev]
                max_c = max(max_c, int(counts.max()))
            if max_c == 0:
                return
            # _capacity gives ceil(n_local * factor / n_dev) lanes per
            # destination; dedup routing only shrinks counts, so this
            # bound is safe for both paths
            need = max_c * n_dev / n_local
            capf = min(float(n_dev), max(1.0, -(-need * 4 // 1) / 4))
            if n_ex is not None:
                try:
                    dataset._pbtpu_preplan_need = (memo_key, capf)
                # pblint: disable=silent-except -- slots-restricted
                # dataset type: the memo is a pure optimization (skips a
                # re-scan); a dataset that cannot carry it just re-plans
                except AttributeError:
                    pass
        if for_eval:
            # a skewed EVAL dataset must never inflate the train step's
            # all_to_all padding or force a train recompile — only the
            # eval program grows
            if capf > self._eval_capacity:
                monitor.counter_add("trainer.capacity_preplanned_eval", 1)
                self._eval_capacity = capf
                self._eval_fn = self._build_eval_step()
        elif capf > self.cfg.capacity_factor:
            monitor.counter_add("trainer.capacity_preplanned", 1)
            self.cfg.capacity_factor = capf
            self._eval_capacity = max(self._eval_capacity, capf)
            self._rebuild_steps()

    def _rebuild_steps(self) -> None:
        """(Re)build the compiled step programs from the current config:
        the step, the deferred step + apply pair (push_overlap), and the
        eval step. _step_fn is ALWAYS the inline step — external callers
        drive it; the training loop uses the deferred pair when
        push_overlap is on."""
        self._step_fn = self._build_train_step()
        self._defer_step_fn = (self._build_train_step(defer=True)
                               if self.push_overlap else None)
        self._apply_fn = (self._build_apply_fn()
                          if self.push_overlap else None)
        self._eval_fn = self._build_eval_step()

    def _check_dropped(self, dev_dropped: list,
                       for_eval: bool = False) -> int:
        """Capacity-drop policy: never silent (the reference never drops —
        it sizes its buffers dynamically, box_wrapper_impl.h:44-81; a fixed
        all_to_all lane is the static-shape trade and must be observable).

        Counts go to the StatRegistry; Flags.routed_drop_fatal raises, and
        by default the capacity factor doubles for the NEXT pass (adaptive
        static capacity — the recompile-across-passes analogue of the
        reference's dynamic resize). Eval drops grow only the EVAL
        capacity/program — skew in an eval-only dataset must never
        inflate the train step's padding or force a train recompile."""
        import warnings
        total = sum(int(d) for d in dev_dropped)
        if not total:
            return 0
        monitor.counter_add("trainer.routed_dropped", total)
        monitor.event("routed_dropped", total=total, for_eval=for_eval)
        capf = (self._eval_capacity if for_eval
                else self.cfg.capacity_factor)
        if self.table_layout == "sharded":
            # the exchange's own overflow accounting: NAMED counter +
            # event so a lossy pass is alarmable, never a silent drop
            # (the acceptance bar of the sharded scale-out issue)
            monitor.counter_add("exchange.overflow_dropped", total)
            monitor.event("exchange_overflow", total=total,
                          capacity_factor=float(capf), for_eval=for_eval)
        msg = (f"{total} tokens exceeded all_to_all capacity this "
               f"{'eval ' if for_eval else ''}pass "
               f"(capacity_factor={capf}); their pulls returned zero "
               f"rows" + ("" if for_eval
                          else " and their grads were dropped"))
        if config_flags.routed_drop_fatal:
            raise RuntimeError(msg)
        if config_flags.routed_drop_adapt:
            grown = min(float(self.n_shards), capf * 2.0)
            if for_eval:
                self._eval_capacity = grown
                self._eval_fn = self._build_eval_step()
            else:
                self.cfg.capacity_factor = grown
                self._eval_capacity = max(self._eval_capacity, grown)
                self._rebuild_steps()
            msg += (f"; raising capacity_factor to {grown} for the next "
                    f"pass (recompiles the "
                    f"{'eval program' if for_eval else 'step'})")
        warnings.warn(msg)
        return total

    def _dump_extra_fields(self, pb: PackedBatch) -> dict:
        """Per-instance extra dump columns (DumpField's dump_fields list,
        trainer_desc.proto:39-41): ins_id, float slots, sparse slot ids."""
        extra: dict[str, Any] = {}
        sparse_names = {s.name for s in self.schema.sparse_slots}
        float_names = {s.name for s in self.schema.float_slots}
        for f in self.cfg.dump_fields:
            if f in ("pred", "label"):
                continue                    # always in the base columns
            if f == "ins_id":
                ins = (pb.ins_id if pb.ins_id is not None
                       else np.zeros(len(pb.floats), np.uint64))
                extra["ins_id"] = ins
            elif f in float_names:
                vals = pb.float_slot(f).reshape(len(pb.floats), -1)
                # all components of a multi-value float field are dumped
                # (comma-joined by the writer thread)
                extra[f] = vals[:, 0] if vals.shape[1] == 1 else vals
            elif f in sparse_names:
                # raw (ids, mask) pair — the per-instance id join runs on
                # the DumpStream writer thread, not the training thread
                extra[f] = pb.slot_ids(f)
            else:
                raise KeyError(f"unknown dump field {f!r}")
        return extra

    def _dump_params(self, dump_stream) -> None:
        """DumpParam (trainer_desc.proto:43-45): write matched dense
        params to the stream at pass end."""
        import jax.tree_util as jtu
        flat = jtu.tree_flatten_with_path(self.eval_params())[0]
        for path, leaf in flat:
            name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path)
            if not any(pat in name for pat in self.cfg.dump_param):
                continue
            vals = np.asarray(leaf).reshape(-1)
            dump_stream.write(
                f"param {name} " + ",".join(f"{v:.6g}" for v in vals))

    def preload_pass(self, keys: np.ndarray) -> None:
        """BeginFeedPass: stage the next pass's working set (key diff, host
        fetch, H2D of fresh rows) on a background thread while the current
        pass trains — box_wrapper.h:994-1072, paired with the dataset's
        preload_into_memory (data_set.cc:1712)."""
        with monitor.span("preload_pass"):
            self.feed_mgr.begin_feed_pass(keys)

    def wait_feed_pass_done(self) -> None:
        """Join the background feed pass (BoxHelper::WaitFeedPassDone)."""
        self.feed_mgr.wait_feed_pass_done()

    def set_shard_ownership(self, ownership) -> None:
        """Bind per-host shard ownership (distributed/ownership.
        ShardOwnership): every feed builds only the keys hash-
        partitioned onto THIS host's shards of the sharded store, so
        working-set build cost divides by world size. Re-bound
        automatically on elastic re-formation (``recover_world``) and on
        elastic grow (``RemediationController.poll_grow``); the
        faultpoint is the grow kill matrix's mid-rebind window."""
        from paddlebox_tpu.utils import faultpoint
        faultpoint.hit("elastic.ownership.rebind.pre")
        self.feed_mgr.set_ownership(ownership)

    def _dispatch_pending_apply(self, table):
        """Dispatch the pending deferred table apply (if any) against
        `table` and return the applied table. The caller owns sequencing:
        this must run before anything consumes the post-apply state."""
        item = self._push_stager.take()
        if item is None:
            return table
        from paddlebox_tpu.utils import faultpoint
        faultpoint.hit("trainer.push_apply.pre")
        idx, mask, labels, plan, ops = item
        with monitor.span("push_apply"):
            table = device_scopes.run(self._apply_fn, table, idx, mask,
                                      labels, *plan, *ops)
        self.push_applies += 1
        monitor.counter_add("trainer.push_applies")
        return table

    def flush_push(self) -> int:
        """Apply any pending deferred sparse-push update to the live
        working set (flags.push_overlap). Runs automatically at pass
        boundaries, before eval passes, and ahead of sparse flushes
        (store save/export/shrink reach it through the feed manager's
        pre-flush hooks). Returns the number of applies dispatched
        (0 or 1 — staleness is bounded at one step)."""
        if not self._push_stager.pending():
            return 0
        ws = self._overlap_ws
        if ws is None:
            return 0
        if self.feed_mgr._in_pass:
            raise RuntimeError(
                "flush_push while a training pass is open — the loop "
                "owns the table mid-pass; finish the pass first")
        ws.table = self._dispatch_pending_apply(ws.table)
        return 1

    def flush_sparse(self) -> int:
        """Force lazily-retained device rows back to the host store (runs
        automatically before store save/export/shrink via flush hooks).
        Deferred push applies (push_overlap) land first — row values must
        be final before they move D2H."""
        self.flush_push()
        return self.feed_mgr.flush()

    def eval_params(self):
        """Replicated dense params for eval/export — collapses the kstep
        per-shard copies (equal right after a sync) to one."""
        if self.cfg.dense_sync_mode == "kstep":
            return self._collapse_fn(self.params)
        return self.params

    def restore_dense(self, params, opt_state=None) -> None:
        """Load dense state from a checkpoint, mode-aware.

        `params` may be the replicated tree (from ``eval_params``/a
        checkpoint) or, for kstep, the stacked per-shard tree. In async
        mode `opt_state` is an AsyncDenseTable state dict (what
        ``self.opt_state`` holds after a pass); omitting it keeps fresh
        zero moments.
        """
        mode = self.cfg.dense_sync_mode
        repl = mesh_lib.replicated_sharding(self.mesh)
        if mode == "async":
            self.params = jax.device_put(params, repl)
            if opt_state is not None:
                self.dense_table.load_state_dict(opt_state)
            else:
                flat, _ = dense_sync.flatten_dense(params)
                self.dense_table.load_state_dict(
                    {"params": flat, "mom1": np.zeros_like(flat),
                     "mom2": np.zeros_like(flat), "steps": np.asarray([0])})
            self.opt_state = self.dense_table.state_dict()
            return
        if mode == "kstep":
            tmpl = jax.tree.leaves(self.params)
            got = jax.tree.leaves(params)
            stacked_already = all(
                np.shape(a) == np.shape(b) for a, b in zip(got, tmpl))
            if not stacked_already:
                params = dense_sync.stack_for_shards(params, self.n_shards)
            self.params = jax.device_put(params, self._stacked_sh)
            if opt_state is not None:
                ot = jax.tree.leaves(self.opt_state)
                og = jax.tree.leaves(opt_state)
                if not all(np.shape(a) == np.shape(b)
                           for a, b in zip(og, ot)):
                    opt_state = dense_sync.stack_for_shards(opt_state,
                                                            self.n_shards)
                self.opt_state = jax.device_put(opt_state, self._stacked_sh)
            return
        self.params = jax.device_put(params, repl)
        if opt_state is not None:
            self.opt_state = jax.device_put(opt_state, repl)

    def enable_midpass_snapshots(self, checkpointer,
                                 every_steps: "int | None" = None,
                                 box=None, metrics=None) -> None:
        """Commit a crash-safe snapshot every ``every_steps`` steps INSIDE
        each training pass (ISSUE 5 mid-pass resume). ``every_steps``
        defaults to ``flags.ckpt_midpass_every_steps`` (0 there keeps
        mid-pass snapshots off — pass-boundary snapshots only, the
        pre-ISSUE-5 behavior), so launchers can set the cadence from the
        environment (``PBTPU_CKPT_MIDPASS_EVERY_STEPS``) without a code
        change. The snapshot's
        cursor records the last COMPLETED pass, ``mid_steps`` (steps of
        the open pass already trained), and the shuffle RNG state the
        driver stashed in ``midpass_cursor_extra['shuffle_state']``
        (captured BEFORE the pass's permutation draw) — so a kill between
        pass boundaries resumes via ``train_pass(skip_steps=mid_steps)``
        from the dataset cursor instead of replaying the pass.

        Supported dense-sync modes:

        - ``allreduce``: any cadence; the live flat/pytree dense state
          rides ``dense_override``.
        - ``kstep``: ``every_steps`` must land on the K-step sync
          boundary (a multiple of ``param_sync_step``) — that is where
          the per-shard replicas are consistent with the uninterrupted
          run's sync cadence; the snapshot stores the STACKED per-shard
          planes, so the resume is bit-exact.
        - ``async``: the snapshot quiesces the host dense table
          (``flush()``) and stores its state dict — exact state at the
          boundary, though the continued run's grad-merge timing remains
          async-nondeterministic by design.
        """
        if every_steps is None:
            every_steps = int(config_flags.ckpt_midpass_every_steps)
        if every_steps <= 0:
            self._midpass = None
            return
        mode = self.cfg.dense_sync_mode
        if mode == "kstep" and every_steps % self.cfg.param_sync_step:
            raise NotImplementedError(
                f"kstep mid-pass snapshots must land on the K-step sync "
                f"boundary: every_steps={every_steps} is not a multiple "
                f"of param_sync_step={self.cfg.param_sync_step} — "
                f"between syncs the replicas' consistency cadence would "
                f"diverge from the uninterrupted run on resume")
        if box is None:
            raise ValueError("enable_midpass_snapshots needs a BoxPS "
                             "(the cursor's pass identity)")
        self._midpass = (checkpointer, int(every_steps), box, metrics)

    @monitor.span("midpass_save")
    def _midpass_save(self, table, ws, dstate, params, opt_state,
                      pass_step: int):
        """Commit a MID-pass snapshot: land the pending deferred push,
        mark + flush the device tier, and save with the LIVE dense planes
        (the loop's dstate/params — ``trainer.params`` still holds the
        pass-start values mid-pass). The feed manager's in-pass guard is
        lifted only around the save: at this instruction the loop owns a
        quiescent table (no step dispatched past it), so the D2H gather
        reads a live buffer."""
        ckpt, _every, box, metrics = self._midpass
        table = self._dispatch_pending_apply(table)
        ws.table = table
        if self.cfg.dense_sync_mode == "async":
            # quiesce the host dense table: every pushed grad applied, so
            # the state dict is THE dense state at this step boundary
            self.dense_table.flush()
            dense = (self._unravel(self.dense_table.pull()),
                     self.dense_table.state_dict())
        else:
            # allreduce: live flat/pytree state; kstep: the loop's STACKED
            # per-shard planes (restore_dense detects stacked shapes)
            dense = (self.unpack_dense(dstate) if dstate is not None
                     else (params, opt_state))
        self.feed_mgr.pass_closed()
        try:
            # mark this pass's touched rows unsynced so the checkpointer's
            # flush_sparse materializes them (no data moves here)
            self.feed_mgr.end_pass(ws, table)
            ckpt.save(
                self, box=box,
                metrics=(metrics if metrics is not None else box.metrics),
                pass_id=int(box.pass_id) - 1, mid_steps=int(pass_step),
                dense_override=dense,
                shuffle_state=self.midpass_cursor_extra.get(
                    "shuffle_state"))
        finally:
            self.feed_mgr.pass_opened()
        faultpoint.hit("trainer.midpass.post_save")
        return table

    def drain_and_snapshot(self, checkpointer, box, metrics=None
                           ) -> str | None:
        """Elastic drain point: after a peer failure aborted the step
        loop, the in-flight work is already landed (the pass's finally
        dispatched the pending deferred push, rebound the live dense
        planes, and closed the pack pipeline) — commit a mid-pass
        snapshot at the abort step so the coming election can keep as
        much of this pass as the world holds in common. Returns the
        snapshot dir, or None when there is nothing to snapshot (the
        failure surfaced at a pass boundary, or a kstep abort landed
        between sync boundaries — the election then falls back to the
        newest committed snapshot)."""
        if box is None or not box.in_pass or not self._pass_aborted:
            return None
        steps = int(self.last_pass_steps)
        ws = self._last_ws
        if steps <= 0 or ws is None:
            return None
        mode = self.cfg.dense_sync_mode
        if mode == "kstep" and steps % self.cfg.param_sync_step:
            # between syncs the uninterrupted run's cadence cannot be
            # reproduced from here; skipping is safe — the election falls
            # back — and observable
            monitor.event("drain_snapshot_skipped",
                          reason="kstep_off_sync_boundary", steps=steps)
            return None
        if mode == "async":
            self.dense_table.flush()
            dense = (self._unravel(self.dense_table.pull()),
                     self.dense_table.state_dict())
        else:
            # the pre-finalize loop planes the pass finally stashed —
            # for kstep the STACKED per-shard state, not the pmean'd
            # finalize output (which can differ by an ulp for
            # non-power-of-2 shard counts)
            dense = self._last_dense
        # the aborted pass never reached feed end_pass: mark its touched
        # rows unsynced so the checkpointer's flush materializes them
        self.feed_mgr.end_pass(ws, ws.table)
        snap = checkpointer.save(
            self, box=box,
            metrics=(metrics if metrics is not None else box.metrics),
            pass_id=int(box.pass_id) - 1, mid_steps=steps,
            dense_override=dense,
            shuffle_state=self.midpass_cursor_extra.get("shuffle_state"))
        monitor.counter_add("resilience.drain_snapshots")
        monitor.event("drain_snapshot", type="lifecycle",
                      snapshot=snap, mid_steps=steps)
        return snap

    def recover_world(self, world, failure, checkpointer, box,
                      metrics=None):
        """The elastic catch-arm: a :class:`PeerFailureError` escaped the
        pass loop — drain-snapshot, re-form the world without the dead
        ranks, re-run the coordinated resume election over the survivors,
        and hand back ``(new_world, cursor)`` for the driver to continue
        from (``cursor`` may be None when the survivors hold no common
        snapshot: whole-world fresh start).

        Bounded retry with exponential backoff: a FURTHER failure during
        the re-formation/election window escalates the generation and
        retries up to ``flags.elastic_max_reforms`` times; exhaustion
        re-raises the original failure (fail-stop, the pre-elastic
        behavior). When survivors would fall below
        ``flags.elastic_min_world`` the drain snapshot already committed
        — returns ``(None, None)`` so the driver checkpoints-and-exits
        cleanly. A :class:`WorldFencedError` (this rank was excluded by a
        sealed generation) propagates: the rank's timeline was abandoned,
        exiting cleanly is the only safe move."""
        from paddlebox_tpu.distributed import resilience
        self.drain_and_snapshot(checkpointer, box, metrics=metrics)
        if box is not None and box.in_pass:
            box.abort_pass(reason=repr(failure))
        dead = sorted(set(int(r) for r in failure.ranks))
        backoff = float(config_flags.elastic_reform_backoff_s)
        for attempt in range(max(1, int(config_flags.elastic_max_reforms))):
            if attempt:
                time.sleep(backoff)
                backoff *= 2.0
            try:
                new_world = world.reform(dead)
            except resilience.WorldTooSmallError as e:
                monitor.event("elastic_min_world_exit", type="lifecycle",
                              survivors=e.survivors, floor=e.floor)
                return None, None
            self.peer_check = new_world.check
            own = self.feed_mgr.ownership
            if own is not None:
                # elastic resize of the per-host build partition: the
                # re-formed world re-deals the store shards, and this
                # host's next begin_pass rebuilds exactly its (new)
                # shards' working set — the replacement-host /
                # degraded-world grow-and-shrink hook
                self.feed_mgr.set_ownership(
                    own.with_world(new_world.world, new_world.rank))
            if box is not None:
                box.attach_collectives(new_world.collectives,
                                       heartbeat=new_world.heartbeat)
            try:
                cursor = resilience.coordinated_resume(
                    checkpointer, self, new_world.collectives, box=box,
                    metrics=(metrics if metrics is not None
                             else (box.metrics if box is not None
                                   else None)))
                monitor.counter_add("resilience.elastic_recoveries")
                return new_world, cursor
            except resilience.PeerFailureError as e:
                # another rank died inside the election/restore window;
                # the restore is idempotent (at worst this rank already
                # stands on the elected snapshot and re-elects it) —
                # escalate the generation without the newly dead
                world = new_world
                dead = sorted(set(int(r) for r in e.ranks))
                failure = e
        raise failure

    def save_checkpoint(self, checkpointer, box=None, metrics=None,
                        pass_id: int | None = None) -> str:
        """Snapshot the complete post-pass state (dense + optimizer +
        sparse base/delta + metrics + cursor) through a
        :class:`~paddlebox_tpu.utils.pass_ckpt.PassCheckpointer`. Flushes
        the device tier (pending deferred push + lazily-retained rows)
        first, so the snapshot is self-contained."""
        return checkpointer.save(self, box=box, metrics=metrics,
                                 pass_id=pass_id)

    def resume(self, checkpointer, box=None, metrics=None,
               collectives=None) -> dict | None:
        """Crash recovery: restore every plane from the newest snapshot
        whose manifest chain verifies (base + ordered deltas checksum-
        clean, tombstone-consistent replay via ``store.restore``), falling
        back past a torn/truncated newest snapshot automatically.

        Restores the sparse store in place (device-resident rows are
        invalidated via the store's mutation counter), the dense
        params/optimizer state mode-aware (``restore_dense``), the metric
        registry + phase bit, and the pass/step cursor. Returns the cursor
        dict ({pass_id, global_step, date, phase, mid_steps,
        shuffle_state}) — the driver re-enters its pass loop at
        ``cursor["pass_id"] + 1`` (with ``skip_steps=mid_steps`` when
        resuming mid-pass) — or None when there is nothing to resume
        (fresh start).

        ``collectives`` (a HostCollectives with world > 1) switches to the
        COORDINATED multi-host path: every rank publishes its intact
        snapshot cursors, the world elects the highest cursor every rank
        holds intact, barriers, and all ranks restore that same snapshot
        (distributed/resilience.coordinated_resume) — a torn newest
        snapshot on one rank rolls the whole world back together instead
        of diverging it."""
        if collectives is not None and collectives.world > 1:
            from paddlebox_tpu.distributed import resilience
            return resilience.coordinated_resume(
                checkpointer, self, collectives, box=box, metrics=metrics)
        return checkpointer.resume(self, box=box, metrics=metrics)

    # the root of an eval pass's timeline, as train_pass is of a train
    # pass's: the stages the two share nest in it under their own names
    # (unique_keys, boundary, preplan, the pack thread's, h2d_stage,
    # auc_update, pass_close/read)
    @monitor.span("eval_pass")
    def eval_pass(self, dataset) -> dict[str, float]:
        """Test-mode pass: no pushes, no dense updates, and the store is
        neither grown nor dirtied by unseen keys (SetTestMode).

        Routed capacity overflow never poisons the returned numbers:
        a pass that dropped tokens already grew the eval capacity
        (``_check_dropped``'s adaptive doubling) and re-runs IN PLACE at
        the grown factor — eval is pure, so the retry is free of side
        effects, and the factor caps at n_shards where drops are
        impossible. The trainer-level half of the exchange's
        never-silent overflow policy (the train side is preplanned
        lossless up front and doubles for its next pass)."""
        if not self._feeds_auc:
            raise NotImplementedError(
                f"model {self.model.name!r} declares no prediction "
                f"(models/base.py): an eval pass has nothing to score")
        # flush-before-eval ordering (push_overlap): predictions must see
        # every trained row value; a pending deferred apply lands first
        self.flush_push()
        out = self._eval_pass_once(dataset)
        for attempt in range(8):      # capf doubles; n_shards cap ends it
            if (not out["routed_dropped"]
                    or config_flags.routed_drop_fatal
                    or not config_flags.routed_drop_adapt):
                break
            faultpoint.hit("exchange.eval.pre_retry")
            monitor.counter_add("exchange.overflow_retries")
            monitor.event("exchange_overflow_retry", type="lifecycle",
                          dropped=int(out["routed_dropped"]),
                          capacity_factor=float(self._eval_capacity),
                          attempt=attempt + 1)
            out = self._eval_pass_once(dataset)
        monitor.event("eval_pass", auc=float(out.get("auc", float("nan"))),
                      routed_dropped=out["routed_dropped"])
        return out

    def _eval_pass_once(self, dataset) -> dict[str, float]:
        bs = self.cfg.global_batch_size
        with self.timers("unique_keys", span="unique_keys"):
            keys = dataset.unique_keys()
        ws = self.feed_mgr.begin_pass(keys, test_mode=True)
        with self.timers("preplan", span="preplan"):
            self._preplan_capacity(dataset, ws, drop_last=False,
                                   for_eval=True)
        auc_acc = auc_lib.AucAccumulator(self.cfg.auc_buckets)
        dev_dropped = []
        # same background pack pipeline as train_pass (translate + H2D
        # overlap the eval steps) — an AUC-runner ablation sweep runs one
        # eval per slot and must not pay a serialized host path per pass
        # (test-mode feed, data_feed.h:1372-1535). Eval never pushes, so
        # the host plan is skipped; the tail batch pads instead of drops.
        pack_it = self._pack_iter(dataset, ws, bs, with_plan=False,
                                  drop_last=False)
        try:
            for pb, staged in pack_it:
                idx, mask, dense, labels = staged[:4]
                extras = staged[4 + PLAN_ARITY:]   # empty plan slots
                preds, dropped = self._eval_fn(ws.table,
                                               self.eval_params(),
                                               idx, mask, dense, *extras)
                valid = jnp.arange(bs) < pb.num    # pre-pad valid count
                with self.timers("auc", span="auc_update"):
                    auc_acc.update(self._auc_masked_fn, preds, labels,
                                   valid)
                dev_dropped.append(dropped)
        finally:
            pack_it.close()
        with monitor.span("pass_close/read"):
            out = auc_acc.compute()
            # drops poison eval predictions too — same non-silent policy,
            # but adaptation stays on the eval program only (and eval_pass
            # re-runs this whole body at the grown factor)
            out["routed_dropped"] = self._check_dropped(dev_dropped,
                                                       for_eval=True)
        return out
