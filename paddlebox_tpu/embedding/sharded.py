"""In-jit sharded embedding lookup/push.

This module is the TPU replacement for the reference's device-side embedding
path: ``BoxWrapper::PullSparse``/``PushSparseGrad`` dispatch
(box_wrapper_impl.h:25,164), the ``PullCopy*``/``PushCopy*``/``PushMergeCopy*``
CUDA kernel families (box_wrapper.cu:35-830), and the sharded
``PullSparseGPU``/``PushSparseGPU`` lookups inside libbox_ps.

Design (SURVEY.md §2.3 "TPU-native equivalents"): the pass working set is a
dense ``(N, row_width)`` float32 table (one array, or the two planes of
``quant.PlaneTable`` where ``working_set.plane_layout`` says so) sharded
contiguously over the mesh's device axis; batches carry dense int32 indices
(index 0 = null/padding row).
Three strategies:

- ``lookup``/``push`` — single-shard (or fully-replicated) gather / dedup'd
  scatter-update. Used standalone on one chip and as the per-shard core of
  the routed path.
- ``routed_lookup``/``routed_push`` — the distributed path inside
  ``shard_map``: tokens are routed to the owning shard with a fixed-capacity
  ``lax.all_to_all`` over ICI (the hand-built hierarchy of the reference's
  NCCL+SyncDense collapses into mesh collectives).

Duplicate keys are merged on-device before the optimizer applies (the role of
``PushMergeCopy``): ``push`` merges token payloads onto one lane per touched
row, then applies the optimizer to those rows — in the table, row by row,
where rows can be addressed (premerged lanes on a lane-tile table), or
vectorized over a per-row accumulator masked to the touched rows elsewhere.
The math matches the reference's merge-then-update semantics either way (see
the ``push`` docstring for the TPU cost rationale).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddlebox_tpu.config import flags as config_flags
from paddlebox_tpu.embedding.config import EmbeddingConfig
from paddlebox_tpu.embedding.optim import apply_updates
from paddlebox_tpu.embedding import gating, quant
from paddlebox_tpu.monitor import device_scope
from paddlebox_tpu.ops import pallas_kernels

NULL_INDEX = 0  # reserved all-zero row; padding tokens point here


def _take_rows(arr: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Full-row gather behind an optimization barrier (see lookup)."""
    return lax.optimization_barrier(jnp.take(arr, idx, axis=0))


def gate_pull(pulled: jnp.ndarray, cfg: EmbeddingConfig) -> jnp.ndarray:
    """Variable/NNCross presence masks (PullCopy*NNCross zero fill,
    box_wrapper.cu:199-221): a key whose show has not reached a plane's
    create threshold pulls that plane as zeros. No-op at thresholds 0."""
    return gating.gate_pull_xp(pulled, cfg, jnp)


# ---------------------------------------------------------------------------
# single-shard core
# ---------------------------------------------------------------------------

def lookup(table: jnp.ndarray, idx: jnp.ndarray,
           cfg: EmbeddingConfig) -> jnp.ndarray:
    """Gather pull values (show, clk, w, embedx) for flat int32 indices.

    idx may have any shape; returns idx.shape + (pull_width,). Null/padding
    indices return the zero row (FLAGS_enable_pull_box_padding_zero
    semantics, flags.cc:607).

    TPU note: gather FULL rows, then slice columns — behind an
    optimization barrier so XLA cannot re-fuse the slice into the gather.
    A fused column-sliced gather (``table[idx, :w]``) lowers to a
    catastrophically slow path on TPU (~26x: 568ms vs 22ms for 213k tokens
    from a 512k x 11 f32 table on one v5e, measured with forced D2H sync).

    Plane tables (quant.PlaneTable) gather both planes: the embedx plane's
    rows directly — an f32 plane of whole lane tiles is row-major on the
    chip, so no copy of the table precedes the gather — and show, clk and
    the w-block out of the narrow plane's rows. Quantized planes
    dequantize at the gather — f32 compute, int storage (quant.py).
    """
    flat = idx.reshape(-1)
    if quant.is_planes(table):
        fp = _take_rows(table.fp, flat)
        x = _take_rows(table.qx, flat)
        if quant.is_quant(table):
            x = x.astype(jnp.float32) * fp[:, -1:]
        pulled = jnp.concatenate([fp[:, :cfg.fixed_cols], x], axis=1)
        return gate_pull(pulled, cfg).reshape((*idx.shape, cfg.pull_width))
    rows = _take_rows(table, flat)
    pulled = rows[:, :cfg.pull_width]
    return gate_pull(pulled, cfg).reshape((*idx.shape, cfg.pull_width))


# ---------------------------------------------------------------------------
# fused gather-pool pull (the multi-hot/wide-dim fast path)
# ---------------------------------------------------------------------------

def fused_pull_supported(cfg: EmbeddingConfig) -> bool:
    """Semantics preconditions of the fused gather-pool pull, independent
    of geometry: the pooled path skips gate_pull (create-threshold
    presence masks act per ROW and the pooled cotangent expansion would
    need the per-row gate to route grads), so it must not engage where
    gating matters. Storage is NOT checked here — the jnp reference
    inside fused_pull_pool handles quantized tables; only the kernel is
    f32-only (gather_pool_supported)."""
    return (cfg.mf_create_threshold == 0
            and cfg.expand_create_threshold == 0)


def fused_pull_pool(table, idx: jnp.ndarray, cfg: EmbeddingConfig,
                    num_slots: int, slot_len: int) -> jnp.ndarray:
    """(B, S*L) translated indices → (B, S, pull_width) sum-pooled rows.

    The fused form of lookup + per-slot sum pool for the uniform slot
    layout: on real TPU with a supported geometry the Pallas gather-pool
    kernel gathers rows from the HBM table and pools them in VMEM — the
    (B*T, pull_width) pulled matrix never materializes. Elsewhere (CPU
    test meshes, quantized storage, unsupported geometry) the identical
    jnp math runs through lookup + reshape-sum. Masked tokens must
    already be nulled to NULL_INDEX (translate does), and the null row
    is all-zero by the working-set contract, so padding contributes
    zeros without a mask operand. The backward pass is NOT defined here:
    trainers take grads against the pooled output and expand them per
    token with pooled_grad_tokens (into the dedup premerge + binned
    push), and the standalone op form lives in
    ops.seqpool_cvm.fused_gather_seqpool_cvm."""
    from paddlebox_tpu.ops import pallas_kernels
    B = idx.shape[0]
    if (not quant.is_planes(table)
            and pallas_kernels.gather_pool_supported(
                cfg, B, num_slots, slot_len, table.shape[1])):
        return pallas_kernels.gather_pool(table, idx, cfg, num_slots,
                                          slot_len)
    pulled = lookup(table, idx.reshape(-1), cfg)
    return pulled.reshape(B, num_slots, slot_len,
                          cfg.pull_width).sum(axis=2)


def pooled_grad_tokens(gpooled: jnp.ndarray, mask: jnp.ndarray,
                       segment_ids, num_slots: int) -> jnp.ndarray:
    """Per-token sparse grads from the pooled cotangent.

    Pooling is a per-segment sum, so each token's pull cotangent is its
    (example, slot) pooled row: gpooled (B, S, pull_width) → (B*T,
    grad_width) rows ``gpooled[b, seg[t], 2:] * mask[b, t]`` (show/clk
    cotangents dropped like the unfused path's ``gpull[..., 2:]``). The
    (B*S, ·) source is ~slot_len times smaller than the token matrix and
    XLA fuses this gather into its consumer (the premerge cumsum /
    binned-push pack), so the fused path's backward never stores a
    (B, T, pull_width) array either. The mask multiply keeps null-row
    grads zero (push's contract for NULL_INDEX)."""
    B, S, P = gpooled.shape
    seg = jnp.asarray(np.asarray(segment_ids), jnp.int32)
    bs = (jnp.arange(B, dtype=jnp.int32)[:, None] * S
          + seg[None, :]).reshape(-1)
    tok = jnp.take(gpooled.reshape(B * S, P)[:, 2:], bs, axis=0)
    return tok * mask.reshape(-1).astype(tok.dtype)[:, None]


# Cumsum restart granularity of the premerge segment sums: bounds the
# f32 prefix magnitude each segment difference cancels against to one
# block's payload sum instead of the whole token stream's (ADVICE r5:
# at ~852k tokens the full-length prefix makes grad error scale with
# the PREFIX magnitude, not the segment's).
_CS_BLOCK = 4096


@device_scope("premerge")
def plan_premerge(idx: jnp.ndarray, grads: jnp.ndarray,
                  shows: jnp.ndarray, clks: jnp.ndarray, plan):
    """Device half of the host dedup plan: segment-sum per-token payloads
    onto one lane per unique row (the merge half of the reference's
    DedupKeysAndFillIdx + PushMergeCopy pairing, box_wrapper_impl.h:103,
    box_wrapper.cu:630-830).

    The host counting sort (native pbtpu_dedup_plan) already grouped
    tokens by row, so the sum is a prefix sum over the sorted payload
    differenced at the (sorted, ascending) segment ends — no argsort, no
    per-duplicate scatter. The prefix sum RESTARTS every _CS_BLOCK
    tokens (block-local cumsum + per-block exclusive bases): the
    block-base terms cancel exactly for segments inside one block
    (identical gathered values), so a segment's rounding error scales
    with its block's payload magnitude, not the full stream's — signed
    grads at 852k tokens would otherwise cancel against an unbounded
    prefix. Pad lanes carry zero-width segments and ascending
    out-of-range row ids, so downstream engines drop them and the
    scatter engine may legally promise sorted+unique indices.

    `order` has one entry a token; `uniq` and `segend` have L lanes, any
    L from the batch's distinct rows up to its tokens (the host ships a
    bucket over the distinct rows where the plan carries no kernel
    windows, Trainer._host_plan). The cumsum runs over tokens, only the
    boundary gathers and their difference over lanes, so the merged
    values of the valid lanes do not depend on L to the bit.

    Returns (uniq_idx, merged_grads, merged_shows, merged_clks,
    kernel_plan) — kernel_plan is (None, rstart, end) unique-lane DMA
    windows (order=None: already sorted), or None when the plan carries
    no kernel windows (scatter-engine widths)."""
    order, rstart, endb, uniq, segend = plan
    pay = jnp.concatenate([grads, shows[:, None], clks[:, None]], axis=1)
    s_pay = jnp.take(pay, order, axis=0)
    n, Wp = s_pay.shape
    C = _CS_BLOCK
    nc = max(1, -(-n // C))
    pad = nc * C - n
    if pad:
        s_pay = jnp.concatenate(
            [s_pay, jnp.zeros((pad, Wp), s_pay.dtype)], axis=0)
    blocks = s_pay.reshape(nc, C, Wp)
    # lcs0[c, j] = sum of block c's first j tokens; base[c] = sum of all
    # tokens before block c. prefix(p) = base[p // C] + lcs0[p // C, p % C]
    lcs0 = jnp.concatenate(
        [jnp.zeros((nc, 1, Wp), s_pay.dtype), jnp.cumsum(blocks, axis=1)],
        axis=1)
    base = jnp.concatenate(
        [jnp.zeros((1, Wp), s_pay.dtype),
         jnp.cumsum(lcs0[:, -1, :], axis=0)], axis=0)[:-1]
    flat_lcs = lcs0.reshape(nc * (C + 1), Wp)
    starts = jnp.concatenate(
        [jnp.zeros((1,), segend.dtype), segend[:-1]])
    # boundary gathers ride the sorted-indices fast path (segend/starts
    # ascend by construction, and // and % preserve that order)
    dnums = lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0,))

    def prefix_parts(p):
        # p == nc*C (the stream end) flattens past lcs0 and clips to the
        # equivalent (nc-1, C) cell, its base index to nc-1 — exactly the
        # stream total; interior block boundaries read (c, 0) = base[c].
        c = p // C
        li = c * (C + 1) + lax.rem(p, C)
        b = lax.gather(base, c[:, None], dnums, (1, Wp),
                       indices_are_sorted=True, mode="clip")
        loc = lax.gather(flat_lcs, li[:, None], dnums, (1, Wp),
                         indices_are_sorted=True, mode="clip")
        return b, loc
    b_hi, l_hi = prefix_parts(segend)
    b_lo, l_lo = prefix_parts(starts)
    # local differences first: same-block segments see their bases cancel
    # exactly in (b_hi - b_lo)
    m = (l_hi - l_lo) + (b_hi - b_lo)
    gw = grads.shape[1]
    kplan = (None, rstart, endb) if rstart.shape[0] else None
    return uniq, m[:, :gw], m[:, gw], m[:, gw + 1], kplan


def _normalize_plan(plan):
    """(plan3_or_None, premerge5_or_None) from a caller plan tuple.

    Plans arrive as 3-tuples (order, rstart, end — the kernel grouping),
    or 5-tuples (+ uniq, segend — the dedup pre-merge); zero-length
    leading arrays mean the corresponding half is absent (the jit static
    branch)."""
    if plan is None:
        return None, None
    if len(plan) == 3:
        return (plan if plan[0].shape[0] else None), None
    order, rstart, endb, uniq, segend = plan
    if uniq.shape[0]:
        return None, plan
    return ((order, rstart, endb) if order.shape[0] else None), None


def deferred_push_operands(idx: jnp.ndarray, grads: jnp.ndarray,
                           shows: jnp.ndarray, clks: jnp.ndarray, plan
                           ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Packed push operands for a DEFERRED table apply (flags.push_overlap).

    The jitted step calls this in place of the push so the scatter-update
    leaves the loss-producing program entirely; the trainer's apply
    program consumes the result one step later. Uniform arity (g0, g1,
    g2) so the step's output signature is static across plan variants:

    - dedup-plan batches premerge IN-STEP (plan_premerge segment-sums
      per-token payloads onto unique lanes) → (merged_grads,
      merged_shows, merged_clks); the apply replays only the engine on
      the staged unique lanes.
    - otherwise → (per-token grads, empty, empty); the apply recomputes
      show/clk increments from the staged mask/labels (bit-identical:
      same arrays, same ops) and runs the full push.

    The premerge stays in the step deliberately: it consumes the sparse
    cotangent right where backward produces it (off the loss path — loss
    and preds do not depend on it), and the apply's operand shrinks to
    one lane per unique row."""
    if plan is not None and plan[3].shape[0]:
        _, mg, ms, mc, _ = plan_premerge(idx, grads, shows, clks, plan)
        return mg, ms, mc
    # zero-length placeholders SLICED from grads (not fresh constants):
    # they inherit the varying-manual-axes type, so the step's batch-spec
    # out_specs hold under strict vma checking
    empty = grads[:0, 0]
    return grads, empty, empty


def push(table: jnp.ndarray, idx: jnp.ndarray, grads: jnp.ndarray,
         shows: jnp.ndarray, clks: jnp.ndarray,
         cfg: EmbeddingConfig, plan=None,
         premerged: bool = False) -> jnp.ndarray:
    """Merge-and-update: apply summed grads + show/clk increments in-table.

    idx   : (n,) int32 row indices (duplicates fine; 0 = null, must carry
            zero grads/increments; values >= table rows are dropped — the
            routed path uses that for empty all-to-all lanes)
    grads : (n, grad_width) d_w, d_embedx per token
    shows, clks : (n,) counter increments per token
    premerged : idx/grads/shows/clks are already unique lanes (ascending,
            pads out-of-range — plan_premerge's output, e.g. replayed by
            a deferred apply); `plan` is then the kernel-window 3-tuple
            (order_or_None, rstart, end) or None, not a caller plan.
    Returns the updated table.

    Implementation note (TPU): the merge engine is selected by
    pallas_kernels.resolve_push_engine — ONE resolver shared with
    Trainer.engines() (flags.push_engine forces for A/Bs). Premerged f32
    lanes on a table whose rows can be addressed (f32 planes, or one
    array of whole lane tiles) take scatter_accumulate: each touched
    row gathered, updated, written back once — no accumulator, no pass
    over untouched rows, the table updated in place. Narrow raw token
    streams take the binned one-hot MXU merge; otherwise duplicates are
    merged with ONE fused scatter-add into a per-row accumulator and
    the optimizer applies vectorized over the whole table, masked to
    touched rows. All three preserve the reference's merge-then-update
    semantics (PushMergeCopy, box_wrapper.cu:630-830) — sort-based
    dedup costs several gather/scatter/sort ops per step, and on TPU
    each of those carries a large fixed cost. Where the accumulator
    engines run (a one-array table the chip stores column-major:
    narrow and half-tile widths, where no row can be addressed; raw
    token streams) their pass is O(table) per step; a wide-row table
    leaves that by its layout (working_set.plane_layout), a very large
    narrow one by a sharded mesh (each shard scans only its rows).
    """
    if premerged:
        kplan, dplan = plan, None
    else:
        kplan, dplan = _normalize_plan(plan)
    if dplan is not None:
        # host dedup plan: segment-sum duplicates onto unique lanes
        # first, so whichever engine runs below sees each touched row
        # once (852k multi-hot tokens -> ~330k unique lanes)
        idx, grads, shows, clks, kplan = plan_premerge(
            idx, grads, shows, clks, dplan)
        premerged = True
    n = idx.shape[0]
    n_rows = quant.table_rows(table)
    is_p = quant.is_planes(table)
    engine = pallas_kernels.resolve_push_engine(
        cfg, n_rows, premerged=premerged,
        storage_f32=not quant.is_quant(table),
        table_width=quant.row_engine_width(table))
    if engine == "scatter_accumulate":
        # row-wise merge-apply over the premerged unique lanes: each
        # touched row gathers once, updates, writes back once — no
        # full-table accumulator, no O(table) update pass (XLA's gather
        # and scatter on planes; on one array the Pallas kernel on real
        # TPU, identical jnp math elsewhere)
        return pallas_kernels.scatter_accumulate(table, idx, grads,
                                                 shows, clks, cfg)
    if (engine == "binned_kernel" and not is_p
            and pallas_kernels.binned_push_supported(table, cfg)):
        # scatter-free merge+update for narrow rows: the binned kernel
        # streams the merge through the MXU and measures ~2x the XLA
        # scatter there; wide rows (G=1) keep the scatter, which
        # measures faster (binned_push_supported docstring)
        return pallas_kernels.binned_push(
            table, idx, grads, shows, clks, cfg,
            n_split=config_flags.binned_push_splits, plan=kplan)
    gw = cfg.grad_width
    if engine == "binned_kernel":
        # quantized tables (and other storage variants) reuse the
        # scatter-free merge: the kernel's acc contract is
        # storage-agnostic, and the in-step scatter it replaces measured
        # ~13ms of the 20.8ms int16 step (dim 8, batch 8192, one v5e —
        # same win as the f32 path)
        acc = pallas_kernels.binned_merge_acc(
            idx, grads, shows, clks, cfg, n_rows,
            n_split=config_flags.binned_push_splits, plan=kplan,
            vma=getattr(jax.typeof(table.fp if is_p else table), "vma",
                        frozenset()))
    else:
        payload = jnp.concatenate(
            [grads, shows[:, None], clks[:, None],
             jnp.ones((n, 1), grads.dtype)], axis=1)
        acc = jnp.zeros((n_rows, gw + 3), payload.dtype)
        # pre-merged lanes are ascending and distinct by construction
        # (pads use ascending out-of-range ids), so the scatter may
        # promise sorted+unique — the hints XLA needs to skip its
        # conflict-safe serial path
        acc = acc.at[idx].add(payload, mode="drop",
                              indices_are_sorted=premerged,
                              unique_indices=premerged)
    # Untouched rows keep their exact bits (stateful optimizers like adam
    # would otherwise decay momentum on every row; a quantized row must not
    # requantize — round twice — unless it really changed). The null row
    # only ever receives zero grads/increments (callers mask padding), and
    # a fresh zero row is a fixed point of every optimizer — it stays zero.
    if not is_p and acc.shape[1] >= 64 and jax.default_backend() == "tpu":
        # wide accumulators: XLA's fused update+where degrades ~3x when
        # the slice fusion consumes a computed acc (in-composition A/B
        # on one v5e, dim 64, 213k tokens: 15.7ms vs 5.9ms with the
        # single-custom-call merge_update; narrow accs show the
        # opposite — dim 8: 2.8ms vs 4.7ms — and keep the XLA fusion)
        return pallas_kernels.merge_update(table, acc, cfg)
    touched = acc[:, gw + 2] > 0
    if is_p:
        # (dequant ->) exact f32 update (-> requant), one fused
        # elementwise pass over the planes (no f32 table materializes
        # in HBM)
        rows = quant.assemble_rows(table.fp, table.qx, cfg)
        new_rows = apply_updates(rows, acc[:, :gw], acc[:, gw],
                                 acc[:, gw + 1], cfg)
        new_fp, new_qx = quant.split_rows(new_rows, cfg)
        return quant.PlaneTable(
            fp=jnp.where(touched[:, None], new_fp, table.fp),
            qx=jnp.where(touched[:, None], new_qx, table.qx))
    if pallas_kernels.use_pallas():
        # single fused read-modify-write pass over the table
        return pallas_kernels.merge_update(table, acc, cfg)
    new_rows = apply_updates(table, acc[:, :gw], acc[:, gw], acc[:, gw + 1],
                             cfg)
    return jnp.where(touched[:, None], new_rows, table)


# ---------------------------------------------------------------------------
# routed (multi-shard) path — runs inside shard_map
# ---------------------------------------------------------------------------

def _axis_size(axis_name) -> jnp.ndarray:
    if isinstance(axis_name, (tuple, list)):
        s = 1
        for a in axis_name:
            s *= lax.axis_size(a)
        return s
    return lax.axis_size(axis_name)


def _route(idx: jnp.ndarray, rows_per_shard: int, n_shards: int, cap: int):
    """Compute the fixed-capacity routing plan for a flat token vector.

    Returns (order, sorted_owner, pos, valid, send_idx) where ``send_idx``
    is the (n_shards, cap) per-destination index buffer (−1 = empty lane).
    Tokens beyond a destination's capacity are dropped (monitor with
    `routed_dropped`).

    NULL_INDEX (masked/padding) tokens are never routed: they want the
    zero row, which every consumer synthesizes locally — and on row-0's
    shard they would otherwise flood the capacity lanes and crowd out
    real tokens (a batch is often 20-40% padding).
    """
    owner = jnp.where(idx == NULL_INDEX, n_shards, idx // rows_per_shard)
    return _route_owner(idx, owner, n_shards, cap)


def _route_owner(idx: jnp.ndarray, owner: jnp.ndarray, n_groups: int,
                 cap: int):
    """The routing-plan core with the destination group precomputed:
    group `n_groups` is the drop group (never sent). The argsort is
    STABLE, so within each group tokens keep their input order — an
    ascending input yields ascending per-destination runs (the invariant
    the D-way merge of the receive side rests on)."""
    n = idx.shape[0]
    order = jnp.argsort(owner)
    sidx = idx[order]
    sowner = owner[order]
    counts = jnp.bincount(owner, length=n_groups + 1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(n, dtype=jnp.int32) - starts[sowner]
    valid = (pos < cap) & (sowner < n_groups)
    send_idx = jnp.full((n_groups, cap), -1, dtype=idx.dtype)
    # sowner == n_groups (null group) lands out of bounds → dropped
    send_idx = send_idx.at[sowner, pos].set(sidx, mode="drop")
    return order, sowner, pos, valid, send_idx


def routed_lookup(table_shard: jnp.ndarray, idx: jnp.ndarray,
                  cfg: EmbeddingConfig, axis_name,
                  capacity_factor: float = 2.0,
                  dedup: bool = False,
                  return_dropped: bool = False):
    """Distributed gather inside shard_map.

    table_shard : (rows_per_shard, row_width) this device's contiguous shard
    idx         : (n,) int32 *global* working-set indices for this device's
                  local batch tokens
    dedup       : route each unique token once and re-expand after the
                  gather (FLAGS_enable_pullpush_dedup_keys). The dedup sort
                  costs more than a whole single-chip step (~6ms at 213k
                  tokens on one v5e), so enable it only where all_to_all
                  volume is the binding cost.
    return_dropped : also return this device's count of real tokens that
                  exceeded a destination's capacity lane and were dropped
                  (exact — computed from the routing plan's validity mask).
                  The reference never drops (dynamic buffers,
                  box_wrapper_impl.h:44-81); here drops are the cost of
                  static shapes, so they MUST be observable (see
                  Trainer.train_pass for the warn/raise/adapt policy).
    Returns (n, pull_width), or (out, dropped) with return_dropped.
    """
    n = idx.shape[0]
    D = _axis_size(axis_name)
    if D == 1:  # single shard: no routing, one direct gather
        out = lookup(table_shard, idx, cfg)
        return (out, jnp.zeros((), jnp.int32)) if return_dropped else out
    if dedup:
        uniq, inverse = dedup_tokens(idx)
        res = routed_lookup(table_shard, uniq, cfg, axis_name,
                            capacity_factor,
                            return_dropped=return_dropped)
        if return_dropped:
            return res[0][inverse], res[1]
        return res[inverse]
    rps = quant.table_rows(table_shard)
    cap = _capacity(n, D, capacity_factor)
    order, sowner, pos, valid, send_idx = _route(idx, rps, D, cap)
    recv_idx = lax.all_to_all(send_idx, axis_name, 0, 0, tiled=True)
    local_row = jnp.where(recv_idx >= 0, recv_idx % rps, 0)
    lane_ok = (recv_idx >= 0)[:, :, None]
    if quant.is_planes(table_shard):
        # plane a2a payload: the embedx plane crosses ICI as it is
        # stored (int8/16 where quantized — the reference's quant pull
        # variants applied to the collective) plus a small f32 plane
        # (show, clk, w-block, and the scale of a quantized row)
        is_q = quant.is_quant(table_shard)
        fc = cfg.fixed_cols
        fp = _take_rows(table_shard.fp, local_row.reshape(-1))
        qx = _take_rows(table_shard.qx, local_row.reshape(-1))
        fph = (jnp.concatenate([fp[:, :fc], fp[:, -1:]], axis=1) if is_q
               else fp[:, :fc])
        fph = jnp.where(lane_ok, fph.reshape(D, cap, -1), 0.0)
        qx = jnp.where(lane_ok, qx.reshape(D, cap, -1), 0)
        back_fp = lax.all_to_all(fph, axis_name, 0, 0, tiled=True)
        back_qx = lax.all_to_all(qx, axis_name, 0, 0, tiled=True)
        x = (back_qx.astype(jnp.float32) * back_fp[:, :, -1:] if is_q
             else back_qx)
        back = jnp.concatenate([back_fp[:, :, :fc], x], axis=2)
    else:
        # full-row take + barrier + slice: see lookup() for the rationale
        vals = _take_rows(table_shard,
                          local_row.reshape(-1))[:, :cfg.pull_width]
        vals = vals.reshape(D, cap, cfg.pull_width)
        vals = jnp.where(lane_ok, vals, 0.0)
        back = lax.all_to_all(vals, axis_name, 0, 0, tiled=True)
    # null-group rows (sowner == D) are clamped then zeroed by `valid`
    gathered = back[jnp.minimum(sowner, D - 1), jnp.minimum(pos, cap - 1)]
    gathered = jnp.where(valid[:, None], gathered, 0.0)
    out = jnp.zeros((n, cfg.pull_width), gathered.dtype).at[order].set(gathered)
    out = gate_pull(out, cfg)
    if return_dropped:
        dropped = jnp.sum((~valid) & (sowner < D)).astype(jnp.int32)
        return out, dropped
    return out


def routed_push(table_shard: jnp.ndarray, idx: jnp.ndarray,
                grads: jnp.ndarray, shows: jnp.ndarray, clks: jnp.ndarray,
                cfg: EmbeddingConfig, axis_name,
                capacity_factor: float = 2.0,
                dedup: bool = False, plan=None) -> jnp.ndarray:
    """Distributed merge-update inside shard_map (reverse of routed_lookup).

    dedup merges per-token payloads onto unique tokens with ONE
    concatenated scatter-add before routing (see routed_lookup on when it
    pays; masked tokens carry zero payloads so their merge onto the null
    slot is a no-op). `plan` (host binned-push token grouping) applies to
    the single-shard path only — post-all_to_all tokens have no host
    plan."""
    n = idx.shape[0]
    D = _axis_size(axis_name)
    if D == 1:
        return push(table_shard, idx, grads, shows, clks, cfg, plan=plan)
    if dedup:
        uniq, inverse = dedup_tokens(idx)
        payload = jnp.concatenate(
            [grads, shows[:, None], clks[:, None]], axis=1)
        merged = jnp.zeros((uniq.shape[0], payload.shape[1]),
                           payload.dtype).at[inverse].add(payload)
        gw = cfg.grad_width
        return routed_push(table_shard, uniq, merged[:, :gw],
                           merged[:, gw], merged[:, gw + 1], cfg,
                           axis_name, capacity_factor)
    rps = quant.table_rows(table_shard)
    cap = _capacity(n, D, capacity_factor)
    order, sowner, pos, valid, send_idx = _route(idx, rps, D, cap)
    payload = jnp.concatenate(
        [grads, shows[:, None], clks[:, None]], axis=1)[order]
    send_pay = jnp.zeros((D, cap, payload.shape[1]), payload.dtype)
    send_pay = send_pay.at[sowner, pos].set(payload, mode="drop")
    recv_idx = lax.all_to_all(send_idx, axis_name, 0, 0, tiled=True)
    recv_pay = lax.all_to_all(send_pay, axis_name, 0, 0, tiled=True)
    flat_idx = recv_idx.reshape(-1)
    flat_pay = recv_pay.reshape(-1, payload.shape[1])
    empty = flat_idx < 0
    # Empty lanes go out-of-bounds so push's final scatter drops them.
    # (Routing them to shard-local row 0 — a real row on shards > 0 — would
    # let stateful optimizers like adam apply a zero-grad momentum-decay
    # update to an untouched row.)
    local_row = jnp.where(empty, rps, flat_idx % rps).astype(jnp.int32)
    flat_pay = jnp.where(empty[:, None], 0.0, flat_pay)
    return push(table_shard, local_row, flat_pay[:, :cfg.grad_width],
                flat_pay[:, cfg.grad_width], flat_pay[:, cfg.grad_width + 1],
                cfg)


def routed_dropped(idx: jnp.ndarray, rows_per_shard: int, n_shards: int,
                   capacity_factor: float = 2.0) -> jnp.ndarray:
    """Number of tokens that exceed per-destination capacity (monitoring).

    Null/padding tokens are not routed (see _route) and do not count."""
    n = idx.shape[0]
    cap = _capacity(n, n_shards, capacity_factor)
    owner = jnp.where(idx == NULL_INDEX, n_shards, idx // rows_per_shard)
    counts = jnp.bincount(owner, length=n_shards)  # null group falls off
    return jnp.maximum(counts - cap, 0).sum()


def _capacity(n: int, n_shards: int, factor: float) -> int:
    return max(1, min(n, int(-(-n * factor // n_shards))))


# ---------------------------------------------------------------------------
# dedup (FLAGS_enable_pullpush_dedup_keys, flags.cc:603)
# ---------------------------------------------------------------------------

def dedup_tokens(idx: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fixed-capacity unique: returns (unique_idx, inverse) with the unused
    tail of unique_idx set to NULL_INDEX — the masked-capacity equivalent of
    the reference's DedupKeysAndFillIdx (box_wrapper_impl.h:103).

    lookup(table, unique_idx)[inverse] == lookup(table, idx).
    """
    n = idx.shape[0]
    order = jnp.argsort(idx)
    sidx = idx[order]
    is_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sidx[1:] != sidx[:-1]])
    seg = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    unique_idx = jnp.zeros((n,), idx.dtype).at[seg].max(sidx)
    inverse = jnp.zeros((n,), jnp.int32).at[order].set(seg)
    return unique_idx, inverse


def merge_sorted_runs(runs: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``dedup_tokens(runs.reshape(-1))`` — bit-identical outputs — for a
    (D, L) batch of row-wise ASCENDING runs, without the global argsort.

    The exchange receive side is exactly this shape: each source device
    premerged (ascending unique rows), routed through the stable-argsort
    plan (order preserved within a destination group), and capacity
    capping keeps an ascending prefix — so every received run is an
    ascending valid prefix padded with a constant out-of-range sentinel.

    The D-way merge computes each element's global sorted rank directly:
    its own within-run position plus, per other run, a searchsorted
    (side="right" for earlier runs, "left" for later ones — equal values
    count only from earlier runs). That tie-break IS the stable argsort's
    run-major-then-position order over the flattened array, so the
    sorted values, segment ids, unique vector, and inverse all match
    ``dedup_tokens`` exactly. D² binary searches of length-L runs
    replace one O(n log n) sort of n = D*L lanes; D is the static axis
    size, so the Python loop unrolls at trace time.
    """
    D, L = runs.shape
    n = D * L
    ranks = []
    for r in range(D):
        acc = jnp.arange(L, dtype=jnp.int32)
        for r2 in range(D):
            if r2 == r:
                continue
            side = "right" if r2 < r else "left"
            acc = acc + jnp.searchsorted(
                runs[r2], runs[r], side=side).astype(jnp.int32)
        ranks.append(acc)
    rank = jnp.stack(ranks).reshape(-1)
    flat = runs.reshape(-1)
    sorted_vals = jnp.zeros((n,), runs.dtype).at[rank].set(flat)
    is_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_vals[1:] != sorted_vals[:-1]])
    seg = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    unique_idx = jnp.zeros((n,), runs.dtype).at[seg].max(sorted_vals)
    inverse = seg[rank]
    return unique_idx, inverse
