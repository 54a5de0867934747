"""Sparsity-aware sparse exchange for the mesh-partitioned embedding table.

The reference shards its embedding table across devices inside libbox_ps
(the sharded HashTable behind ``PullSparseGPU``/``PushSparseGPU``) and
moves batches through hand-built all-to-all pull/push over NCCL
(box_wrapper_impl.h:44-103). This module is that exchange, grown from the
``sharded.routed_lookup``/``routed_push`` cores with the two ideas the
scale-out literature grounds (ROADMAP "Sharded embedding scale-out"):

- **Route only the deduped unique rows** (Parallax's sparsity-aware
  partitioning, arXiv:1808.02621): the host pack pipeline's dedup plan
  (``native.key_index.dedup_plan``) already orders tokens by row; the
  exchange premerges per-token push payloads onto one lane per unique row
  BEFORE the all_to_all (``sharded.plan_premerge``) and pulls each unique
  row once, re-expanding after the gather (``plan_dedup_indices`` — no
  device argsort: the plan's host permutation replaces it). A multi-hot
  CTR batch dedups ~2.5x, and the wire carries exactly that factor less.
- **Compress the push wire** (adaptive space-efficient sparse collectives,
  arXiv:2607.04676): the grad payload crosses ICI as bf16 or int8 with a
  per-lane scale (``flags.exchange_wire``); show/clk counter increments
  and the scale ride a small f32 side plane — the same split the
  quantized-table pull already uses for its a2a payload
  (``sharded.routed_lookup``). f32 keeps the wire exact (the parity
  baseline).

The fused gather-pool pull runs **per shard after routing**:
``routed_pull_pooled`` routes the unique rows, lands them in a local
(lanes, pull_width) table, and pools per (example, slot) from THAT
table with plain jnp (the Pallas ``gather_pool`` kernel needs a source
of whole 128-lane tiles; received lanes are pull_width wide). On a TPU
the trainer selects this route only where that kernel engages, which a
multi-shard mesh never does — it is the CPU-mesh form of the engine.

The push side mirrors it: when ``resolve_push_engine`` selects the
fused ``scatter_accumulate`` engine, ``routed_push``'s apply tail
merges the received lanes (unique per source device, at most one lane
per (source, row)) onto one lane per unique row with a compact
lane-grade scatter and updates exactly those shard rows in place — the
same kernel the single-shard premerged path runs, so the O(shard-table)
update pass disappears from the routed apply too.

Capacity overflow is never silent: every pull reports its exact dropped
count, the trainer feeds it to named counters/events
(``exchange.overflow_dropped`` / ``exchange_overflow``) and the
grow-retry policy (``Trainer._check_dropped`` — preplan sizing, adaptive
doubling, and the eval-pass in-place retry at the grown factor).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from paddlebox_tpu.config import flags as config_flags
from paddlebox_tpu.embedding import quant
from paddlebox_tpu.embedding.config import EmbeddingConfig
from paddlebox_tpu.embedding import sharded
from paddlebox_tpu.embedding.sharded import (_axis_size, _capacity,
                                             _normalize_plan, _route,
                                             _route_owner, dedup_tokens,
                                             merge_sorted_runs)

# push-payload wire formats (the pull's embedx plane already crosses
# quantized for quantized tables — sharded.routed_lookup)
WIRES = ("f32", "bf16", "int8")

# all_to_all decompositions for the push exchange
TOPOLOGIES = ("flat", "hier")


def select_wire(cfg: EmbeddingConfig) -> str:
    """Resolve flags.exchange_wire for this table (trace-time static;
    ``Trainer.engines()["exchange_wire"]`` reports it). "auto" =
    bf16 — the sparse grads reaching the wire already carry bf16-level
    rounding from the backward matmuls (the same argument as
    binned_push_splits=2), so the wire halves for free; int8 tables get
    int8 (their pull payload already crosses at that precision, and the
    push should not be the wider leg)."""
    w = config_flags.exchange_wire
    if w == "auto":
        return "int8" if cfg.storage == "int8" else "bf16"
    if w not in WIRES:
        raise ValueError(
            f"flags.exchange_wire={w!r} (want auto|f32|bf16|int8)")
    return w


def select_topology(axis_sizes) -> str:
    """Resolve flags.exchange_topology against the mesh shape
    (trace-time static, recorded in the flight-record extras).

    "hier" decomposes the push all_to_all into an intra-host shuffle
    over the trailing (dp) axis followed by a host-merged inter-host
    exchange over the leading (node) axis — it needs a real 2-axis
    mesh. "auto" picks hier exactly when such a (node, dp) shape
    exists (both axes > 1: a degenerate axis has nothing to merge
    across or nothing to exchange between), flat elsewhere."""
    t = config_flags.exchange_topology
    if t not in ("auto",) + TOPOLOGIES:
        raise ValueError(
            f"flags.exchange_topology={t!r} (want auto|flat|hier)")
    sizes = tuple(int(s) for s in axis_sizes)
    if t == "hier":
        if len(sizes) < 2:
            raise ValueError(
                "flags.exchange_topology='hier' needs a (node, dp) mesh; "
                f"got axis sizes {sizes}")
        return "hier"
    if t == "auto" and len(sizes) >= 2 and all(s > 1 for s in sizes):
        return "hier"
    return "flat"


# ---------------------------------------------------------------------------
# per-pass wire selection (the adaptive controller)
# ---------------------------------------------------------------------------

# Modeled precision-exposure surcharge per merged token contribution, in
# byte units per grad column: each duplicate of a row adds one ROUNDED
# contribution to the cross-device sum (bf16: 8-bit mantissa on each
# value; int8: 7-bit resolution of the per-lane max, worse when a lane's
# columns spread in magnitude). f32 is exact — the parity baseline.
_WIRE_EXPOSURE = {"f32": 0.0, "bf16": 0.25, "int8": 1.0}


def wire_cost(cfg: EmbeddingConfig, tokens: int, unique_lanes: int,
              wire: str) -> float:
    """Modeled per-pass cost of a push wire, in byte units: the real
    a2a bytes for the pass's unique lanes plus the precision-exposure
    surcharge scaled by the token count (the number of rounded
    contributions that merge). The dedup depth d = tokens/unique is the
    regime knob: duplication-heavy passes amortize the wide exact wire
    over many merged contributions (f32 wins past d ≈ 8), unique-heavy
    passes are bytes-bound (bf16, then int8 once the grad plane dwarfs
    the fixed index/side/scale columns)."""
    u = max(1, int(unique_lanes))
    t = max(int(tokens), u)
    if wire not in WIRES:
        raise ValueError(f"wire={wire!r} (want f32|bf16|int8)")
    base = float(push_wire_bytes(cfg, u, wire))
    return base + _WIRE_EXPOSURE[wire] * t * cfg.grad_width


class WireController:
    """Per-pass exchange_wire selection from the evidence the exchange
    already emits (flags.exchange_adaptive, ROADMAP "self-adapting
    exchange") — the collective-selection loop of the adaptive sparse
    collectives line (arXiv:2607.04676) run at pass grain, the way
    spill_cache_autotune adapts the cache budget.

    ``observe`` is called once per owned pass with the pass's OWN
    counter deltas (exchange.tokens / exchange.unique_lanes /
    overflow retries) and, when a world trace has been attributed,
    the clock-corrected flow-edge summary
    (``critical_path.attribute_flow_edges``). It returns a decision
    dict; the caller applies ``decision["wire"]`` to the NEXT pass
    (a switch recompiles the steps — same contract as the adaptive
    capacity doubling).

    Stability rules (the no-flap guarantee):
      - a challenger wire must win ``hysteresis`` CONSECUTIVE passes
        before the switch; a different challenger resets the streak;
      - overflow retries hold the wire (the capacity histogram is
        shifting — the evidence is stale);
      - a flow attribution that shows the exchange edge under
        ``min_share`` of the wall holds the wire (not the limiter:
        switching buys nothing and costs a recompile);
      - cost ties break toward the ACTIVE wire, then the wider one.

    The parity guard is structural, not a controller rule: show/clk
    counter increments (and the int8 scale) ride the f32 side plane on
    EVERY wire (``_compress_push``), so no decision can round a counter.
    """

    def __init__(self, cfg: EmbeddingConfig, wire: str,
                 hysteresis: int = 2, min_share: float = 0.02):
        self.cfg = cfg
        self.wire = wire
        self.hysteresis = max(1, int(hysteresis))
        self.min_share = float(min_share)
        self.switches = 0
        self._challenger = None
        self._streak = 0

    def _hold(self, reason: str, costs=None) -> dict:
        self._challenger, self._streak = None, 0
        return {"wire": self.wire, "prev_wire": self.wire,
                "switched": False, "candidate": None, "streak": 0,
                "costs": costs or {}, "reason": reason}

    def observe(self, tokens: int, unique_lanes: int,
                overflow_retries: int = 0, flow: dict | None = None,
                wall_seconds: float | None = None) -> dict:
        if int(tokens) <= 0:
            return self._hold("no-traffic")
        if int(overflow_retries) > 0:
            return self._hold("overflow-hold")
        if flow and wall_seconds and flow.get("edges", 0) > 0:
            ex = (flow.get("by_kind") or {}).get("exchange")
            share = (float(ex["max_latency_s"]) / float(wall_seconds)
                     if ex else 0.0)
            if share < self.min_share:
                return self._hold("not-limiter")
        costs = {w: wire_cost(self.cfg, tokens, unique_lanes, w)
                 for w in WIRES}
        # tie-break: active wire first, then wider (WIRES is widest-first)
        best = min(WIRES, key=lambda w: (costs[w], 0 if w == self.wire
                                         else 1, WIRES.index(w)))
        if best == self.wire:
            self._challenger, self._streak = None, 0
            return {"wire": self.wire, "prev_wire": self.wire,
                    "switched": False, "candidate": None, "streak": 0,
                    "costs": costs, "reason": "optimal"}
        if best == self._challenger:
            self._streak += 1
        else:
            self._challenger, self._streak = best, 1
        if self._streak >= self.hysteresis:
            prev, self.wire = self.wire, best
            self._challenger, self._streak = None, 0
            self.switches += 1
            return {"wire": best, "prev_wire": prev, "switched": True,
                    "candidate": best, "streak": self.hysteresis,
                    "costs": costs, "reason": "switched"}
        return {"wire": self.wire, "prev_wire": self.wire,
                "switched": False, "candidate": best,
                "streak": self._streak, "costs": costs,
                "reason": "challenger"}


def push_wire_bytes(cfg: EmbeddingConfig, lanes: int, wire: str) -> int:
    """Per-direction a2a bytes for `lanes` push lanes under `wire`
    (index plane + grad plane + f32 side plane) — the host-side
    accounting behind the ``exchange.push_bytes`` counter."""
    gw = cfg.grad_width
    gbytes = {"f32": 4 * gw, "bf16": 2 * gw, "int8": gw}[wire]
    side = 4 * (3 if wire == "int8" else 2)   # show, clk (+ scale)
    return lanes * (4 + gbytes + side)


def flow_fields(cfg: EmbeddingConfig, wire: str, tokens: int) -> dict:
    """Edge-label fields for a world-trace ``exchange`` flow point
    (monitor/trace.py): the wire format plus an UPPER BOUND on the bytes
    this step's all_to_all crosses (lanes <= tokens — the dedup plan can
    only shrink it; the exact per-pass totals are the ``exchange.*``
    counter deltas the flight record carries). Host-side arithmetic
    only — a flow point costs two multiplies, never a device readback."""
    return {"wire": str(wire), "tokens": int(tokens),
            "bytes_bound": pull_wire_bytes(cfg, int(tokens))
            + push_wire_bytes(cfg, int(tokens), wire)}


def pull_wire_bytes(cfg: EmbeddingConfig, lanes: int) -> int:
    """A2a bytes for `lanes` pull lanes: the index plane out plus the
    value payload back (quantized tables cross embedx at their storage
    width plus the fixed f32 head — the routed_lookup quant path)."""
    if cfg.storage != "f32":
        qbytes = 1 if cfg.storage == "int8" else 2
        return lanes * (4 + 4 * (cfg.fixed_cols + 1)
                        + qbytes * cfg.total_dim)
    return lanes * (4 + 4 * cfg.pull_width)


# ---------------------------------------------------------------------------
# plan-keyed dedup: the host counting sort replaces the device argsort
# ---------------------------------------------------------------------------

def plan_dedup_indices(dplan) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(uniq, inverse) from the host dedup plan — the device-argsort-free
    form of ``sharded.dedup_tokens`` (the sort already happened on the
    pack thread, ``native.key_index.dedup_plan``).

    uniq    : (n,) unique row ids, ascending, padded with ascending
              out-of-range ids (never routed — they fall into the null
              group like padding).
    inverse : (n,) unique lane per original token position, so
              ``pulled_lanes[inverse]`` re-expands a per-lane gather to
              per-token order. Sorted position i belongs to the segment
              whose ``segend`` is the first one past i — a vectorized
              searchsorted, no argsort.
    """
    order, _rstart, _endb, uniq, segend = dplan
    n = order.shape[0]
    seg_sorted = jnp.searchsorted(
        segend, jnp.arange(n, dtype=segend.dtype), side="right"
    ).astype(jnp.int32)
    seg_sorted = jnp.minimum(seg_sorted, n - 1)
    inverse = jnp.zeros((n,), jnp.int32).at[order].set(seg_sorted)
    return uniq, inverse


# ---------------------------------------------------------------------------
# pull
# ---------------------------------------------------------------------------

def routed_pull(table_shard, idx: jnp.ndarray, cfg: EmbeddingConfig,
                axis_name, capacity_factor: float = 2.0, plan=None,
                dedup: bool = False, return_dropped: bool = False):
    """Dedup-plan-keyed distributed gather: each unique row crosses the
    wire once; tokens re-expand from the returned lanes. Without a plan
    this degrades to ``sharded.routed_lookup`` (device dedup per the
    `dedup` flag — the eval path, which packs no plan)."""
    D = _axis_size(axis_name)
    if D == 1:
        out = sharded.lookup(table_shard, idx, cfg)
        return (out, jnp.zeros((), jnp.int32)) if return_dropped else out
    _, dplan = _normalize_plan(plan)
    if dplan is None:
        return sharded.routed_lookup(table_shard, idx, cfg, axis_name,
                                     capacity_factor, dedup=dedup,
                                     return_dropped=return_dropped)
    uniq, inverse = plan_dedup_indices(dplan)
    res = sharded.routed_lookup(table_shard, uniq, cfg, axis_name,
                                capacity_factor,
                                return_dropped=return_dropped)
    if return_dropped:
        return res[0][inverse], res[1]
    return res[inverse]


def routed_pull_pooled(table_shard, idx: jnp.ndarray, cfg: EmbeddingConfig,
                       axis_name, num_slots: int, slot_len: int,
                       capacity_factor: float = 2.0, plan=None,
                       return_dropped: bool = False):
    """(B, S*L) indices → (B, S, pull_width): the fused gather-pool pull
    on the sharded mesh. The unique rows route once (plan-keyed when a
    plan rides the batch, device dedup otherwise), land in a local
    (lanes, pull_width) table, and the per-(example, slot) pool gathers
    FROM THAT local table. Masked tokens point at the null row's lane,
    whose routed value is the zero row, so padding contributes zeros
    exactly like the single-shard fused path."""
    B = idx.shape[0]
    flat = idx.reshape(-1)
    D = _axis_size(axis_name)
    if D == 1:
        out = sharded.fused_pull_pool(table_shard, idx, cfg, num_slots,
                                      slot_len)
        return (out, jnp.zeros((), jnp.int32)) if return_dropped else out
    _, dplan = _normalize_plan(plan)
    if dplan is not None:
        uniq, inverse = plan_dedup_indices(dplan)
    else:
        uniq, inverse = dedup_tokens(flat)
    rows, dropped = sharded.routed_lookup(table_shard, uniq, cfg,
                                          axis_name, capacity_factor,
                                          return_dropped=True)
    # pool per (example, slot) from the received-lane table — plain jnp:
    # the lanes are pull_width columns wide, never the whole 128-lane
    # tiles the Pallas gather_pool kernel's row DMAs need
    pooled = jnp.take(rows, inverse, axis=0).reshape(
        B, num_slots, slot_len, rows.shape[1]).sum(axis=2)
    return (pooled, dropped) if return_dropped else pooled


# ---------------------------------------------------------------------------
# push (wire-compressed)
# ---------------------------------------------------------------------------

def _compress_push(send_pay: jnp.ndarray, gw: int, wire: str) -> tuple:
    """(D, cap, gw+2) f32 payload → wire planes. Grad columns compress;
    show/clk increments (exact small counts) and the int8 scale stay in
    an f32 side plane — counters must never round."""
    if wire == "f32":
        return (send_pay,)
    g, side = send_pay[..., :gw], send_pay[..., gw:]
    if wire == "bf16":
        return (g.astype(jnp.bfloat16), side)
    q, scale = quant.quantize_lanes(g, "int8")
    return (q, jnp.concatenate([side, scale[..., None]], axis=-1))


def _decompress_push(planes: tuple, wire: str) -> jnp.ndarray:
    if wire == "f32":
        return planes[0]
    g, side = planes
    if wire == "bf16":
        return jnp.concatenate([g.astype(jnp.float32), side], axis=-1)
    x = quant.dequantize_lanes(g, side[..., -1])
    return jnp.concatenate([x, side[..., :-1]], axis=-1)


def _scatter_engine(table_shard, cfg: EmbeddingConfig, rps: int) -> bool:
    from paddlebox_tpu.ops import pallas_kernels
    return pallas_kernels.resolve_push_engine(
        cfg, rps, premerged=True,
        storage_f32=not quant.is_quant(table_shard),
        table_width=quant.row_engine_width(table_shard)) \
        == "scatter_accumulate"


def _apply_received(table_shard, local_row, flat_pay, touched,
                    cfg: EmbeddingConfig, rps: int, runs: int):
    """The exchange apply tail on the owner shard: `runs` row-wise
    ascending received runs of local rows (``local_row`` flattened,
    out-of-range ``rps`` on empty lanes) with (gw+2) payloads and a
    per-lane real-contribution count `touched`.

    When the fused row-wise engine is selected, the cross-device merge
    onto one lane per unique row is a D-way MERGE of the received runs
    (``sharded.merge_sorted_runs``) — each source premerged ascending,
    the routing argsort is stable, and capacity capping keeps ascending
    prefixes, so no global sort is needed; the result is bit-identical
    to the ``dedup_tokens`` argsort it replaces. Empty lanes merge onto
    the out-of-range rps lane and merge pads carry a zero touch count,
    so neither ever writes."""
    gw = cfg.grad_width
    if _scatter_engine(table_shard, cfg, rps):
        from paddlebox_tpu.ops import pallas_kernels
        if runs > 0:
            uniq, inverse = merge_sorted_runs(
                local_row.reshape(runs, -1))
        else:
            uniq, inverse = dedup_tokens(local_row)
        payload = jnp.concatenate([flat_pay, touched[:, None]], axis=1)
        merged = jnp.zeros((local_row.shape[0], gw + 3),
                           payload.dtype).at[inverse].add(payload)
        return pallas_kernels.scatter_accumulate(
            table_shard, uniq, merged[:, :gw], merged[:, gw],
            merged[:, gw + 1], cfg, touched=merged[:, gw + 2])
    return sharded.push(table_shard, local_row, flat_pay[:, :gw],
                        flat_pay[:, gw], flat_pay[:, gw + 1], cfg)


def routed_push(table_shard, idx: jnp.ndarray, grads: jnp.ndarray,
                shows: jnp.ndarray, clks: jnp.ndarray,
                cfg: EmbeddingConfig, axis_name,
                capacity_factor: float = 2.0, wire: str = "f32",
                plan=None, premerged: bool = False,
                topology: str = "flat"):
    """Distributed merge-update with a premerged, wire-compressed
    payload (the exchange's push half; reverse of ``routed_pull``).

    When `plan` carries the host dedup bounds (or `premerged` lanes
    arrive from a deferred apply — the plan's unique order, ascending),
    per-token payloads merge onto one lane per unique row BEFORE
    routing — each row crosses the wire once per source device. The
    grad plane crosses in `wire` format; the owner shard then merges
    cross-device lanes and applies the optimizer exactly as the
    single-shard engine does.

    `topology` "flat" is the one-stage global all_to_all; "hier"
    (``select_topology``) runs the two-stage intra-host/inter-host
    decomposition — axis_name must then be the (node, dp) axis pair."""
    D = _axis_size(axis_name)
    if D == 1:
        return sharded.push(table_shard, idx, grads, shows, clks, cfg,
                            plan=plan, premerged=premerged)
    merged_input = premerged
    if not premerged:
        _, dplan = _normalize_plan(plan)
        if dplan is not None:
            idx, grads, shows, clks, _ = sharded.plan_premerge(
                idx, grads, shows, clks, dplan)
            merged_input = True
    if topology == "hier":
        return _routed_push_hier(table_shard, idx, grads, shows, clks,
                                 cfg, axis_name, capacity_factor, wire,
                                 merged=merged_input)
    n = idx.shape[0]
    rps = quant.table_rows(table_shard)
    cap = _capacity(n, D, capacity_factor)
    order, sowner, pos, valid, send_idx = _route(idx, rps, D, cap)
    gw = cfg.grad_width
    payload = jnp.concatenate(
        [grads, shows[:, None], clks[:, None]], axis=1)[order]
    send_pay = jnp.zeros((D, cap, gw + 2), payload.dtype)
    send_pay = send_pay.at[sowner, pos].set(payload, mode="drop")
    recv_idx = lax.all_to_all(send_idx, axis_name, 0, 0, tiled=True)
    recv = tuple(lax.all_to_all(p, axis_name, 0, 0, tiled=True)
                 for p in _compress_push(send_pay, gw, wire))
    recv_pay = _decompress_push(recv, wire)
    flat_idx = recv_idx.reshape(-1)
    flat_pay = recv_pay.reshape(-1, gw + 2)
    empty = flat_idx < 0
    # empty lanes go out-of-bounds so push's scatter drops them (see
    # sharded.routed_push on why row 0 would be wrong for adam)
    local_row = jnp.where(empty, rps, flat_idx % rps).astype(jnp.int32)
    flat_pay = jnp.where(empty[:, None], 0.0, flat_pay)
    # ascending-runs invariant for the D-way merge: it needs a MERGED
    # source order (the plan's unique rows ascend; token-order input
    # does not), so unmerged input keeps the argsort dedup
    return _apply_received(table_shard, local_row, flat_pay,
                           (~empty).astype(flat_pay.dtype), cfg, rps,
                           runs=D if merged_input else 0)


def _routed_push_hier(table_shard, idx: jnp.ndarray, grads: jnp.ndarray,
                      shows: jnp.ndarray, clks: jnp.ndarray,
                      cfg: EmbeddingConfig, axis_name,
                      capacity_factor: float, wire: str,
                      merged: bool):
    """Two-stage push exchange on a (node, dp) mesh (the array-
    redistribution decomposition, arXiv:2112.01075, applied to the
    sparse push):

    1. **intra-host shuffle** over the dp axis, f32 uncompressed (the
       in-host leg is not the scarce bandwidth): tokens route to the
       host-local device whose dp slot owns their column of the shard
       grid, so every lane bound for host h sits on the one local
       device that will talk to h's matching dp slot.
    2. **host merge**: the P received runs (ascending — premerged
       sources through the stable routing argsort) D-way-merge onto
       one lane per unique global row, summing payloads and real
       counts. This is the whole point: a row referenced by all P
       local devices crosses the inter-host wire ONCE.
    3. **inter-host exchange** over the node axis, wire-compressed
       (``_compress_push`` — the merged touch counts ride the f32 side
       plane with show/clk, so counters stay exact on every wire).

    Capacities are sized so hier never drops a batch flat would not:
    stage 1's per-slot lanes hold H flat-capacity groups; stage 2's
    per-host lanes hold P. Under exact arithmetic (f32 wire) the final
    per-row sums are the same contributions in the same merged order as
    the flat exchange — bit-identical, which the hier-vs-flat parity
    test pins."""
    if not isinstance(axis_name, (tuple, list)) or len(axis_name) != 2:
        raise ValueError(
            "exchange_topology='hier' needs the (node, dp) axis pair; "
            f"got axis_name={axis_name!r}")
    node_ax, dp_ax = axis_name
    H = lax.axis_size(node_ax)
    P = lax.axis_size(dp_ax)
    D = H * P
    gw = cfg.grad_width
    rps = quant.table_rows(table_shard)
    if not merged:
        # host plan absent (e.g. a planless caller): device-merge first
        # so the stage-1 runs ascend and each row leaves a device once
        uniq0, inv0 = dedup_tokens(idx)
        payload = jnp.concatenate(
            [grads, shows[:, None], clks[:, None]], axis=1)
        m0 = jnp.zeros((uniq0.shape[0], gw + 2),
                       payload.dtype).at[inv0].add(payload)
        idx, grads, shows, clks = (uniq0, m0[:, :gw], m0[:, gw],
                                   m0[:, gw + 1])
    n = idx.shape[0]
    flat_cap = _capacity(n, D, capacity_factor)
    # --- stage 1: route by the owner shard's dp slot, intra-host a2a.
    # NULL tokens and the plan's out-of-range pads (>= the table's
    # rps*D rows) go to the drop group — the slot modulus would
    # otherwise wrap pads into real groups and crowd out tokens
    cap1 = min(n, H * flat_cap)
    owner1 = jnp.where((idx == sharded.NULL_INDEX) | (idx >= rps * D),
                       P, (idx // rps) % P)
    order1, sown1, pos1, valid1, send_idx1 = _route_owner(
        idx, owner1, P, cap1)
    payload = jnp.concatenate(
        [grads, shows[:, None], clks[:, None]], axis=1)[order1]
    send_pay1 = jnp.zeros((P, cap1, gw + 2), payload.dtype)
    send_pay1 = send_pay1.at[sown1, pos1].set(payload, mode="drop")
    recv_idx1 = lax.all_to_all(send_idx1, dp_ax, 0, 0, tiled=True)
    recv_pay1 = lax.all_to_all(send_pay1, dp_ax, 0, 0, tiled=True)
    # --- host merge: P ascending runs of global rows → unique lanes
    flat1 = recv_idx1.reshape(-1)
    empty1 = flat1 < 0
    sentinel = rps * D                        # > every valid global row
    midx = jnp.where(empty1, sentinel, flat1)
    uniq1, inverse1 = merge_sorted_runs(midx.reshape(P, cap1))
    real1 = (~empty1).astype(recv_pay1.dtype)
    pay1 = jnp.where(empty1[:, None], 0.0,
                     recv_pay1.reshape(-1, gw + 2))
    merged1 = jnp.zeros((uniq1.shape[0], gw + 3),
                        pay1.dtype).at[inverse1].add(
        jnp.concatenate([pay1, real1[:, None]], axis=1))
    # --- stage 2: route merged uniques by owner host, inter-host a2a.
    # The sentinel lane and the merge's tail pads carry a zero touch
    # count — both go to the drop group (a padded row 0 would otherwise
    # reach shard 0 and let a stateful optimizer decay an untouched row)
    drop2 = merged1[:, gw + 2] <= 0.0
    owner2 = jnp.where(drop2, H, uniq1 // (rps * P))
    cap2 = P * flat_cap
    order2, sown2, pos2, valid2, send_idx2 = _route_owner(
        uniq1, owner2, H, cap2)
    send_pay2 = jnp.zeros((H, cap2, gw + 3), merged1.dtype)
    send_pay2 = send_pay2.at[sown2, pos2].set(merged1[order2],
                                              mode="drop")
    recv_idx2 = lax.all_to_all(send_idx2, node_ax, 0, 0, tiled=True)
    recv2 = tuple(lax.all_to_all(p, node_ax, 0, 0, tiled=True)
                  for p in _compress_push(send_pay2, gw, wire))
    recv_pay2 = _decompress_push(recv2, wire)
    # --- apply: every arriving row belongs to THIS shard; H ascending
    # runs of local rows merge through the same D-way-merge tail
    flat2 = recv_idx2.reshape(-1)
    empty2 = flat2 < 0
    local_row = jnp.where(empty2, rps, flat2 % rps).astype(jnp.int32)
    pay2 = jnp.where(empty2[:, None], 0.0,
                     recv_pay2.reshape(-1, gw + 3))
    return _apply_received(table_shard, local_row, pay2[:, :gw + 2],
                           pay2[:, gw + 2], cfg, rps, runs=H)
