"""Pallas TPU kernels for the embedding-table hot path.

Four kernel families live here:

- ``gather_pool`` (the fused pull for multi-hot/wide layouts): gathers
  rows from the HBM device table and sum-pools them per (example, slot)
  segment in VMEM, so the (tokens, pull_width) pulled matrix never
  materializes — the pull-side dual of ``binned_push`` (see its section
  comment). Tables of whole 128-lane tiles only.

- ``scatter_accumulate`` (the fused push for premerged unique lanes):
  the mirror image of ``gather_pool`` — DMA-gathers exactly the table
  rows the premerged cotangent lanes touch, applies the optimizer
  row-wise in VMEM, and DMA-writes each row back once. Neither the
  (tokens, pull_width) cotangent matrix nor the (n_rows, grad_width+3)
  full-table accumulator ever materializes, and the O(table) update
  pass of the scatter/binned engines disappears (see its section
  comment). Engine selection across the three push engines is owned by
  ``resolve_push_engine`` — ONE resolver shared by the compiled
  dispatch and ``Trainer.engines()``. Tables of whole 128-lane tiles
  only.

- ``binned_push`` (the production path, flags.binned_push): replaces the
  XLA token scatter-add with block-binned one-hot MXU matmuls that build
  a per-row merge accumulator; the optimizer then applies as ONE fused
  XLA pass over the table — see the section comment.
- ``merge_update``: fuses only the table-update scan after the merge has
  built the accumulator. sharded.push selects it on a TPU for
  accumulators of 64 lanes and more; at narrow widths it is off unless
  ``PBTPU_PALLAS=1`` (use_pallas). It reuses
  ``embedding.optim.apply_updates`` verbatim inside the kernel body, so
  numerics are bit-identical to the XLA path and every optimizer
  (sgd/adagrad/adam/ftrl) works unchanged. Narrow rows pad to 128 lanes
  in VMEM, so block_rows stays modest.

Speeds quoted in this file's comments were taken on the first rounds'
chip and tree; none is a measurement of this code (PERF.md).

On CPU the kernel runs in interpret mode — the pure-Python Pallas
interpreter — which is how the tests exercise it without TPU hardware
(SURVEY.md §4: everything must be testable hardware-free).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddlebox_tpu.embedding import quant
from paddlebox_tpu.embedding.config import EmbeddingConfig
from paddlebox_tpu.embedding.optim import apply_updates


def use_pallas() -> bool:
    """Default OFF: PBTPU_PALLAS=1 routes the narrow-accumulator table
    update through merge_update for experiments. On the first rounds'
    chip the XLA scatter+select pass measured faster than this kernel
    at narrow widths, and the kernel's row-major operand layout costs
    XLA a padded O(table) copy of a narrow table around the call; both
    statements predate the current tree (ROADMAP D2 re-measures or
    deletes).

    Read at TRACE time: set it before the first train step compiles.
    Flipping it later does nothing — jitted steps (donated, fed back) never
    retrace, so the already-compiled path keeps running."""
    return os.environ.get("PBTPU_PALLAS") == "1"


def _merge_update_kernel(table_ref, acc_ref, out_ref, *, cfg: EmbeddingConfig):
    rows = table_ref[...]
    acc = acc_ref[...]
    gw = cfg.grad_width
    new_rows = apply_updates(rows, acc[:, :gw], acc[:, gw], acc[:, gw + 1],
                             cfg)
    touched = acc[:, gw + 2] > 0
    out_ref[...] = jnp.where(touched[:, None], new_rows, rows)


@functools.partial(jax.jit, static_argnames=("cfg", "block_rows", "interpret"))
def merge_update(table: jnp.ndarray, acc: jnp.ndarray, cfg: EmbeddingConfig,
                 block_rows: int = 512,
                 interpret: bool | None = None) -> jnp.ndarray:
    """One fused pass of the per-step table update.

    table : (N, row_width) f32
    acc   : (N, grad_width + 3) f32 — summed [grads, show, clk, touch_count]
            per row (the output of the scatter-add merge)
    Returns the updated table; identical to the jnp path in sharded.push.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, w = table.shape
    a = acc.shape[1]
    grid = (pl.cdiv(n, block_rows),)
    # inside shard_map the output varies over the same mesh axes as the
    # table shard (new-style shard_map vma checking)
    vma = getattr(jax.typeof(table), "vma", frozenset())
    if interpret and vma:
        # The Pallas interpreter evaluates the kernel jaxpr with
        # vma-carrying block values, and EVERY op mixing a literal
        # (x * 2.0, x > 0, ...) trips shard_map's vma check — interpret
        # mode fundamentally cannot run nontrivial kernels inside a
        # check_vma shard_map (JAX 0.9.0). Use the identical jnp math on
        # CPU test meshes; Mosaic lowering on real TPU is a custom call
        # and does not hit this.
        gw = cfg.grad_width
        new_rows = apply_updates(table, acc[:, :gw], acc[:, gw],
                                 acc[:, gw + 1], cfg)
        return jnp.where((acc[:, gw + 2] > 0)[:, None], new_rows, table)
    return pl.pallas_call(
        functools.partial(_merge_update_kernel, cfg=cfg),
        out_shape=jax.ShapeDtypeStruct((n, w), table.dtype, vma=vma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, w), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, a), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, w), lambda i: (i, 0)),
        interpret=interpret,
        name="pbtpu_merge_update",
    )(table, acc)


# ---------------------------------------------------------------------------
# Binned push: the scatter-free merge-update.
#
# XLA's scatter is random-access latency-bound INSIDE the fused step
# (in-step A/B on one v5e, 213k tokens: the scatter step runs 15.5ms vs
# 7.7ms with this kernel at dim 8 — isolated scatter microbenchmarks
# read 100x faster and are a trap; only in-step A/B is decision-grade).
# This kernel replaces it with MXU matmuls: tokens are sorted by row
# id (one argsort), bucketed to contiguous table "super-blocks", and each
# super-block's accumulator is built as one-hot(local_row) @ payload — a
# streaming matmul instead of random-access writes. The optimizer then
# applies OUTSIDE the kernel as one fused full-width XLA pass (the merge +
# update halves of PushMergeCopy, box_wrapper.cu:630-830; see
# _binned_acc_kernel's docstring for why the split wins on TPU).
#
# Exactness: the payload crosses the MXU as an n_split-plane bf16 mantissa
# split computed IN-KERNEL (hi/mid/lo by integer masking, so
# --xla_allow_excess_precision cannot elide the rounding); one-hot entries
# are exact in bf16 and accumulation is f32, so n_split=3 matches the f32
# scatter to ~1e-7 relative (measured 1.6e-7 over a 213k-token batch;
# summation ORDER differs from XLA's scatter, so bitwise equality is not
# expected). n_split=1 rounds grads to bf16 (2x fewer dots).
#
# Packed operand: [payload_f32 (PP lanes) | id_hi | id_lo], PP = payload
# padded to a multiple of 8. Because the mantissa split happens in VMEM,
# the operand width is independent of n_split — ~5x less HBM/DMA traffic
# than the old pre-split 128-lane layout for narrow CTR payloads, and NO
# upper width limit: wide rows (dim 64..280+, the reference's full embedx
# envelope, box_wrapper.cc:444-461) run the same kernel with a >128-lane
# accumulator that Mosaic tiles across lane registers.
#
# Lane packing (narrow rows): G = pow2(128 // PP) row-groups share one
# dot's 128 output lanes (each token's payload is routed into its group's
# lane block), so narrow CTR payloads do not waste ~10x MXU throughput on
# lane padding. Wide rows (PP > 64) take G = 1 and the dot's output lanes
# are the payload itself.
#
# Measured (one v5e, 528k x 13 f32 table, 213k tokens, adagrad, forced-D2H
# repeat-in-one-jit windows): XLA scatter+update ~16.6 ms/call; round-2
# kernel (in-VMEM optimizer) 5.2 ms; round-3 pre-split acc-only 3.6 ms.
# This in-kernel-split layout has no reading of its own: no cell of
# BENCHMARK.json resolves to this engine (ROADMAP D2).
# ---------------------------------------------------------------------------

_BP_TILE = 1024          # tokens per DMA/matmul tile
_BP_MAX_PP = 512         # accumulator lane cap (dim 280 -> PP 288)


def _bp_lanes(cfg: EmbeddingConfig, rows: int):
    """Shared lane geometry: (P, PP, G, target_SB) or None past the
    width cap. The single source of truth for both the kernel geometry
    and the working-set row alignment — they MUST agree or shard row
    counts desynchronize from the kernel's actual block choice.

    G = largest power of two <= 128 // PP: lane routing only needs
    G * PP <= 128, and a non-pow2 G (PP=24 -> 128//24=5) would fail the
    SB % G divisibility and silently lose the kernel for those widths.
    PP > 64 -> G=1: the dot's output lanes are the payload itself
    (Mosaic tiles >128-lane accumulators across lane registers).

    target_SB trades one-hot dot FLOPs against grid overhead: each
    token's one-hot row is RB = SB/G wide (work ~ tokens * RB * PP per
    plane) while each block costs a fixed ~20us of DMA/prologue (cost ~
    n_rows/SB) — so SB* ~ sqrt(c * n_rows * 128/PP), c fitted on v5e
    (~3; for PP <= 64 the 128/PP ratio equals G up to pow2 rounding, so
    this reduces to the round-3 sqrt(3*G*n_rows)). A 10.5M-row table at
    SB=4096 is 2560 mostly-empty grid steps (measured +2.6ms); a
    557k-row table at SB=16384 wastes 4x MXU work (measured +1.4ms)."""
    P = cfg.grad_width + 3
    PP = -(-P // 8) * 8
    if PP > _BP_MAX_PP:
        return None
    G = max(1, 1 << ((128 // PP).bit_length() - 1)) if PP <= 128 else 1
    target = int((3.0 * max(1, rows) * 128.0 / PP) ** 0.5)
    return P, PP, G, target


def _bp_geometry(cfg: EmbeddingConfig, n_rows: int):
    """(payload P, padded PP, groups G, super-block SB) or None if the
    table doesn't fit the kernel's divisibility/width needs."""
    lanes = _bp_lanes(cfg, n_rows)
    if lanes is None:
        return None
    P, PP, G, target = lanes
    # nearest dividing block to target_SB. RB = SB/G is capped at 2048:
    # the (TILE, RB) one-hot operand blew v5e's 16MB scoped-vmem limit
    # at RB=4096 (the tile also halves past RB 1024 — _bp_tile).
    best = None
    SB = min(2048 * G, 1 << 16)
    while SB >= 512:
        if n_rows % SB == 0 and SB % G == 0:
            if best is None or abs(SB - target) < abs(best - target):
                best = SB
        SB //= 2
    if best is None:
        return None
    return P, PP, G, best


def bp_row_alignment(cfg: EmbeddingConfig, rows: int) -> int:
    """Row-count alignment that lets `_bp_geometry` pick its TARGET
    super-block for a table of ~`rows` rows: the power of two nearest
    target_SB, clamped to [4096, RB-cap]. Working-set builders align
    shard row counts to this — big tables get big-block divisibility,
    small tables keep the cheap 4096 alignment."""
    lanes = _bp_lanes(cfg, rows)
    if lanes is None:
        return 4096
    _, _, G, target = lanes
    pow2 = 1 << max(0, target.bit_length() - 1)
    if target - pow2 > 2 * pow2 - target:       # round to nearest pow2
        pow2 <<= 1
    return max(4096, min(pow2, 2048 * G, 1 << 16))


def _bp_tile(SB: int, G: int) -> int:
    """Tokens per DMA/matmul tile: halved for big blocks so the
    (TILE, RB) one-hot operand stays ~2MB."""
    return _BP_TILE if SB // G <= 1024 else _BP_TILE // 2


def _bp_acc_width(G: int, PP: int) -> int:
    """Accumulator lane count: G*PP for narrow rows; padded to a full
    128-lane tile past one tile (Mosaic rejects multi-tile shapes with
    odd tails, and a 136-lane dot already costs two 128-lane MXU blocks,
    so the padding is free)."""
    gp = G * PP
    return gp if gp <= 128 else -(-gp // 128) * 128


def _binned_acc_kernel(rstart_ref, end_ref, packed_ref, acc_ref,
                       pack_s, sem, *, PP: int, G: int, SB: int,
                       n_split: int, TILE: int):
    """Per-block merge accumulator via one-hot MXU matmuls.

    Writes this block's accumulator in GROUPED layout (RB, G*PP) — row
    ``local % RB``, lane block ``(local // RB) * PP`` — which the caller
    untangles with a reshape/transpose that XLA fuses into the table
    update. The optimizer deliberately does NOT run in here: a
    (block, group)-tiled elementwise chain wastes ~90% of each VPU lane
    on narrow CTR rows, while the same update as ONE fused XLA pass over
    the whole table runs at full width (measured on one v5e, 528k x 13
    adagrad: in-kernel update ~3.5ms of the old 5.2ms kernel vs 0.5ms as
    a fused XLA pass over the grouped acc).

    The bf16 mantissa planes are built HERE from the f32 payload (cheap
    VPU integer masking on the tile) rather than pre-split host/XLA-side:
    the packed operand carries each payload value once, so DMA traffic is
    ~(PP+2)/128 of the old pre-split layout and the payload-prep XLA
    chain disappears from the step."""
    RB = SB // G
    b = pl.program_id(0)
    start = rstart_ref[b]
    endv = end_ref[b]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    n_t = lax.div(endv - start + TILE - 1, TILE)

    def _copy(t):
        slot = lax.rem(t, 2)
        # rstart entries are //8*8-aligned by construction (plan builder
        # and device fallback both); Mosaic needs the hint to prove the
        # row slice respects (8,128) memref tiling for W > 128 operands
        row0 = pl.multiple_of(start + t * TILE, 8)
        return pltpu.make_async_copy(
            packed_ref.at[pl.ds(row0, TILE), :],
            pack_s.at[slot], sem.at[slot])

    # double-buffered DMA: tile t+1 streams in while tile t computes
    @pl.when(n_t > 0)
    def _prefetch_first():
        _copy(0).start()

    def body(t, _):
        @pl.when((t + 1) < n_t)
        def _prefetch_next():
            _copy(t + 1).start()

        _copy(t).wait()
        packed = pack_s[lax.rem(t, 2)]
        off = start + t * TILE
        # row id rides the two lanes PAST the payload as two exact
        # integer-valued floats (hi*4096+lo): f32 BIT patterns of small
        # ints are denormals and XLA flushes them, so a bitcast column
        # would read back as zeros
        tok = (packed[:, PP:PP + 1].astype(jnp.int32) * 4096
               + packed[:, PP + 1:PP + 2].astype(jnp.int32))
        pos = lax.broadcasted_iota(jnp.int32, (TILE, 1), 0) + off
        local = tok - b * SB
        valid = (pos < endv) & (local >= 0) & (local < SB)
        grp = jnp.where(valid, local // RB, G)
        within = jnp.where(valid, local % RB, RB)
        oh = (within == lax.broadcasted_iota(
            jnp.int32, (TILE, RB), 1)).astype(jnp.bfloat16)
        AW = _bp_acc_width(G, PP)
        lane_grp = lax.broadcasted_iota(jnp.int32, (TILE, AW), 1) // PP
        # in-kernel mantissa split: plane s holds the top 16 bits of the
        # running residual (exact in bf16); the LAST plane is the raw
        # residual, which after two maskings has <= 8 significant bits
        # (exact) and for n_split=1 is the full payload (bf16-rounded).
        # Wide rows (G=1, AW > PP) split the packed tile whole — the id /
        # padding lanes past PP are split along for the ride; their acc
        # lanes are never read by the caller's [:, :P] slice.
        rem = packed[:, 0:PP] if G > 1 else packed[:, 0:AW]
        for s in range(n_split):
            if s == n_split - 1:
                plane = rem
            else:
                plane = lax.bitcast_convert_type(
                    lax.bitcast_convert_type(rem, jnp.int32)
                    & jnp.int32(-65536), jnp.float32)
                rem = rem - plane
            wide = jnp.tile(plane, (1, G)) if G > 1 else plane
            routed = jnp.where(lane_grp == grp, wide, 0.0)
            acc_ref[...] += lax.dot_general(
                oh, routed.astype(jnp.bfloat16), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return 0

    lax.fori_loop(0, n_t, body, 0)


# ---------------------------------------------------------------------------
# _bp_pack width-class engines.
#
# The pack's one expensive op is the token reorder (``[order]`` row
# gather), and the v5e row-gather sweep is sharply non-monotone in source
# width: <=13-lane sources gather at ~5-10ns/row (fast narrow path),
# 14..63-lane sources fall off a cliff (3-8x slower per row — 23.2ms at
# 40 lanes vs 3.6ms at 128 over 852k tokens), and >=64-lane sources are
# back on the fast path. One pack layout therefore cannot serve every
# payload width: the round-5 _bp_pack rewrite moved the dim-8 headline's
# 12-lane payload onto the pad-first layout and silently halved headline
# throughput (VERDICT r5 — reverting that one function restored 1.87x).
# The engines below make the choice EXPLICIT, per width class, overridable
# for in-composed-step A/Bs (flags.pack_engine) and named by
# pack_engine(), so a wrong choice can be read off instead of shipping:
#
#   narrow      (P < 14)       reorder at the logical payload width, pad
#                              after — the fast-narrow-gather path.
#   gather_zone (14 <= P < 64) pad to 64 lanes BEFORE the reorder (the
#                              smallest fast-path width), zero-extend to
#                              the DMA width after — half the gather
#                              bytes of the 128-lane layout.
#   wide        (P >= 64)      pack at the full 128-lane-tile DMA width
#                              first, one wide gather.
# ---------------------------------------------------------------------------

PACK_ENGINES = ("narrow", "gather_zone", "wide")


def pack_width_class(P: int) -> str:
    """Width class of a P-lane push payload (the v5e gather-sweep zones;
    the 14-lane zone start matches device_width's pad rule)."""
    if P < 14:
        return "narrow"
    if P < 64:
        return "gather_zone"
    return "wide"


def _resolve_pack_engine(P: int, premerged: bool) -> str:
    """THE pack-engine resolver — both the compiled path (_bp_pack) and
    what a caller reports (pack_engine) call this one function, so a
    report can never name a code path the program does not contain
    (the round-5 unattributable-regression failure mode). Raises on a
    typo'd forced engine: the flag exists for trustworthy A/Bs."""
    if premerged:
        # premerged lanes arrive sorted (order=None): no reorder
        # compiles regardless of width class or override
        return "premerged_no_reorder"
    from paddlebox_tpu.config import flags as config_flags
    eng = config_flags.pack_engine
    if eng in PACK_ENGINES:
        return eng
    if eng != "auto":
        raise ValueError(f"pack_engine={eng!r} (want 'auto' or one of "
                         f"{PACK_ENGINES})")
    return pack_width_class(P)


def pack_engine(cfg: EmbeddingConfig, n_rows: int,
                premerged: bool = False) -> str | None:
    """Which _bp_pack code path the binned push compiles with for this
    (cfg, rows) — "narrow" | "gather_zone" | "wide", or None when the
    binned kernel does not engage (scatter-engine dispatch has no pack).
    flags.pack_engine overrides for A/B runs.

    premerged: the dedup premerge feeds the pack already-sorted lanes
    (order=None), so NO reorder compiles regardless of width class —
    reported as "premerged_no_reorder" so the answer names the code
    path the program actually contains, not the one the width alone
    would pick."""
    if binned_push_geometry(cfg, n_rows) is None:
        return None
    return _resolve_pack_engine(cfg.grad_width + 3, premerged)


def _pack_narrow(grads, shows, clks, hi, lo, order, tok, P, PP, W):
    # reorder at the logical payload width (fast <14-lane gathers), pad
    # to the DMA width after — one extra elementwise pass over the
    # already-sorted payload
    payload = jnp.concatenate(
        [grads, shows[:, None], clks[:, None],
         jnp.ones((tok, 1), jnp.float32)], axis=1)
    s_pay = jnp.take(payload, order, axis=0)
    return jnp.concatenate(
        [s_pay, jnp.zeros((tok, PP - P), jnp.float32),
         jnp.take(hi, order)[:, None], jnp.take(lo, order)[:, None],
         jnp.zeros((tok, W - PP - 2), jnp.float32)], axis=1)


def _pack_gather_zone(grads, shows, clks, hi, lo, order, tok, P, PP, W):
    # 14..63-lane gathers are the pathological zone — pad to 64 lanes
    # (the smallest fast-path source width) BEFORE the reorder, then
    # zero-extend to the DMA width; the gather moves half the bytes of
    # the 128-lane-first layout
    G64 = 64 if PP + 2 <= 64 else W
    pay64 = jnp.concatenate(
        [grads, shows[:, None], clks[:, None],
         jnp.ones((tok, 1), jnp.float32),
         jnp.zeros((tok, PP - P), jnp.float32),
         hi[:, None], lo[:, None],
         jnp.zeros((tok, G64 - PP - 2), jnp.float32)], axis=1)
    s64 = jnp.take(pay64, order, axis=0)
    if G64 == W:
        return s64
    return jnp.concatenate(
        [s64, jnp.zeros((tok, W - G64), jnp.float32)], axis=1)


def _pack_wide(grads, shows, clks, hi, lo, order, tok, P, PP, W):
    # >=64-lane payloads are already on the fast gather path — pack at
    # the full DMA width first, one wide gather (order=None skips the
    # gather entirely: pre-merged lanes arrive sorted)
    pay_full = jnp.concatenate(
        [grads, shows[:, None], clks[:, None],
         jnp.ones((tok, 1), jnp.float32),
         jnp.zeros((tok, PP - P), jnp.float32),
         hi[:, None], lo[:, None],
         jnp.zeros((tok, W - PP - 2), jnp.float32)], axis=1)
    if order is None:
        return pay_full
    return jnp.take(pay_full, order, axis=0)


_PACK_BUILDERS = {"narrow": _pack_narrow, "gather_zone": _pack_gather_zone,
                  "wide": _pack_wide}


def _bp_pack(idx, grads, shows, clks, geom, TILE: int, n_rows: int,
             plan=None):
    """Build the kernel's packed operand: tokens grouped by super-block,
    each row ``[payload_f32 (PP lanes) | id_hi | id_lo]`` padded to a
    multiple of 8 lanes (then to whole 128-lane tiles for the DMA).
    Split from the kernel so the prep shows as its own XLA ops in a
    device trace.

    The token reorder is dispatched per payload width class (see the
    section comment above): narrow payloads gather at logical width and
    pad after; gather-zone widths pad to 64 lanes first; wide payloads
    pack at the full DMA width. All three produce the identical packed
    array — only the gather's source width differs — so forcing one via
    flags.pack_engine is always legal (the A/B knob)."""
    P, PP, G, SB = geom
    NB = n_rows // SB
    tok = idx.shape[0]
    # Mosaic DMA slices must be 128-lane aligned (memref tiling (1,128));
    # narrow payloads pad up to one lane tile, wide ones to the next
    W = -(-(PP + 2) // 128) * 128
    order = rstart = end = None
    if plan is None:
        order = jnp.argsort(idx)
        s_idx = idx[order]
        bounds = jnp.searchsorted(
            s_idx,
            jnp.arange(NB + 1, dtype=jnp.int32) * SB).astype(jnp.int32)
        rstart = (bounds[:-1] // 8) * 8      # DMA-aligned tile starts
        end = bounds[1:]
    else:
        order, rstart, end = plan
    # id digits: two exact integer-valued floats — f32 bit patterns of
    # small ints are denormals and would flush; see kernel comment
    hi = (idx // 4096).astype(jnp.float32)
    lo = (idx % 4096).astype(jnp.float32)
    eng = _resolve_pack_engine(P, premerged=order is None)
    # premerged_no_reorder builds the full-width operand with no gather
    # (the wide builder's order=None path)
    builder = _PACK_BUILDERS.get(eng, _pack_wide)
    packed = builder(grads, shows, clks, hi, lo, order, tok, P, PP, W)
    # pad so the last tile's DMA stays in bounds; pad tokens carry row
    # id n_rows, which every block's local-range mask rejects
    pad_block = jnp.zeros((TILE, W), jnp.float32)
    pad_block = pad_block.at[:, PP].set(float(n_rows // 4096))
    pad_block = pad_block.at[:, PP + 1].set(float(n_rows % 4096))
    packed = jnp.concatenate([packed, pad_block], axis=0)
    return packed, rstart, end


def binned_push_geometry(cfg: EmbeddingConfig, n_rows: int):
    """(super_block, n_blocks) for host-side plan building, or None when
    the dispatch keeps another engine (no geometry; wide rows where the
    scatter measures faster — see binned_push_supported; or a forced
    non-binned flags.push_engine) and a plan would be wasted host work
    + H2D.

    flags.push_engine overrides the per-width dispatch for A/B runs:
    "binned_kernel" keeps the kernel at G=1, "xla_scatter" /
    "scatter_accumulate" disable the binned kernel everywhere (the
    fused engine consumes premerged lanes, not block windows).
    """
    geom = _bp_geometry(cfg, n_rows)
    if geom is None:
        return None
    eng = _push_engine_flag()
    if eng in ("xla_scatter", "scatter_accumulate") \
            or (geom[2] == 1 and eng != "binned_kernel"):
        return None
    _, _, _, SB = geom
    return SB, n_rows // SB


# ---------------------------------------------------------------------------
# Push merge-engine registry + resolver.
#
# Three engines cover the push dispatch envelope:
#
#   xla_scatter        scatter-add merge into a full-table accumulator +
#                      one fused XLA update pass over the table. The
#                      no-geometry fallback, and the measured winner for
#                      wide NON-premerged token streams.
#   binned_kernel      the block-binned one-hot MXU merge above + the
#                      fused XLA update pass — the narrow-row (G >= 2)
#                      winner for raw token streams (the headline path).
#   scatter_accumulate the fused row-wise engine below: premerged unique
#                      lanes gather exactly their table rows, the
#                      optimizer applies in VMEM, each row writes back
#                      once — no full-table accumulator, no O(table)
#                      update pass. Serves both the single-shard
#                      premerged path and the routed exchange's
#                      post-all_to_all apply.
#
# The resolver below is THE one selection function (the PR-2 pack_engine
# discipline): the compiled dispatch (sharded.push / exchange.routed_push)
# and what a run reports (Trainer.engines(), the run's `engines` line)
# both call it, so a report can never name an engine the program does
# not contain.
# ---------------------------------------------------------------------------

PUSH_ENGINES = ("xla_scatter", "binned_kernel", "scatter_accumulate")

def _push_engine_flag() -> str:
    from paddlebox_tpu.config import flags as config_flags
    eng = config_flags.push_engine
    if eng != "auto" and eng not in PUSH_ENGINES:
        raise ValueError(
            f"push_engine={eng!r} (want 'auto' or one of {PUSH_ENGINES})")
    return eng


def resolve_push_engine(cfg: EmbeddingConfig, n_rows: int, *,
                        premerged: bool, storage_f32: bool = True,
                        table_width: int | None = None) -> str:
    """THE push merge-engine resolver — returns the PUSH_ENGINES member
    the push compiles with for this (cfg, rows, lane contract, storage)
    class. Both the compiled dispatch (sharded.push, exchange.
    routed_push's apply tail) and Trainer.engines() call this one
    function, so a run's `engines` line can never name a code path the
    program does not contain (the round-5 unattributable-regression
    failure mode, and the PR-2 pack_engine discipline). Raises on a typo'd
    forced engine: the flag exists for trustworthy A/Bs.

    premerged : the lanes reaching the engine are one-lane-per-unique-row
        (plan_premerge output, a deferred premerged replay, or the routed
        apply's cross-device lane merge). The fused engine REQUIRES this
        contract — without it a forced "scatter_accumulate" falls back to
        the scatter and the record says so.
    storage_f32 : quantized tables keep the binned/scatter engines (the
        fused engine updates f32 rows in place; quant planes dequant →
        update → requant around the storage-agnostic merge acc instead).
    table_width : columns of the f32 array whose rows the fused engine
        moves (quant.row_engine_width): the one array's physical width
        (>= cfg.row_width when padded), or the embedx plane's of an f32
        plane table — whole lane tiles by working_set.plane_layout, so
        the plane class always has the geometry. One rule on every
        mesh: a plane table's premerged lanes take this engine on one
        shard (sharded.push) and in the sharded exchange's apply tail
        (exchange._apply_received) alike.

    Auto heuristic per (row width class, lane contract, storage, shard):
    premerged f32 lanes on a supported geometry — a device table of
    whole 128-lane tiles, flags.table_pad_width — take the fused engine
    (the dim64/dim128/multihot4 floor points ride premerged lanes; at
    their logical widths the v5e compiler refuses the kernel's row
    DMAs, so unpadded they resolve to the engines below); narrow raw
    token streams keep the binned kernel
    (the measured headline winner); everything else (quant without
    binned geometry, wide raw tokens, off-TPU) scatters. Forced engines
    engage wherever their contract allows — "scatter_accumulate" off-TPU
    runs the identical-math jnp fallback (the A/B and CPU-parity knob),
    and a forced "binned_kernel" bypasses the flags.binned_push enable
    knob — geometry + backend are the contract; an enable flag must not
    silently void an explicit force.
    """
    eng = _push_engine_flag()
    width = int(table_width) if table_width is not None else cfg.row_width
    # an f32 plane table's embedx plane is total_dim wide — narrower than
    # any one-array table of this cfg — and takes XLA's gather and scatter
    # (_scatter_accumulate_planes), which the row-DMA kernel's width cap
    # does not bind: a 2560-wide token embedding is touched by rows too
    planes = width == cfg.total_dim and width % _LANES == 0
    sa_ok = (premerged and storage_f32 and n_rows > 0
             and (planes or scatter_accumulate_supported(n_rows, width)))
    if eng == "xla_scatter":
        return "xla_scatter"
    if eng == "scatter_accumulate":
        return "scatter_accumulate" if sa_ok else "xla_scatter"
    if eng == "binned_kernel":
        # forced: geometry + backend are the contract — the
        # flags.binned_push enable knob must not be a second SILENT
        # gate on an explicit force (the A/B would measure nothing)
        return ("binned_kernel" if binned_acc_supported(cfg, n_rows)
                else "xla_scatter")
    from paddlebox_tpu.config import flags as config_flags
    binned = (config_flags.binned_push
              and binned_acc_supported(cfg, n_rows))
    # auto: the fused engine first — wherever premerged f32 lanes exist
    # it replaces BOTH the binned kernel's one-hot dots (the multi-hot
    # ~10x overhead) and the scatter's full-table pass (the wide-row
    # floor), on real TPU only (the jnp fallback is a parity tool, not
    # a CPU production win)
    if sa_ok and jax.default_backend() == "tpu":
        return "scatter_accumulate"
    return "binned_kernel" if binned else "xla_scatter"


def lane_groups(cfg: EmbeddingConfig, n_rows: int):
    """G (payload row-groups per 128 dot lanes) for this geometry, or
    None when no kernel geometry exists. G == 1 identifies the wide-row
    widths whose dispatch keeps the XLA scatter (the dedup pre-merge's
    "wide" criterion keys off this)."""
    geom = _bp_geometry(cfg, n_rows)
    return None if geom is None else geom[2]


_geom_fallback_logged: set = set()


def binned_acc_supported(cfg: EmbeddingConfig, n_rows: int) -> bool:
    """Whether binned_merge_acc's geometry engages for this (cfg, rows)
    on the current backend — the storage-agnostic half of
    binned_push_supported (quantized tables check this directly; their
    planes aren't a plain f32 array but the merge acc doesn't care).
    The single engage predicate: binned_push_geometry already folds in
    the G=1 scatter preference."""
    if jax.default_backend() != "tpu":
        return False
    if binned_push_geometry(cfg, n_rows) is None:
        # a geometry miss on an eligible narrow table is a perf loss
        # that must be visible, not silent (ADVICE r2) — same policy as
        # the f32 gate. G=1 misses are deliberate and unwarned.
        geom = _bp_geometry(cfg, n_rows)
        if geom is None:
            key = (n_rows, cfg.grad_width)
            if key not in _geom_fallback_logged:
                _geom_fallback_logged.add(key)
                import warnings
                warnings.warn(
                    f"binned_push geometry unavailable for table rows="
                    f"{n_rows} grad_width={cfg.grad_width}; "
                    f"falling back to the XLA scatter path")
        return False
    return True


def binned_push_supported(table, cfg: EmbeddingConfig) -> bool:
    """Engages on real-TPU f32 tables where the kernel MEASURES faster
    than the XLA scatter: narrow payloads (G >= 2 lane groups, dim <=
    ~56) with a row count fitting the block geometry.

    Wide rows (G = 1) deliberately keep the scatter: the one-hot dot
    work per token grows with SB*PP once lane grouping is gone, and the
    in-step A/B on one v5e (213k tokens, batch 8192) measured scatter
    23.1ms vs kernel 28.1ms at dim 64 and 34.6ms vs 44.0ms at dim 128,
    while the kernel wins 22.9ms vs 39.3ms at dim 32 and 7.7ms vs
    15.5ms at dim 8. Both engines cover the reference's full dispatch
    envelope (box_wrapper.cc:444-461); this picks the faster one per
    width as those rounds measured it — no cell of BENCHMARK.json sits
    on either side of the crossover yet (ROADMAP D2)."""
    if not isinstance(table, jnp.ndarray) or table.dtype != jnp.float32:
        return False
    return binned_acc_supported(cfg, table.shape[0])


def binned_push(table: jnp.ndarray, idx: jnp.ndarray, grads: jnp.ndarray,
                shows: jnp.ndarray, clks: jnp.ndarray,
                cfg: EmbeddingConfig, n_split: int = 3,
                plan=None, interpret: bool = False) -> jnp.ndarray:
    """Merge + in-table optimizer via block-binned one-hot matmuls.

    Semantics match sharded.push's XLA path (duplicates merged before the
    optimizer; out-of-range idx dropped; untouched rows bit-identical) up
    to f32 summation order. n_split: bf16 planes the payload crosses the
    MXU in, built in-kernel from the f32 payload (3 ~= f32-exact; 1 =
    bf16 grads, ~3x fewer dots). Covers the reference's full embedx
    envelope (dims 2..280+, box_wrapper.cc:444-461): narrow rows share
    dot lanes across G row-groups, wide rows take a >128-lane
    accumulator.

    plan: optional (order, rstart, end) token grouping from the host
    (native block_plan, computed in the pack pipeline overlapped with
    device compute — saves the ~2.2ms on-device argsort). Without it the
    grouping runs on device. The kernel only needs tokens GROUPED per
    super-block; order within a block is irrelevant (the matmul merges).
    interpret=True runs the Pallas interpreter (CPU test path).
    """
    n_rows = table.shape[0]
    vma = getattr(jax.typeof(table), "vma", frozenset())
    acc = binned_merge_acc(idx, grads, shows, clks, cfg, n_rows,
                           n_split=n_split, plan=plan,
                           interpret=interpret, vma=vma)
    gw = cfg.grad_width
    new_rows = apply_updates(table, acc[:, :gw], acc[:, gw],
                             acc[:, gw + 1], cfg)
    touched = acc[:, gw + 2] > 0
    return jnp.where(touched[:, None], new_rows, table)


# ---------------------------------------------------------------------------
# Fused gather-pool: the pull-side dual of binned_push.
#
# Multi-hot slots are bottlenecked by the (tokens, pull_width) pulled
# matrix the unfused path materializes between the table gather and the
# per-slot sum pool (the reference fuses exactly this in its
# fused_seqpool_cvm* CUDA kernels): at 4 ids a slot and dim 32 the step
# moves 852k x 35 f32 rows to HBM, pools them, then moves the same-shape
# gradient back — the first rounds read 37.7k examples/s/chip there
# against 645k one-hot. This kernel gathers rows from the (HBM-resident) device
# table with per-row async copies and sum-pools them per (example, slot)
# segment while they sit in VMEM, emitting only the pooled
# (B, num_slots, pull_width) output — the per-token matrix never exists
# in HBM. The per-token filters of the reference kernel family
# (need_filter show/clk thresholds — scalar or per-slot —
# embed_threshold, quant_ratio) apply to the gathered rows in VMEM
# before pooling, same math as seqpool_cvm._filter_and_quant.
#
# Layout: tokens of one batch tile land in the gathered scratch at row
# ``l * BB*S + b*S + s`` (pool-position-major), so the pool is L
# contiguous block adds — no strided reads, no scatter. Masked tokens
# are pre-mapped to row NULL_INDEX (all zeros by the working-set
# contract), so padding contributes zeros without a mask operand.
#
# What the chip's compiler takes (asked through tests/
# test_aot_tpu_compile.py; interpret mode accepts far more): a row DMA
# must move whole (8, 128) tiles of a 2-D f32 array, so one row of the
# (n_rows, W) table cannot be sliced; rows are read through an
# (n_rows, 1, W) view in 128-lane chunks (_row_view, _row_chunk_copy),
# which needs W a multiple of 128 — the geometry function refuses any
# other table, and the trainer then names the unfused engine. 1-D int32
# SMEM windows tile by 1024 words, so each batch tile's ids are padded
# to whole blocks.
#
# The backward pass does not run in here: the pooled cotangent is
# (B, S, P) — already ~L times smaller than the token matrix — and
# sharded.pooled_grad_tokens expands it per token straight into the
# dedup pre-merge + binned_push pipeline (see PARITY.md "Fused
# gather-pool pull").
#
# On CPU the kernel runs under the Pallas interpreter for the parity
# tests; production CPU paths (and any unsupported geometry) take the
# jnp reference in sharded.fused_pull_pool.
# ---------------------------------------------------------------------------

_GP_VMEM_BUDGET = 4 << 20   # gathered-rows scratch cap (bytes)
_GP_MAX_WIDTH = 512         # table row lanes past this: fall back
_GP_SEMS = 8                # in-flight row DMAs
_LANES = 128                # lane tile: row DMAs move whole lane tiles
_SMEM_BLOCK = 1024          # 1-D int32 SMEM blocks tile by 1024 words


def _row_view(table: jnp.ndarray) -> jnp.ndarray:
    """(n_rows, W) -> (n_rows, 1, W): the per-row DMA view.

    Mosaic only slices HBM along a tiled dimension in whole tiles, and a
    2-D f32 table tiles (8, 128) — a one-row slice is refused ("Slice
    shape along dimension 0 must be aligned to tiling (8), but is 1").
    The 3-D view tiles (1, 128), so ``view.at[row, :, lanes]`` is whole
    lane tiles of one row. At W == 128 both layouts are the same
    row-major bytes and XLA lowers the reshape to a bitcast; wider
    tables (W = 256..512) get a physical relayout of the whole table
    around the call (a table-sized temp in the compiled program) —
    ROADMAP S3 decides whether those widths keep the fused engines."""
    n_rows, W = table.shape
    return table.reshape(n_rows, 1, W)


def _row_chunk_copy(view_ref, row, rows_ref, t, c, sem, to_table=False):
    """Async copy of lane tile `c` of table row `row` between the
    (n_rows, 1, W) HBM view and row `t` of the (W/128, rows, 128) VMEM
    row buffer — chunk planes keep the buffer's (8, 128) tiles dense for
    the compute that follows, where a (rows, 1, W) buffer would hand the
    VPU one sublane per register."""
    hbm = view_ref.at[row, :, pl.ds(c * _LANES, _LANES)]
    vmem = rows_ref.at[c, pl.ds(t, 1), :]
    return (pltpu.make_async_copy(vmem, hbm, sem) if to_table
            else pltpu.make_async_copy(hbm, vmem, sem))


def _lane_chunks(rows_ref, lo: int, hi: int) -> jnp.ndarray:
    """Rows [lo, hi) of a (W/128, rows, 128) row buffer as one
    (hi-lo, W) value."""
    parts = [rows_ref[c, lo:hi, :] for c in range(rows_ref.shape[0])]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def gather_pool_geometry(B: int, S: int, L: int, table_width: int):
    """Batch-tile size BB for the gather-pool kernel, or None when the
    (batch, slots, slot_len, width) combination doesn't fit its layout
    needs. The table must be whole lane tiles wide (a multiple of 128
    columns, flags.table_pad_width): the v5e compiler refuses row DMAs
    of any other width ("Slice shape along dimension 1 must be aligned
    to tiling (128)"). BB is the largest power of two <= 64 dividing B
    whose gathered scratch (L * BB * S rows) fits the VMEM budget —
    bigger tiles amortize the grid prologue, smaller ones keep wide rows
    resident."""
    if (B <= 0 or S <= 0 or L <= 0 or table_width <= 0
            or table_width % _LANES or table_width > _GP_MAX_WIDTH):
        return None
    BB = 64
    while BB > 1 and (B % BB
                      or L * BB * S * table_width * 4 > _GP_VMEM_BUDGET):
        BB //= 2
    if B % BB or L * BB * S * table_width * 4 > _GP_VMEM_BUDGET:
        return None
    return BB


def gather_pool_supported(cfg: EmbeddingConfig, B: int, S: int, L: int,
                          table_width: int) -> bool:
    """Whether the fused gather-pool kernel engages for this geometry on
    the current backend. Real-TPU f32 tables only: quantized storage
    gathers two planes (the jnp reference handles it), and the pull
    gating masks (mf/expand create thresholds) are applied by lookup —
    the kernel skips both, so it must not engage where they matter.
    CPU callers get the jnp reference in sharded.fused_pull_pool; tests
    drive the kernel directly in interpret mode."""
    if jax.default_backend() != "tpu":
        return False
    if cfg.storage != "f32":
        return False
    if cfg.mf_create_threshold > 0 or cfg.expand_create_threshold > 0:
        return False
    return gather_pool_geometry(B, S, L, table_width) is not None


def _gather_pool_kernel(idx_ref, thr_ref, table_ref, out_ref, gathered, sem,
                        *, BB: int, S: int, L: int, T: int, P: int,
                        n_rows: int, n_sem: int, need_filter: bool,
                        show_coeff: float, clk_coeff: float,
                        embed_threshold: float, quant_ratio: int,
                        cvm_offset: int):
    """One batch tile: DMA-gather BB*T table rows into the
    pool-position-major scratch, then pool with L contiguous block adds.

    idx_ref : (>= BB*T,) int32 in SMEM — this tile's (already
              translated, mask-nulled) row ids, padded to whole SMEM
              blocks; the DMA source address for each row.
    thr_ref : (BB*S, 1) f32 — per-(example, slot) need_filter threshold
              (the per-slot diff-thres variant tiled over the tile's
              examples; zeros when need_filter is off).
    table_ref : the (n_rows, 1, W) row view of the table (_row_view).
    gathered  : (W/128, L*BB*S, 128) row buffer (_row_chunk_copy).
    The row DMAs run n_sem deep: copy t+n_sem is issued as soon as copy
    t completes (same-slot semaphore reuse forces that order anyway).
    """
    n = BB * T
    BBS = BB * S

    C = gathered.shape[0]

    def copies(t):
        row = jnp.minimum(idx_ref[t], n_rows - 1)
        b = t // T
        within = t - b * T
        s = within // L
        l = within - s * L
        dest = l * BBS + b * S + s
        return [_row_chunk_copy(table_ref, row, gathered, dest, c,
                                sem.at[lax.rem(t, n_sem)])
                for c in range(C)]

    for k in range(n_sem):
        for cp in copies(k):
            cp.start()

    def body(t, _):
        for cp in copies(t):
            cp.wait()

        @pl.when(t + n_sem < n)
        def _prefetch():
            for cp in copies(t + n_sem):
                cp.start()

        return 0

    lax.fori_loop(0, n, body, 0)

    acc = None
    for l in range(L):
        x = _lane_chunks(gathered, l * BBS, (l + 1) * BBS)
        keep = None
        if need_filter:
            show, clk = x[:, 0:1], x[:, 1:2]
            keep = ((show - clk) * show_coeff + clk * clk_coeff
                    >= thr_ref[...])
        if embed_threshold > 0.0:
            show, w = x[:, 0:1], x[:, cvm_offset:cvm_offset + 1]
            drop = ((show > embed_threshold)
                    & (jnp.abs(w) < embed_threshold))
            keep = ~drop if keep is None else keep & ~drop
        if quant_ratio > 0:
            # quantize embedx lanes only (lanes past P are sliced away
            # below; quantizing them along for the ride is harmless)
            lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
            q = jnp.round(x * quant_ratio) / quant_ratio
            x = jnp.where(lane >= cvm_offset + 1, q, x)
        if keep is not None:
            x = x * keep.astype(x.dtype)
        acc = x if acc is None else acc + x
    out_ref[...] = acc[:, :P]


def gather_pool(table: jnp.ndarray, idx: jnp.ndarray, cfg: EmbeddingConfig,
                num_slots: int, slot_len: int, *,
                need_filter: bool = False, show_coeff: float = 0.2,
                clk_coeff: float = 1.0, threshold=0.96,
                embed_threshold: float = 0.0, quant_ratio: int = 0,
                cvm_offset: int = 2,
                interpret: bool | None = None) -> jnp.ndarray:
    """Fused gather + per-(example, slot) sum pool over the device table.

    table : (n_rows, W) f32 device table, W a multiple of 128 columns
            >= cfg.pull_width (pad/opt columns past pull_width are
            gathered and discarded). Row NULL_INDEX must be the all-zero
            row — masked/padding tokens point there and contribute zeros
            (callers null idx by mask).
    idx   : (B, S*L) int32 translated indices, slot-major uniform layout
            (token (b, s, l) at column s*L + l — SparseLayout with equal
            max_len per slot).
    threshold may be a scalar or a per-slot (S,) vector (the diff-thres
    variant). Returns (B, S, pull_width) pooled rows; the CVM transform
    applies downstream on this small output (seqpool_cvm.PooledSlots).
    """
    B, T = idx.shape
    S, L = num_slots, slot_len
    assert T == S * L, (T, S, L)
    n_rows, W = table.shape
    BB = gather_pool_geometry(B, S, L, W)
    assert BB is not None, "caller must check gather_pool geometry support"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    P = cfg.pull_width
    thr = jnp.asarray(threshold, jnp.float32)
    if thr.ndim == 0:
        thr = jnp.broadcast_to(thr, (S,))
    thr_col = jnp.tile(thr, (BB,))[:, None]
    BBS = BB * S
    n_sem = min(_GP_SEMS, BB * T)
    # each batch tile's ids ride one whole-block SMEM window
    blk = -(-BB * T // _SMEM_BLOCK) * _SMEM_BLOCK
    idx_t = jnp.pad(idx.astype(jnp.int32).reshape(B // BB, BB * T),
                    ((0, 0), (0, blk - BB * T))).reshape(-1)
    kernel = functools.partial(
        _gather_pool_kernel, BB=BB, S=S, L=L, T=T, P=P, n_rows=n_rows,
        n_sem=n_sem, need_filter=bool(need_filter),
        show_coeff=float(show_coeff), clk_coeff=float(clk_coeff),
        embed_threshold=float(embed_threshold),
        quant_ratio=int(quant_ratio), cvm_offset=int(cvm_offset))
    vma = getattr(jax.typeof(table), "vma", frozenset())
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * S, P), jnp.float32, vma=vma),
        grid=(B // BB,),
        in_specs=[
            pl.BlockSpec((blk,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((BBS, 1), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((BBS, P), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((W // _LANES, L * BBS, _LANES),
                                   jnp.float32),
                        pltpu.SemaphoreType.DMA((n_sem,))],
        interpret=interpret,
        name="pbtpu_gather_pool",
    )(idx_t, thr_col, _row_view(table))
    return out.reshape(B, S, P)


def binned_merge_acc(idx: jnp.ndarray, grads: jnp.ndarray,
                     shows: jnp.ndarray, clks: jnp.ndarray,
                     cfg: EmbeddingConfig, n_rows: int, n_split: int = 3,
                     plan=None, interpret: bool = False,
                     vma=None) -> jnp.ndarray:
    """The kernel's merge half alone: the (n_rows, grad_width+3) per-row
    accumulator [summed grads, show, clk, touch_count] — identical
    contract to the XLA scatter-add acc, so storage variants (quantized
    tables dequant->update->requant around it) reuse the scatter-free
    merge without the kernel knowing their row encoding."""
    geom = _bp_geometry(cfg, n_rows)
    assert geom is not None, "caller must check binned geometry support"
    P, PP, G, SB = geom
    NB = n_rows // SB
    TILE = _bp_tile(SB, G)
    packed, rstart, end = _bp_pack(idx, grads, shows, clks, geom, TILE,
                                   n_rows, plan)
    W = packed.shape[1]
    if vma is None:
        vma = getattr(jax.typeof(grads), "vma", frozenset())
    RB = SB // G
    AW = _bp_acc_width(G, PP)
    kernel = functools.partial(_binned_acc_kernel, PP=PP,
                               G=G, SB=SB, n_split=n_split, TILE=TILE)
    acc_g = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((NB * RB, AW), jnp.float32,
                                       vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(NB,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((RB, AW), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM((2, TILE, W), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        interpret=interpret,
        name="pbtpu_binned_merge_acc",
    )(rstart, end, packed)
    # untangle the grouped layout (fuses into the consumer's update pass)
    return acc_g[:, :G * PP].reshape(NB, RB, G, PP).transpose(
        0, 2, 1, 3).reshape(n_rows, PP)[:, :P]


# ---------------------------------------------------------------------------
# Fused scatter-accumulate: the push-side mirror image of gather_pool.
#
# The scatter and binned engines both end in ONE fused XLA pass over the
# WHOLE table (read + update + where(touched) + write), so their cost has
# an O(table) term that dominates wide tables: at dim 128 a 528k x ~134
# f32 table moves ~0.6GB per step through that pass while only ~200k
# unique rows changed, and the binned kernel additionally pays one-hot
# dots that grow ~10x on multi-hot batches. This kernel takes the premerged unique lanes
# the dedup plan already produces (sharded.plan_premerge — one lane per
# touched row, pads out-of-range) and touches ONLY those rows: per lane,
# DMA the table row into VMEM (n_sem-deep pipelined, the gather_pool
# pattern), apply ``embedding.optim.apply_updates`` row-wise on the tile
# in VMEM — the identical update the XLA pass runs, so numerics match
# bit-for-bit — and DMA the updated row back in place
# (input_output_aliases keeps the table buffer donated). Traffic is
# O(unique rows x row bytes x 2) instead of O(table); benchmark/work.py
# counts those bytes for the cells' `step_hbm_roofline_pct`.
#
# Lane contract (the premerged form everywhere in this codebase): row ids
# UNIQUE among touched lanes; pad lanes carry out-of-range ids or a zero
# touch flag and are skipped — their write-back DMA never issues, so a
# pad can never clobber a real row's update (the failure mode a clamped
# unconditional write-back exhibits when a real row-0 lane and clamped
# pads interleave). The same kernel serves the single-shard premerged
# push and the routed exchange's post-all_to_all apply: received lanes
# are unique per SOURCE device, so the routed tail merges the <= D lanes
# per row with one compact lane-grade scatter (exchange.routed_push) and
# hands the kernel unique lanes again.
#
# Off-TPU the identical math runs as the jnp reference (gather → row-wise
# apply_updates → one masked scatter write) — the CPU production path and
# the bit-parity baseline; interpret=True drives the Pallas interpreter
# for the hardware-free kernel tests (SURVEY.md §4), except under a
# check_vma shard_map where interpret mode cannot run nontrivial kernels
# (see merge_update) and the jnp reference takes over.
# ---------------------------------------------------------------------------

_SA_TILE = _SMEM_BLOCK   # lanes per grid step (one SMEM block of row ids)
_SA_MAX_WIDTH = 512     # table row lanes past this: fall back
_SA_SEMS = 8            # in-flight row DMAs per direction
_SA_SUB = 256           # rows per in-VMEM optimizer sub-block


def scatter_accumulate_geometry(n_rows: int, table_width: int):
    """Lane-tile size for the fused scatter-accumulate, or None when the
    table doesn't fit the kernel's per-row-DMA layout: the table must be
    whole lane tiles wide (a multiple of 128 columns,
    flags.table_pad_width — the v5e compiler refuses row DMAs of any
    other width, see _row_view), and rows past the width cap stream
    whole rows the row buffer can't hold."""
    if (n_rows <= 0 or table_width <= 0 or table_width % _LANES
            or table_width > _SA_MAX_WIDTH):
        return None
    return _SA_TILE


def scatter_accumulate_supported(n_rows: int, table_width: int) -> bool:
    """Whether the fused engine can run for this table on the current
    backend: on a TPU the Mosaic kernel's geometry decides; elsewhere
    the identical-math jnp reference takes any width up to the cap (the
    forced CPU-parity/A/B form)."""
    if jax.default_backend() == "tpu":
        return scatter_accumulate_geometry(n_rows, table_width) is not None
    return n_rows > 0 and 0 < table_width <= _SA_MAX_WIDTH


def _scatter_accumulate_kernel(idx_ref, tch_ref, pay_ref, table_ref,
                               out_ref, gathered, sem_in, sem_out, *,
                               TILE: int, n_rows: int, n_sem: int,
                               cfg: EmbeddingConfig):
    """One lane tile: pipelined row gather → row-wise optimizer in VMEM
    → predicated pipelined row write-back.

    idx_ref : (TILE,) int32 SMEM — table row per lane (out-of-range =
              pad; reads clamp to row 0, whose gathered bits are
              discarded because the pad's write never issues).
    tch_ref : (TILE,) int32 SMEM — touch flag per lane; 0 skips the
              write-back entirely (untouched rows keep their exact bits,
              the push contract).
    pay_ref : (TILE, grad_width+2) f32 — [merged grads | show | clk].
    table_ref / out_ref : the (n_rows, 1, W) row view of the device
              table (_row_view), aliased — rows update in place; rows no
              valid lane names are never touched. Lanes are unique among
              touched lanes, so write DMAs never collide and tile order
              cannot matter.
    gathered : (W/128, TILE, 128) row buffer (_row_chunk_copy).
    """
    def _row(t):
        r = idx_ref[t]
        return jnp.where((r >= 0) & (r < n_rows), r, 0)

    def _valid(t):
        r = idx_ref[t]
        return (r >= 0) & (r < n_rows) & (tch_ref[t] > 0)

    C = gathered.shape[0]

    def reads(t):
        return [_row_chunk_copy(table_ref, _row(t), gathered, t, c,
                                sem_in.at[lax.rem(t, n_sem)])
                for c in range(C)]

    def writes(t):
        return [_row_chunk_copy(out_ref, _row(t), gathered, t, c,
                                sem_out.at[lax.rem(t, n_sem)],
                                to_table=True)
                for c in range(C)]

    for k in range(n_sem):
        for cp in reads(k):
            cp.start()

    def gbody(t, _):
        for cp in reads(t):
            cp.wait()

        @pl.when(t + n_sem < TILE)
        def _prefetch():
            for cp in reads(t + n_sem):
                cp.start()

        return 0

    lax.fori_loop(0, TILE, gbody, 0)
    gw = cfg.grad_width
    # the identical row-wise update the scatter engine's full-table pass
    # runs — elementwise per row, so gather→apply ≡ apply→gather
    # bitwise; in row sub-blocks so the update's temporaries stay a
    # fraction of the row buffer
    for lo in range(0, TILE, _SA_SUB):
        hi = lo + _SA_SUB
        pay = pay_ref[lo:hi, :]
        new_rows = apply_updates(_lane_chunks(gathered, lo, hi),
                                 pay[:, :gw], pay[:, gw], pay[:, gw + 1],
                                 cfg)
        for c in range(C):
            gathered[c, lo:hi, :] = new_rows[:, c * _LANES:(c + 1) * _LANES]

    # predicated pipeline: lane t's start AND wait share one predicate,
    # and slot t % n_sem is reused only after t's wait ran (or never
    # started) — at most one outstanding row per slot in every
    # valid/invalid interleaving
    for k in range(n_sem):
        @pl.when(_valid(k))
        def _start(k=k):
            for cp in writes(k):
                cp.start()

    def sbody(t, _):
        @pl.when(_valid(t))
        def _wait():
            for cp in writes(t):
                cp.wait()

        @pl.when((t + n_sem < TILE) & _valid(t + n_sem))
        def _next():
            for cp in writes(t + n_sem):
                cp.start()

        return 0

    lax.fori_loop(0, TILE, sbody, 0)


def _scatter_accumulate_planes(table, idx, grads, shows, clks,
                               cfg: EmbeddingConfig, touched):
    """scatter_accumulate on f32 planes (quant.PlaneTable): the jnp
    reference below, plane by plane — gather the touched rows of both
    planes, assemble full rows, the same apply_updates, scatter them
    back with the pads dropped. This is what runs on a TPU too: at 213 k
    lanes of a 2.6 M-row dim-128 table XLA's gather -> update -> scatter
    measured 8.5 ms against the row-DMA kernel's 20.0 ms (one v5e,
    PERF.md PR 25), and the row-major embedx plane needs no view.

    With no `touched` the lanes are plan_premerge's (ascending, unique,
    pads ascending past the last row), so the scatter may promise both;
    the routed apply's lanes (`touched` given) are unique only among the
    touched ones, and their pads leave the scatter out of range."""
    n_rows = table.shape[0]
    in_range = (idx >= 0) & (idx < n_rows)
    safe = jnp.where(in_range, idx, 0)
    # barrier: the column slices of assemble_rows must not fuse into the
    # narrow plane's gather (sharded.lookup on what that costs)
    fp, qx = (lax.optimization_barrier(jnp.take(p, safe, axis=0))
              for p in table)
    new = quant.split_rows(
        apply_updates(quant.assemble_rows(fp, qx, cfg), grads, shows, clks,
                      cfg), cfg)
    if touched is None:
        wr = jnp.where(idx >= 0, idx, n_rows)
        hints = {"indices_are_sorted": True, "unique_indices": True}
    else:
        wr = jnp.where((touched > 0) & in_range, idx, n_rows)
        hints = {}
    # the narrow plane column by column: the chip holds it column-major,
    # where a scatter of rows costs one pass a column either way, and as
    # 1-D scatters each pass is cheaper (five columns at 213 k lanes: 5.3
    # against 8.6 ms inside the composed apply, one v5e, PERF.md PR 25)
    fp = jnp.stack(
        [table.fp[:, c].at[wr].set(new.fp[:, c], mode="drop", **hints)
         for c in range(table.fp.shape[1])], axis=1)
    return quant.PlaneTable(
        fp=fp, qx=table.qx.at[wr].set(new.qx, mode="drop", **hints))


def scatter_accumulate(table: jnp.ndarray, idx: jnp.ndarray,
                       grads: jnp.ndarray, shows: jnp.ndarray,
                       clks: jnp.ndarray, cfg: EmbeddingConfig,
                       touched: jnp.ndarray | None = None,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Row-wise fused merge-apply over premerged unique lanes.

    table : (n_rows, W) f32 device table (W >= cfg.row_width; pad
            columns pass through apply_updates untouched). The kernel
            needs W a multiple of 128 columns
            (scatter_accumulate_geometry); the jnp reference takes any.
            Or f32 planes (quant.PlaneTable): XLA's gather and scatter
            on every backend (_scatter_accumulate_planes).
    idx   : (n,) int32 — ONE lane per touched row (plan_premerge's
            contract: ascending unique with out-of-range pads, or any
            unique-among-touched order — the routed apply's lanes).
    grads/shows/clks : merged per-row payload (exact counters included).
    touched : optional per-lane touch flag; default = in-range(idx).
            The routed apply passes the cross-device lane count so its
            dedup-capacity pads (in-range row 0, zero payload) skip the
            write entirely instead of leaning on the null-row fixed
            point.
    interpret : None = jnp reference off-TPU / Mosaic kernel on TPU;
            True = the Pallas interpreter (hardware-free kernel tests);
            False = the Mosaic kernel (AOT compile tests).

    Semantics match sharded.push's scatter path bit-for-bit: the same
    apply_updates runs on the same merged values; untouched rows keep
    their exact bits (their row is never DMA'd back). Returns the
    updated table (aliased in place under jit donation).
    """
    n_rows, W = table.shape
    gw = cfg.grad_width
    idx = idx.astype(jnp.int32)
    if quant.is_planes(table):
        return _scatter_accumulate_planes(table, idx, grads, shows, clks,
                                          cfg, touched)
    if touched is None:
        tch = ((idx >= 0) & (idx < n_rows)).astype(jnp.int32)
    else:
        tch = (touched > 0).astype(jnp.int32)
    pay = jnp.concatenate(
        [grads, shows[:, None], clks[:, None]], axis=1)
    vma = getattr(jax.typeof(table), "vma", frozenset())
    on_tpu = jax.default_backend() == "tpu"
    use_kernel = interpret is not None or on_tpu
    if interpret is None:
        interpret = not on_tpu
    if not use_kernel or (interpret and vma):
        # the jnp reference: identical math (same gather, same row-wise
        # apply_updates, one masked unique scatter write) — the CPU
        # production path, and the only form interpret mode can run
        # inside a check_vma shard_map (see merge_update)
        safe = jnp.where((idx >= 0) & (idx < n_rows), idx, 0)
        rows = jnp.take(table, safe, axis=0)
        new_rows = apply_updates(rows, pay[:, :gw], pay[:, gw],
                                 pay[:, gw + 1], cfg)
        keep = (tch > 0) & (idx >= 0) & (idx < n_rows)
        # dropped lanes leave the scatter entirely (out-of-range +
        # mode="drop") — a pad must never write a real row's old bits
        # over another lane's update
        wr = jnp.where(keep, idx, n_rows)
        return table.at[wr].set(new_rows, mode="drop")
    TILE = scatter_accumulate_geometry(n_rows, W)
    assert TILE is not None, \
        "caller must check scatter_accumulate geometry support"
    n = idx.shape[0]
    pad = (-n) % TILE
    if pad:
        idx = jnp.concatenate(
            [idx, jnp.full((pad,), n_rows, jnp.int32)])
        tch = jnp.concatenate([tch, jnp.zeros((pad,), tch.dtype)])
        pay = jnp.concatenate(
            [pay, jnp.zeros((pad, pay.shape[1]), pay.dtype)])
    n_sem = min(_SA_SEMS, TILE)
    kernel = functools.partial(_scatter_accumulate_kernel, TILE=TILE,
                               n_rows=n_rows, n_sem=n_sem, cfg=cfg)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_rows, 1, W), table.dtype,
                                       vma=vma),
        grid=(idx.shape[0] // TILE,),
        in_specs=[
            pl.BlockSpec((TILE,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((TILE,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((TILE, gw + 2), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((W // _LANES, TILE, _LANES),
                                   jnp.float32),
                        pltpu.SemaphoreType.DMA((n_sem,)),
                        pltpu.SemaphoreType.DMA((n_sem,))],
        input_output_aliases={3: 0},
        interpret=interpret,
        name="pbtpu_scatter_accumulate",
    )(idx, tch, pay, _row_view(table))
    return out.reshape(n_rows, W)
