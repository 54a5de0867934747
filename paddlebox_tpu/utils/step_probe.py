"""Per-stage device-time attribution of the train step.

The reference prints a read/trans/cal/sync split per pass
(``log_for_profile``, boxps_worker.cc:746-759); this module is the
device-side analogue for the jitted TPU step: it measures each stage of
the step — embedding ``lookup``, ``dense_fwd_bwd``, ``sparse_push``
(which includes the payload reorder, pack, and binned kernel), and the
``dispatch_floor`` (per-program launch cost, measured with a no-op step
of identical signature) — as wall-free DEVICE time; the remainder is
``unattributed_seconds`` (fusion/overlap differences between isolated
stages and the real fused step). The bench embeds the result
(``attribute_step``) so a throughput regression names its stage.

Measurement discipline (see bench.py module docstring): a single jit call
costs a dispatch that can rival a stage's device time, so every stage is
measured by repeating it K times INSIDE one jit, chained through
``lax.optimization_barrier`` so XLA can neither hoist the loop-invariant
body nor dead-code it, and every window ends with a 4-byte host read of
its result. Per-call time is (window - empty_window)
/ K, where the empty window (same K-iteration fori_loop over a barrier
no-op) measures the dispatch + loop floor.
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _sync(x) -> float:
    return float(np.asarray(jax.tree.leaves(x)[0].reshape(-1)[0]))


def _all_alive(*trees) -> bool:
    """True iff no leaf has been invalidated by donation. A step that
    fails DURING execution has already consumed its donated inputs; the
    recovery rebind must not touch those (reading them raises and would
    mask the original, real error)."""
    for leaf in jax.tree.leaves(trees):
        if getattr(leaf, "is_deleted", lambda: False)():
            return False
    return True


def timed_repeat(fn: Callable, args: tuple, k: int = 32,
                 warmup: int = 2) -> float:
    """Device seconds per fn(*args) call, dispatch-subtracted.

    fn must return an array (or pytree). Iterations are data-chained so
    the body stays inside the loop and none of it dead-codes: EVERY leaf
    of the output is reduced with jnp.sum, the sums feed the next
    iteration's carry through an optimization_barrier, and the carry
    perturbs fn's first argument. The sum is a full read of the output —
    a small, bandwidth-bounded overhead included in the reported time
    (it cancels when comparing variants with equal output shapes).
    """

    def chained(carry_arg, *rest):
        def body(_, state):
            c, acc = state
            out = fn(c, *rest)
            # full data dependence on out: nothing in fn can be DCE'd
            s = jnp.asarray(0.0, jnp.float32)
            for leaf in jax.tree.leaves(out):
                s = s + jnp.sum(leaf).astype(jnp.float32)
            c2, s2 = lax.optimization_barrier((c, s))
            # s2 is opaque past the barrier: XLA cannot fold the float
            # multiply-by-zero, so the carry genuinely depends on out
            bump = (s2 * 0.0).astype(carry_arg.dtype)
            return c2 + bump, acc + s2
        final, acc = lax.fori_loop(0, k, body,
                                   (carry_arg, jnp.float32(0.0)))
        return acc

    def empty(carry_arg):
        def body(_, state):
            c, acc = state
            c2, a2 = lax.optimization_barrier((c, acc))
            return c2, a2 + 1.0
        _, acc = lax.fori_loop(0, k, body,
                               (carry_arg, jnp.float32(0.0)))
        return acc

    jfn = jax.jit(chained)
    jempty = jax.jit(empty)
    for _ in range(warmup):
        _sync(jfn(*args))
        _sync(jempty(args[0]))
    best = min(_window(jfn, args) for _ in range(5))
    floor = min(_window(jempty, (args[0],)) for _ in range(5))
    if timed_repeat.debug:
        print(f"#   timed_repeat k={k} best={best*1e3:.2f}ms "
              f"floor={floor*1e3:.2f}ms", flush=True)
    return max(0.0, (best - floor)) / k


timed_repeat.debug = False


def _window(jfn, args) -> float:
    t0 = time.perf_counter()
    _sync(jfn(*args))
    return time.perf_counter() - t0


def measure_step_floor(trainer, ws, staged, n: int = 100) -> float:
    """Per-step dispatch/launch/aliasing floor: a no-op step with the train
    step's exact signature (same dense-state transport, same donation,
    same out_shardings), looped like the bench loop. What remains after
    subtracting real compute stages from the step time is mostly THIS —
    per-program launch cost — and it is a real, measured stage, not a
    fudge residual."""
    from paddlebox_tpu.parallel import mesh as mesh_lib

    repl = mesh_lib.replicated_sharding(trainer.mesh)
    tbl_sh = mesh_lib.table_sharding(trainer.mesh)
    nd = trainer._n_dense_args

    def noop(table, *args):
        labels = args[nd + 3]
        loss = jnp.sum(labels) * 0.0
        return (table, *args[:nd], loss)

    fn = jax.jit(noop, donate_argnums=tuple(range(1 + nd)),
                 out_shardings=(tbl_sh,) + (repl,) * nd + (repl,))
    table = ws.table
    dstate = trainer.pack_dense()
    # the loop donates table/dstate every call; on ANY escape, rebind the
    # caller-visible state to the last arrays that exist so a retry of the
    # surrounding attribution never reads a deleted buffer
    try:
        for _ in range(2):
            out = fn(table, *dstate, *staged)
            table, dstate, loss = out[0], out[1:1 + nd], out[-1]
        _sync(loss)
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n):
                out = fn(table, *dstate, *staged)
                table, dstate, loss = out[0], out[1:1 + nd], out[-1]
            _sync(loss)
            w = time.perf_counter() - t0
            best = w if best is None else min(best, w)
    finally:
        # rebind only live arrays: an execution-time failure donated these
        # away, and unpack_dense on dead buffers would raise inside the
        # finally, masking the real error (state is then genuinely lost —
        # the caller's retry fails fast with 'Array has been deleted')
        if _all_alive(table, dstate):
            ws.table = table
            trainer.params, trainer.opt_state = trainer.unpack_dense(
                dstate)
    return best / n


def _run_step_loop(trainer, fn, staged, n: int, holder: list) -> float:
    """Bench-identical donation loop over holder's [table, dense_state];
    returns sec/step. `holder` is kept current after every step so the
    caller can recover state when a call fails BEFORE executing
    (compile/trace/dispatch errors). A failure DURING execution has
    already consumed holder's
    arrays via donation; the caller's _all_alive guard detects that case
    and recovery is then impossible by design."""
    def step():
        out = fn(holder[0], *holder[1], *staged)
        table, dstate, loss, _, _ = trainer.split_step_out(out)
        holder[0], holder[1] = table, dstate
        return loss

    for _ in range(2):
        loss = step()
    _sync(loss)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            loss = step()
        _sync(loss)
        w = time.perf_counter() - t0
        best = w if best is None else min(best, w)
    return best / n


def _run_defer_loop(trainer, staged, n: int, holder: list,
                    with_apply: bool) -> float:
    """Bench-identical loop over the DEFERRED step program (push_overlap):
    the loss-path program alone (with_apply=False — the table is read,
    never updated; fine for timing) or the real pipeline pair (deferred
    step + apply dispatched back to back, the training loop's dataflow).
    holder carries [table, dense_state] like _run_step_loop."""
    idx, mask, dense, labels = staged[:4]
    plan = staged[4:9]

    def step():
        out = trainer._defer_step_fn(holder[0], *holder[1], *staged)
        dstate, ops, loss, preds, drop = trainer.split_defer_out(out)
        holder[1] = dstate
        if with_apply:
            holder[0] = trainer._apply_fn(holder[0], idx, mask, labels,
                                          *plan, *ops)
        return loss

    for _ in range(2):
        loss = step()
    _sync(loss)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            loss = step()
        _sync(loss)
        w = time.perf_counter() - t0
        best = w if best is None else min(best, w)
    return best / n


def attribute_step(trainer, ws, staged, step_seconds: float,
                   k: int = 24, n_loop: int = 100) -> dict:
    """Stage breakdown of one train step, as device seconds.

    Primary account — **telescoping cumulative ablation**: the trainer
    builds the SAME jitted step with successively more stages replaced by
    shape-preserving no-ops (``Trainer._build_train_step(ablate=...)``,
    biggest stage removed first), each measured with the bench's own
    donation loop. Successive differences sum exactly to the full step,
    so coverage is ~100% by construction; a stage's delta is its marginal
    cost given the stages removed before it (XLA overlaps stages, so
    shared time lands on the earliest-removed stage that exposes it).
    ``glue_residual`` is what the emptied-out step still costs above the
    no-op ``dispatch_floor`` (grad scaling, dense optimizer, psum, output
    plumbing). Isolated per-stage times are reported as ``isolated`` —
    they over-count overlap and bound each stage from above.

    trainer : Trainer in "allreduce" dense-sync mode (the bench config)
    ws      : the PassWorkingSet whose table the step trains
    staged  : one staged batch tuple (idx, mask, dense, labels, *plan)
    step_seconds : measured full-step seconds (the number to attribute)
    """
    from paddlebox_tpu.embedding import sharded

    assert trainer.cfg.dense_sync_mode == "allreduce", (
        "stage attribution instruments the allreduce step")
    idx, mask, dense, labels, *plan = staged
    emb_cfg = trainer.store.cfg
    flat_idx = jnp.asarray(np.asarray(idx).reshape(-1))
    B = idx.shape[0]
    T = trainer.layout.total_len

    # --- telescoping cumulative ablation (primary): remove stages
    # biggest-first; successive differences sum EXACTLY to the full step,
    # so the account is complete by construction. A stage's delta is its
    # marginal cost GIVEN the stages removed before it — shared/overlapped
    # time is charged to the earliest-removed stage that exposes it.
    holder = [ws.table, trainer.pack_dense()]
    times = []
    # every call donates the table; `holder` tracks the newest live arrays
    # and the finally rebinds them, so a transient failure anywhere in the
    # ablation leaves ws/trainer retry-able instead of pointing at deleted
    # buffers (the r3 BENCH loss was a transient error in exactly here).
    # The unablated anchor is measured HERE with the same loop (not taken
    # from the caller): the headline may run k-microbatch supersteps whose
    # per-step time amortizes the dispatch floor, while this account
    # telescopes the SINGLE-step program — the two anchors differ by
    # design and are both reported.
    try:
        for abl in ((), ("push",), ("push", "lookup"),
                    ("push", "lookup", "fwdbwd")):
            # the unablated anchor reuses the already-compiled step
            fn = (trainer._step_fn if not abl
                  else trainer._build_train_step(ablate=abl))
            times.append(_run_step_loop(trainer, fn, staged, n_loop,
                                        holder))
    finally:
        # see measure_step_floor: never rebind donated-away arrays
        if _all_alive(holder):
            ws.table = holder[0]
            trainer.params, trainer.opt_state = trainer.unpack_dense(
                holder[1])
    floor = measure_step_floor(trainer, ws, staged, n=n_loop)
    stages = {
        "sparse_push": times[0] - times[1],
        "lookup": times[1] - times[2],
        "dense_fwd_bwd": times[2] - times[3],
        "glue_residual": times[3] - floor,
        "dispatch_floor": floor,
    }

    # --- deferred-push pipeline A/B (flags.push_overlap): the inline
    # single step vs the real deferred pair (loss-path program + apply
    # program, dispatched back to back like train_pass) and the
    # loss-path program alone — the in-composed-step measurement that
    # keeps the overlap engine choice decision-grade per matrix point.
    overlap_ab = None
    if getattr(trainer, "push_overlap", False) \
            and trainer._defer_step_fn is not None:
        holder = [ws.table, trainer.pack_dense()]
        try:
            t_pair = _run_defer_loop(trainer, staged, n_loop, holder,
                                     with_apply=True)
            t_loss = _run_defer_loop(trainer, staged, n_loop, holder,
                                     with_apply=False)
        finally:
            if _all_alive(holder):
                ws.table = holder[0]
                trainer.params, trainer.opt_state = trainer.unpack_dense(
                    holder[1])
        overlap_ab = {
            "inline_single_step": round(times[0], 6),
            "deferred_step_plus_apply": round(t_pair, 6),
            "deferred_loss_path_step": round(t_loss, 6),
            "note": "pair = both programs dispatched back to back (the "
                    "training loop's dataflow); loss_path = the "
                    "deferred step alone — what the AUC/D2H consumer "
                    "waits on when the apply overlaps the next pack",
        }

    # --- isolated stage times (secondary; shows cross-stage overlap) ---
    # fused-pull trainers measure the stages the fused step actually
    # runs: gather-pool pull, pooled model fwd/bwd, and the pooled-
    # cotangent expansion inside the push window — so the mh4d32/d128
    # matrix attributions name the fused stages, not the unfused ones.
    table, params = ws.table, trainer.params
    import optax
    from paddlebox_tpu.ops.seqpool_cvm import PooledSlots
    model = trainer.model
    seg = trainer.layout.segment_ids
    num_slots = trainer.layout.num_slots
    fused_pull = (getattr(trainer, "pull_engine", "gather_seqpool")
                  == "fused_gather_pool")
    mask_dev = jnp.asarray(np.asarray(mask))
    shows0 = jnp.asarray(np.asarray(mask).reshape(-1).astype(np.float32))
    clks0 = jnp.zeros_like(shows0)
    plan_t = tuple(plan) if plan and plan[0].shape[0] else None

    if fused_pull:
        L_hot = T // num_slots
        idx_dev = jnp.asarray(np.asarray(idx))

        def lookup_fn(fidx2, tbl):
            return sharded.fused_pull_pool(tbl, fidx2, emb_cfg,
                                           num_slots, L_hot)

        isolated = {"lookup": timed_repeat(lookup_fn, (idx_dev, table),
                                           k=k)}
        pooled0 = jax.jit(lookup_fn)(idx_dev, table)

        def fwdbwd(pooled, p):
            def loss_fn(pp, pin):
                logits = model.apply(pp, PooledSlots(pin), mask, dense,
                                     seg, num_slots)
                return jnp.mean(
                    optax.sigmoid_binary_cross_entropy(logits, labels))
            _, (gp, gpooled) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(p, pooled)
            return gpooled

        isolated["dense_fwd_bwd"] = timed_repeat(fwdbwd,
                                                 (pooled0, params), k=k)
        gpooled0 = jax.jit(fwdbwd)(pooled0, params)

        def push_fn(gpool, tbl):
            sg = sharded.pooled_grad_tokens(gpool, mask_dev, seg,
                                            num_slots)
            return sharded.push(tbl, flat_idx, sg, shows0, clks0,
                                emb_cfg, plan=plan_t)

        isolated["sparse_push"] = timed_repeat(push_fn, (gpooled0, table),
                                               k=k)
    else:
        def lookup_fn(fidx, tbl):
            return sharded.lookup(tbl, fidx, emb_cfg).reshape(
                B, T, emb_cfg.pull_width)

        isolated = {"lookup": timed_repeat(lookup_fn, (flat_idx, table),
                                           k=k)}
        pulled0 = jax.jit(lookup_fn)(flat_idx, table)

        def fwdbwd(pulled, p):
            def loss_fn(pp, pin):
                logits = model.apply(pp, pin, mask, dense, seg, num_slots)
                return jnp.mean(
                    optax.sigmoid_binary_cross_entropy(logits, labels))
            _, (gp, gpull) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
                p, pulled)
            return gpull

        isolated["dense_fwd_bwd"] = timed_repeat(fwdbwd, (pulled0, params),
                                                 k=k)
        gpull0 = jax.jit(fwdbwd)(pulled0, params)
        sgrad0 = jax.jit(
            lambda g: g[..., 2:].reshape(-1, emb_cfg.grad_width))(gpull0)

        def push_fn(sg, tbl):
            return sharded.push(tbl, flat_idx, sg, shows0, clks0, emb_cfg,
                                plan=plan_t)

        isolated["sparse_push"] = timed_repeat(push_fn, (sgrad0, table),
                                               k=k)

    attributed = float(sum(stages.values()))
    single = times[0]
    return {
        "stages": {n: round(s, 6) for n, s in stages.items()},
        "isolated": {n: round(s, 6) for n, s in isolated.items()},
        "push_overlap": ("on" if getattr(trainer, "push_overlap", False)
                         else "off"),
        "overlap_ab": overlap_ab,
        "attributed_seconds": round(attributed, 6),
        "single_step_seconds": round(single, 6),
        "headline_step_seconds": round(step_seconds, 6),
        "unattributed_seconds": round(single - attributed, 6),
        "coverage": round(attributed / single, 3) if single else 0.0,
        "method_overlap": "overlap_ab (when push_overlap is on) A/Bs the "
                  "inline step against the deferred step+apply pair in "
                  "the real programs",
        "method": "stages = telescoping cumulative ablation of the "
                  "SINGLE-step program (full -> -push -> -push-lookup "
                  "-> -push-lookup-fwdbwd -> no-op floor, bench-"
                  "identical donation loops; differences sum to the "
                  "measured single step). headline_step_seconds is the "
                  "bench's per-step time and amortizes the dispatch "
                  "floor over steps_per_dispatch microbatches, so it "
                  "can sit below the single-step anchor. isolated = "
                  "each stage repeated in one jit (over-counts XLA "
                  "overlap); device_get-terminated windows",
    }


# ---------------------------------------------------------------------------
# Sparse-push floor analysis: what the push SHOULD cost on this hardware.
#
# The stage attribution says what the push DOES cost; this derives the
# analytic floor of each push sub-stage so a regression alarms against a
# floor, not just against the chip's headline peaks (an 11ms push can pass
# an MFU audit while sitting 10x above its own physics). Stages mirror the
# binned-push pipeline: plan-H2D (host plan staging — rides the pack
# pipeline, NOT on the step's critical path), kernel DMA (packed-operand
# build + the kernel's tile streams), one-hot dots (the MXU merge), and
# the fused table update (one bandwidth pass over the table). Scatter-
# engine widths (no kernel geometry) get the scatter's bandwidth model.
# ---------------------------------------------------------------------------

def push_floor_analysis(emb_cfg, n_rows: int, tokens: int,
                        n_split: int = 2, peaks=None,
                        measured_push: float | None = None,
                        slack: float = 3.0, premerged: bool = False,
                        table_width: int | None = None,
                        unique_lanes: int | None = None) -> dict:
    """Per-stage analytic bounds of one sparse push + closure statements.

    peaks : (peak_bf16_flops, peak_hbm_bytes) or None (unknown hardware —
            bounds are reported as bytes/FLOPs only, closure abstains).
    measured_push : the attribution's sparse_push seconds, if available.
    premerged / table_width : the lane contract + physical table width
            the engine resolver keys on — pass what the step compiled
            with so `engine` names the real code path.
    unique_lanes : rows the premerged lanes actually touch (defaults to
            tokens — an upper bound; the fused engine's floor scales
            with THIS, which is the whole point of that engine).
    closed : True when the measured push sits within `slack` x the
            active engine's floor; otherwise a reason string naming the
            gap — the alarm line. `engines` carries the same statement
            per CANDIDATE engine at this geometry, so a non-closed
            point names the concrete flags.push_engine to force
            (best_engine) instead of a bare alarm.
    """
    from paddlebox_tpu.ops import pallas_kernels as pk

    geom = pk._bp_geometry(emb_cfg, n_rows)
    storage_f32 = emb_cfg.storage == "f32"
    width = int(table_width) if table_width is not None \
        else emb_cfg.row_width
    # THE resolver names the engine the step actually compiles with
    # (the same call the bench's per-point push_engine record makes)
    engine = pk.resolve_push_engine(emb_cfg, n_rows, premerged=premerged,
                                    storage_f32=storage_f32,
                                    table_width=width)
    gw = emb_cfg.grad_width
    rw = emb_cfg.row_width
    lanes = int(unique_lanes) if unique_lanes is not None else tokens
    peak_f, peak_b = peaks if peaks is not None else (None, None)

    def _bw_stage(nbytes, note):
        return {"bytes": int(nbytes),
                "bound_seconds": (round(nbytes / peak_b, 6)
                                  if peak_b else None),
                "note": note}

    def _engine_stages(name):
        """The three floor stages (constant keys across engines) for one
        candidate engine at this geometry, or None when the engine
        cannot engage here."""
        st: dict = {}
        if name == "binned_kernel":
            if geom is None:
                return None
            P, PP, G, SB = geom
            W = -(-(PP + 2) // 128) * 128
            TILE = pk._bp_tile(SB, G)
            RB = SB // G
            AW = pk._bp_acc_width(G, PP)
            tok_pad = tokens + TILE
            st["kernel_dma"] = _bw_stage(
                tok_pad * W * 4 * 2          # packed build write + DMA read
                + (n_rows // SB) * RB * AW * 4,   # grouped acc write
                "packed-operand build + double-buffered tile DMA + acc "
                "write")
            dot_flops = 2.0 * n_split * tokens * RB * AW
            st["onehot_dots"] = {
                "flops": dot_flops,
                "bound_seconds": (round(dot_flops / peak_f, 6)
                                  if peak_f else None),
                "note": f"{n_split}-plane one-hot MXU merge, RB={RB} "
                        f"AW={AW}"}
            st["fused_update"] = _bw_stage(
                n_rows * (rw * 4 * 2 + PP * 4),
                "one full-width XLA pass: table read+write + acc read")
            return st
        if name == "scatter_accumulate":
            if not storage_f32 \
                    or not pk.scatter_accumulate_supported(n_rows, width):
                return None
            st["kernel_dma"] = _bw_stage(
                lanes * (width * 4 * 2 + (gw + 3) * 4),
                f"per-unique-row DMA read + write-back at the physical "
                f"table width ({width} lanes) + merged payload read — "
                f"{lanes} lanes, O(unique rows), no full-table term")
            st["onehot_dots"] = {
                "flops": 0.0,
                "bound_seconds": 0.0 if peak_b else None,
                "note": "fused engine — row-wise VMEM update, no MXU "
                        "merge"}
            st["fused_update"] = _bw_stage(
                0,
                "optimizer applied in-kernel on the gathered rows — the "
                "O(table) update pass never runs")
            return st
        st["kernel_dma"] = _bw_stage(
            tokens * (gw + 3) * 4 * 2,
            "scatter payload write + read (XLA scatter engine)")
        st["onehot_dots"] = {
            "flops": 0.0, "bound_seconds": 0.0 if peak_b else None,
            "note": "scatter engine — no MXU merge"}
        st["fused_update"] = _bw_stage(
            n_rows * (rw * 4 * 2 + (gw + 3) * 4 * 2),
            "scatter-add accumulate + fused update pass over the table")
        return st

    def _floor_of(st):
        bounded = [s["bound_seconds"] for s in st.values()]
        return (round(sum(b for b in bounded if b is not None), 6)
                if any(b is not None for b in bounded) else None)

    stages = _engine_stages(engine)
    assert stages is not None, engine    # the resolver only names engageable engines
    # plan staging: order + block windows (+ dedup lanes at worst)
    stages = {"plan_h2d": {
        "bytes": tokens * 4 * 3 + 1024,
        "bound_seconds": None,
        "note": "host plan staged by the pack pipeline, overlapped with "
                "device compute — off the step's critical path; counted "
                "for completeness, excluded from the floor",
    }, **stages}
    # candidate-engine floors: every engine that COULD engage at this
    # geometry gets its own bound, so the closure statements below can
    # name the concrete engine to force when the active one is off its
    # physics (the doctor's push-floor rule consumes exactly this)
    engines: dict = {}
    for name in pk.PUSH_ENGINES:
        st = _engine_stages(name)
        if st is None:
            continue
        e = {"floor_seconds": _floor_of(st)}
        if name == "scatter_accumulate" and not premerged:
            e["note"] = ("requires premerged unique lanes "
                         "(flags.push_dedup_premerge)")
        if name == "binned_kernel":
            from paddlebox_tpu.config import flags as _flags
            if not _flags.binned_push:
                # auto skips it while the enable knob is off; a forced
                # flags.push_engine=binned_kernel bypasses the knob
                e["note"] = ("flags.binned_push is off — engages only "
                             "when forced")
        engines[name] = e
    out = {
        "engine": engine,
        "premerged": bool(premerged),
        "tokens": tokens,
        "unique_lanes": lanes,
        "table_rows": n_rows,
        "stages": stages,
        "floor_seconds": _floor_of(
            {k: v for k, v in stages.items() if k != "plan_h2d"}),
        "engines": engines,
        "measured_push_seconds": (round(measured_push, 6)
                                  if measured_push is not None else None),
    }
    finalize_push_floor(out, measured_push, slack)
    return out


def finalize_push_floor(floor: dict, measured_push: float | None,
                        slack: float = 3.0) -> None:
    """(Re)close a push_floor_analysis result once the attribution has
    measured the real push stage — mutates `floor` in place (the bench
    computes the floor before attribution runs and finalizes after).
    Closes the active engine's statement AND the per-candidate-engine
    statements, and names `best_engine` — the lowest-floor candidate —
    so an off-floor point suggests a concrete flags.push_engine force.
    """
    f = floor.get("floor_seconds")
    if measured_push is not None:
        floor["measured_push_seconds"] = round(measured_push, 6)

    def _close(bound, label):
        if bound is None:
            return "no peak table for this hardware (CPU smoke?)"
        if measured_push is None:
            return "no measured push stage (attribution absent)"
        if measured_push <= slack * max(bound, 1e-9):
            return True
        return (f"measured {measured_push*1e3:.2f}ms > {slack:.0f}x "
                f"{label} {bound*1e3:.2f}ms")

    closed = _close(f, "floor")
    floor["closed"] = (closed if closed is True or f is None
                       or measured_push is None else closed +
                       " — push is off its physics; check the pack "
                       "engine and plan staging before trusting the "
                       "step")
    engines = floor.get("engines") or {}
    best = None
    for name, e in engines.items():
        e["closed"] = _close(e.get("floor_seconds"),
                             f"{name} floor")
        fs = e.get("floor_seconds")
        if fs is not None and (best is None
                               or fs < engines[best]["floor_seconds"]):
            best = name
    if best is not None:
        floor["best_engine"] = best
