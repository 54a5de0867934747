"""Pass/day lifecycle façade — the BoxWrapper/BoxHelper singleton surface.

Reference (box_wrapper.h:419-424, 487-494, 625; pybind box_helper_py.cc:40-110):
the user-facing lifecycle is

    dataset.set_date(d)        → BoxHelper::SetDate
    dataset.begin_pass()       → BoxWrapper::BeginPass
    exe.train_from_dataset(..) → hot loop (§3.1), join/update FlipPhase
    dataset.end_pass(save)     → BoxWrapper::EndPass
    box.save_base/save_delta   → sparse checkpoint planes

Here the singleton owns the host embedding store, the metric registry, and
the phase bit; `Trainer.train_pass` does the per-pass HBM working-set build
(BeginFeedPass/EndFeedPass equivalent) internally, so BeginPass/EndPass at
this level is bookkeeping + persistence policy, which matches the reference's
split of labor between BoxHelper (data) and BoxPS (table).
"""

from __future__ import annotations

import time
from typing import Any

from paddlebox_tpu import monitor
from paddlebox_tpu.embedding import HostEmbeddingStore, tiering
from paddlebox_tpu.metrics.metric import MetricRegistry

JOIN_PHASE = 1
UPDATE_PHASE = 0


class BoxPS:
    """Owns the sparse store + metrics + pass/phase state for one job."""

    def __init__(self, store: HostEmbeddingStore,
                 metrics: MetricRegistry | None = None):
        self.store = store
        self.metrics = metrics or MetricRegistry()
        self.metrics.phase = JOIN_PHASE
        self.date: int | None = None
        self.pass_id = 0
        self.in_pass = False
        self._pass_t0 = 0.0
        # multi-host lifecycle (attach_collectives): lockstep barriers at
        # the pass boundaries + the heartbeat/watchdog pair
        self._col = None
        self._heartbeat = None

    # ---- multi-host lifecycle (ISSUE 5) ----

    def attach_collectives(self, collectives, heartbeat=None) -> None:
        """Make the pass lifecycle world-synchronous: ``begin_pass`` and
        ``end_pass`` barrier over the rendezvous store so no rank trains a
        pass the world has not entered (the reference's MPICluster barrier
        around BeginPass/EndPass, box_wrapper.h:415). With a
        ``HeartbeatMonitor``, the barriers poll its watchdog — a dead or
        stalled peer surfaces as a named-rank PeerLost/PeerStalled error
        instead of the bare store timeout — and each boundary publishes a
        fresh heartbeat so peers see this rank's pass progress
        immediately.

        Re-attachable: after an elastic world re-formation the driver (or
        ``Trainer.recover_world``) attaches the NEW generation's
        collectives + heartbeat — pass barriers then ride the new
        generation's store namespace, so a fenced straggler's stale
        arrivals can never satisfy them."""
        self._col = collectives
        self._heartbeat = heartbeat
        if heartbeat is not None and getattr(collectives, "watchdog",
                                             None) is None:
            collectives.watchdog = heartbeat

    def abort_pass(self, reason: str = "") -> None:
        """Close an open pass WITHOUT the end-of-pass snapshot/barrier —
        the elastic drain path: a peer failure unwound the step loop
        mid-pass, the world is about to re-form, and the normal
        ``end_pass`` barrier would hang on the dead rank. Safe when no
        pass is open (no-op). The telemetry pass scope is aborted so the
        flight record is not committed for a half-trained pass."""
        if not self.in_pass:
            return
        self.in_pass = False
        monitor.hub().abort_pass(reason=reason or "pass aborted")
        monitor.event("pass_aborted", pass_id=int(self.pass_id),
                      reason=reason[:200])

    @property
    def phase(self) -> int:
        """Single source of truth lives in the metric registry, which gates
        accumulation by phase."""
        return self.metrics.phase

    # ---- lifecycle (box_wrapper.h:419-424) ----

    def set_date(self, date: int) -> None:
        self.date = int(date)

    @monitor.span("box_begin_pass")
    def begin_pass(self) -> None:
        if self.in_pass:
            raise RuntimeError("begin_pass while a pass is open")
        if self._col is not None:
            # lockstep: no rank opens pass N+1 until the world is ready
            self._col.barrier("begin_pass")
        self.in_pass = True
        self.pass_id += 1
        self._pass_t0 = time.time()
        # telemetry pass scope: everything until end_pass — trainer steps,
        # worker threads, checkpoint commits — is tagged with this pass
        monitor.hub().begin_pass(self.pass_id, phase=self.phase)
        if self._heartbeat is not None:
            self._heartbeat.publish()     # peers see the new pass at once

    def end_pass(self, need_save_delta: bool = False,
                 delta_path: str | None = None,
                 checkpointer=None, trainer=None,
                 dataset=None, publisher=None) -> dict[str, Any]:
        """Close the pass; optionally snapshot the delta plane
        (BoxPSDataset.end_pass(need_save_delta), dataset.py:1124).

        With ``checkpointer`` (a PassCheckpointer) + ``trainer``, commits
        the full crash-safe pass snapshot instead: dense + optimizer +
        sparse base-or-delta + metrics + cursor, atomically manifested —
        the need_save_delta flow upgraded to a resumable one. ``dataset``
        additionally records the shuffle RNG cursor
        (SlotDataset.shuffle_state) so a resumed rank draws the identical
        next-pass permutation. With attached collectives the snapshot is
        followed by a world barrier: no rank starts the next pass before
        every rank's snapshot committed (the election's common prefix
        stays one pass deep at most).

        ``publisher`` (a serving.ServingPublisher, requires ``trainer``)
        ships this pass's model to the serving plane — the reference's
        per-pass xbox delta (SaveDelta → donefile → ad servers). Publish
        runs AFTER the crash-safe snapshot; a publish failure degrades
        (warn + telemetry, serving keeps its last good version) instead
        of killing the pass loop — training is the producer, and the
        serving side's staleness reporting is the alarm."""
        if not self.in_pass:
            raise RuntimeError("end_pass without begin_pass")
        self.in_pass = False
        out = self._close_pass(need_save_delta, delta_path, checkpointer,
                               trainer, dataset, publisher)
        # flight-record commit LAST: checkpoint/delta durations and bytes
        # above land in this pass's stats_delta and event stream
        out["flight_record"] = monitor.hub().end_pass(metrics=self.metrics)
        # live doctor (flags.doctor_live): end_pass above ran the rule
        # set over the committed records and emitted doctor.finding
        # events; surface the findings to the driver too — the operator
        # loop reads the end_pass dict, not the event stream
        findings = monitor.hub().last_doctor_findings
        if findings:
            out["doctor"] = findings
        if self._heartbeat is not None:
            self._heartbeat.publish()
        if self._col is not None:
            self._col.barrier("end_pass")
        return out

    @monitor.span("box_end_pass")
    def _close_pass(self, need_save_delta, delta_path, checkpointer,
                    trainer, dataset, publisher) -> dict[str, Any]:
        """What end_pass does for the pass before its flight record
        commits — one span, closed while the pass scope is still open so
        that its record carries the pass."""
        out: dict[str, Any] = {"pass_id": self.pass_id,
                               "seconds": time.time() - self._pass_t0}
        if checkpointer is not None:
            if trainer is None:
                raise ValueError("end_pass(checkpointer=...) needs trainer")
            shuffle_state = (dataset.shuffle_state()
                             if dataset is not None
                             and hasattr(dataset, "shuffle_state")
                             else None)
            out["snapshot"] = checkpointer.save(trainer, box=self,
                                                metrics=self.metrics,
                                                shuffle_state=shuffle_state)
        if need_save_delta:
            if delta_path is None:
                raise ValueError("need_save_delta requires delta_path")
            out["delta_file"] = self.store.save_delta(
                delta_path, pass_id=self.pass_id)
        if publisher is not None:
            if trainer is None:
                raise ValueError("end_pass(publisher=...) needs trainer "
                                 "(the dense params to publish)")
            try:
                out["publish"] = publisher.publish(
                    self.store, trainer.eval_params(),
                    pass_id=self.pass_id)
            except Exception as e:   # noqa: BLE001 — degrade, don't die
                import warnings
                out["publish"] = {"error": repr(e)}
                monitor.counter_add("serving.publish_failures")
                monitor.event("serving_publish_failed",
                              pass_id=int(self.pass_id),
                              error=repr(e)[:300])
                warnings.warn(f"serving publish failed for pass "
                              f"{self.pass_id} ({e!r}); serving stays on "
                              f"its last good version")
        # pass-boundary tier re-evaluation: spill-backed stores re-score
        # their RAM hot tier off this pass's observed per-row traffic
        # (embedding/tiering.py) — BEFORE the flight-record commit so the
        # tiering.* counter deltas land in this pass's stats_delta
        tier = tiering.end_pass_rebalance(self.store)
        if tier is not None:
            out["tiering"] = tier
        # HBM replica-tier refresh (flags.use_replica_cache): rebuilt off
        # the ranking the rebalance above just re-scored, and BEFORE the
        # flight-record commit so the pass's replica-hit delta lands in
        # this pass's stats_delta
        if trainer is not None and hasattr(trainer,
                                           "refresh_replica_boundary"):
            trainer.refresh_replica_boundary()
        # pass-boundary exchange-wire adaptation (flags.exchange_adaptive):
        # fleet-driven scopes adapt here, mirroring the tier re-eval —
        # BEFORE the flight-record commit so the decision (and any
        # exchange_wire_adapted event) lands in this pass's record
        if trainer is not None and hasattr(trainer, "adapt_wire_boundary"):
            wire_next = trainer.adapt_wire_boundary()
            if wire_next is not None:
                out["exchange_wire_next"] = wire_next
        # self-healing boundary (flags.self_healing): the remediation
        # loop consumes the live doctor findings and applies at most one
        # guarded action — BEFORE the flight-record commit so the
        # remediation record + before-deltas land in this pass's record
        if trainer is not None and hasattr(trainer, "remediation_boundary"):
            healed = trainer.remediation_boundary()
            if healed is not None:
                out["remediation"] = healed
        return out

    def flip_phase(self) -> None:
        """Join↔update flip (box_wrapper.h:625); metrics follow the phase.

        (The reference's SetTestMode is covered by Trainer.eval_pass /
        PassWorkingSet(test_mode=True) — no separate box-level flag.)"""
        self.metrics.flip_phase()
        monitor.context.set_phase(self.phase)
        monitor.event("flip_phase", phase=self.phase)

    # ---- table hygiene ----

    def shrink_table(self, min_show: float, decay: float = 1.0) -> int:
        return self.store.shrink(min_show, decay)

    # ---- metric surface (box_helper_py.cc:87-110) ----

    def init_metric(self, name: str, **kw) -> None:
        self.metrics.init_metric(name, **kw)

    def get_metric_msg(self, name: str) -> dict[str, float]:
        return self.metrics.get_metric_msg(name)
