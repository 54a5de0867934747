"""Per-stage wall-clock timers, hub-aware.

The reference instruments every per-card stage: read/trans/cal/sync/main
times printed by ``log_for_profile`` (boxps_worker.cc:746-759) plus the
pull/push/dense-sync timers in DeviceBoxData (box_wrapper.h:375-391).
``StageTimers`` is that instrument, moved under the telemetry hub: totals
feed the per-pass flight record's stage split (the trainer diffs them at
pass boundaries), and when the hub's event stream is on each stage scope
additionally emits a tagged span event — so the "read" wait, the pack
thread's "translate", and the post-loop "drain" all land in the JSONL
with their pass/step identity; the scope is besides a profiler
annotation ``pbtpu/<prefix>/<stage>`` (``hub.annotate``), like every
``monitor.span``. A stage whose interval is also a registered span
(``timers("train", span="train_step")``) is one scope for both: the span
carries the annotation and the events, the stage only the total.
Disabled cost: the annotation and one global check per scope
(``utils.timer`` re-exports this class for back-compat).
"""

from __future__ import annotations

import contextlib
import time

from paddlebox_tpu.monitor.hub import _HUB, _Span, annotate


class StageTimers:
    def __init__(self, stages: list[str], emit_prefix: str = "stage"):
        self.total: dict[str, float] = {s: 0.0 for s in stages}
        self.count: dict[str, int] = {s: 0 for s in stages}
        self._emit_prefix = emit_prefix

    @contextlib.contextmanager
    def __call__(self, stage: str, span: str | None = None):
        """Time one scope of `stage`. With `span`, the scope is that
        ``monitor.span`` too and the stage emits nothing of its own, so
        one interval is never measured or emitted twice."""
        if span is None:
            scope = annotate(f"{self._emit_prefix}/{stage}")
        else:
            scope = _Span(_HUB, span, {}).__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(stage, time.perf_counter() - t0, emit=span is None)
            scope.__exit__(None, None, None)

    def add(self, stage: str, seconds: float, emit: bool = False) -> None:
        """Account `seconds` to `stage`: for a stage that is not one
        scope (the trainer's ``head`` ends inside the step loop)."""
        self.total[stage] = self.total.get(stage, 0.0) + seconds
        self.count[stage] = self.count.get(stage, 0) + 1
        h = _HUB
        if emit and h._enabled:
            rec = h._record("span", f"{self._emit_prefix}/{stage}", None)
            rec["dur_s"] = seconds
            h._dispatch(rec)

    def mean(self, stage: str) -> float:
        c = self.count.get(stage, 0)
        return self.total.get(stage, 0.0) / c if c else 0.0

    def snapshot(self) -> dict[str, float]:
        """Current totals (the flight record's stage-split input)."""
        return dict(self.total)

    def report(self) -> str:
        """One log_for_profile-style line."""
        parts = [f"{s}={self.total[s]:.3f}s/{self.count[s]}"
                 for s in self.total]
        return " ".join(parts)

    def reset(self) -> None:
        for s in self.total:
            self.total[s] = 0.0
            self.count[s] = 0
