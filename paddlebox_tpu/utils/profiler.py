"""Tracing, counters, nan guards, and debug dump streams.

The reference's observability stack (SURVEY.md §5):

- ``RecordEvent`` RAII spans + chrome-trace timelines — platform/profiler.{h,cc}
  (RecordEvent, profiler.cc:303) and device_tracer.cc:815 (CUPTI → chrome
  trace). Here: :class:`RecordEvent` spans collected by a process-global
  profiler, exported with :func:`export_chrome_trace`; device-side traces
  are ``jax.profiler`` captures (``flags.trace_device``, read back by
  ``python -m paddlebox_tpu.monitor.trace --device``), which play the
  CUPTI role on TPU. Spans are tagged with the current
  pass/step (``monitor.context``) and the buffer is a bounded ring
  (``flags.profiler_max_events``) with a dropped-span counter — a day-scale
  run can leave the profiler on without growing without limit.
- global stat counters — platform/monitor.h ``StatRegistry``/``STAT_ADD``
  (monitor.h:76,129). The registry now lives in
  :mod:`paddlebox_tpu.monitor.registry` (the telemetry hub owns it);
  ``StatRegistry``/``STATS``/``stat_add`` here are back-compat shims over
  the same object — new code should use ``monitor.counter_add``.
- nan/inf safety net — ``FLAGS_check_nan_inf`` + details/nan_inf_utils
  (CheckBatchNanOrInfRet dumps the whole scope on trip,
  boxps_worker.cc:575-580). Here: :func:`find_nonfinite` walks a pytree and
  :func:`dump_tree` snapshots it to an .npz next to the raised error
  (wired into the trainer via ``flags.check_nan_inf``).
- per-batch field/param dump threads — DumpField/DumpParam
  (device_worker.cc; dump channel + threads boxps_trainer.cc:96-108, proto
  knobs trainer_desc.proto:39-45). Here: :class:`DumpStream`, a
  background-thread line writer the trainer feeds per batch; the writer
  thread inherits the trainer's pass/step context so its telemetry is
  tagged.
"""

from __future__ import annotations

import collections
import json
import os
import queue
import threading
import time
from typing import Any, Iterable

import numpy as np

from paddlebox_tpu.config import flags as _flags
from paddlebox_tpu.monitor import context as _mon_ctx
from paddlebox_tpu.monitor.registry import STATS, StatRegistry  # noqa: F401

# ---------------------------------------------------------------------------
# RecordEvent spans + chrome trace
# ---------------------------------------------------------------------------

_events: collections.deque = collections.deque()
_events_lock = threading.Lock()
_enabled = False
_dropped = 0
_t0 = time.perf_counter()


def enable_profiler() -> None:
    """Start collecting RecordEvent spans (profiler.cc EnableProfiler)."""
    global _enabled, _t0, _dropped
    with _events_lock:
        _events.clear()
        _dropped = 0
        _t0 = time.perf_counter()
    _enabled = True


def disable_profiler() -> None:
    global _enabled
    _enabled = False


def profiler_events() -> list[dict]:
    with _events_lock:
        return list(_events)


def dropped_spans() -> int:
    """Spans evicted from the ring since enable_profiler() (satellite of
    the bounded buffer: a day-scale run drops oldest-first past
    ``flags.profiler_max_events`` instead of growing unbounded)."""
    return _dropped


def _append_event(ev: dict) -> None:
    global _dropped
    cap = _flags.profiler_max_events
    with _events_lock:
        if cap and len(_events) >= cap:
            _events.popleft()
            _dropped += 1
            STATS.add("profiler.dropped_spans", 1)
        _events.append(ev)


def _ctx_args(extra: dict | None = None) -> dict | None:
    """pass/step tags for a chrome event (None outside a pass, no args key)."""
    c = _mon_ctx.current()
    if c.pass_id is None and not extra:
        return None
    args = {} if c.pass_id is None else {"pass_id": c.pass_id,
                                         "step": c.step}
    if extra:
        args.update(extra)
    return args


def record_span(name: str, start: float, end: float,
                args: dict | None = None) -> None:
    """Record one complete span (perf_counter endpoints). The
    ``start >= _t0`` guard drops spans that straddle an enable_profiler()
    reset — they belong to neither trace."""
    if not _enabled or start < _t0:
        return
    ev = {
        "name": name,
        "ph": "X",
        "ts": (start - _t0) * 1e6,        # chrome trace is in µs
        "dur": (end - start) * 1e6,
        "pid": os.getpid(),
        "tid": threading.get_ident() & 0xFFFFFFFF,
    }
    a = _ctx_args(args)
    if a:
        ev["args"] = a
    _append_event(ev)


def record_instant(name: str, args: dict | None = None) -> None:
    """Record a chrome-trace instant marker (``ph: i``) — pass boundaries
    and checkpoint commits use these so a Perfetto timeline reads in pass
    units."""
    if not _enabled:
        return
    ev = {
        "name": name,
        "ph": "i",
        "s": "g",                          # global-scope instant line
        "ts": (time.perf_counter() - _t0) * 1e6,
        "pid": os.getpid(),
        "tid": threading.get_ident() & 0xFFFFFFFF,
    }
    a = _ctx_args(args)
    if a:
        ev["args"] = a
    _append_event(ev)


class RecordEvent:
    """Named span: context manager or decorator.

    ``with RecordEvent("translate"): ...`` records a complete-event when the
    profiler is enabled; negligible cost when disabled. (For spans that
    should ALSO reach the telemetry event stream, use ``monitor.span`` —
    it forwards here when the profiler is on.)
    """

    def __init__(self, name: str):
        self.name = name
        self._start: float | None = None

    def __enter__(self):
        # latch enabled-ness here: if the profiler flips on mid-span the
        # half-open span is skipped rather than emitted with a garbage start
        self._start = time.perf_counter() if _enabled else None
        return self

    def __exit__(self, *exc):
        if _enabled and self._start is not None:
            record_span(self.name, self._start, time.perf_counter())
        return False

    def __call__(self, fn):
        def wrapped(*a, **kw):
            with RecordEvent(self.name):
                return fn(*a, **kw)
        wrapped.__name__ = getattr(fn, "__name__", self.name)
        return wrapped


def export_chrome_trace(path: str) -> int:
    """Write collected spans as a chrome://tracing / Perfetto JSON file.

    Returns the number of events written (the profiler.proto → chrome-trace
    path of device_tracer.cc:815, host spans only). Includes the
    pass-boundary / checkpoint-commit instant markers recorded via
    :func:`record_instant`."""
    evs = profiler_events()
    # atomic tmp->fsync->replace: a crash mid-export must not leave a torn
    # trace under the final name (Perfetto half-loads truncated JSON, and
    # a monitoring cron shipping the file would ship the torn copy)
    from paddlebox_tpu.utils.checkpoint import atomic_file
    with atomic_file(path) as tmp:
        with open(tmp, "w") as f:
            json.dump({"traceEvents": evs, "displayTimeUnit": "ms"}, f)
    return len(evs)


# ---------------------------------------------------------------------------
# StatRegistry (platform/monitor.h) — back-compat shims over
# monitor.registry.STATS; new call sites use monitor.counter_add/gauge_set.
# ---------------------------------------------------------------------------

def stat_add(name: str, value: float = 1.0) -> None:  # STAT_ADD(name, v)
    STATS.add(name, value)


def stat_get(name: str) -> float:
    return STATS.get(name)


def stat_set(name: str, value: float) -> None:
    STATS.set(name, value)


# ---------------------------------------------------------------------------
# nan/inf guard (details/nan_inf_utils)
# ---------------------------------------------------------------------------

def host_local(a: Any) -> np.ndarray:
    """np.asarray that survives multi-host sharded jax arrays: falls back to
    concatenating this host's addressable shards along axis 0 (right for
    batch-dim sharding; each host dumps its own slice)."""
    try:
        return np.asarray(a)
    except RuntimeError:
        shards = getattr(a, "addressable_shards", None)
        if not shards:
            raise
        return np.concatenate([np.asarray(s.data) for s in shards], axis=0)


def find_nonfinite(tree: Any) -> list[str]:
    """Paths of pytree leaves containing nan/inf (empty list = all finite)."""
    import jax
    bad = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        arr = host_local(leaf)
        if np.issubdtype(arr.dtype, np.floating) and not np.isfinite(arr).all():
            bad.append(jax.tree_util.keystr(path))
    return bad


def dump_tree(path: str, tree: Any) -> str:
    """Snapshot a pytree to ``<path>.npz`` (the dump-all-scope behavior of
    CheckBatchNanOrInfRet's trip handler). Returns the file written."""
    import jax
    flat = {}
    for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat[jax.tree_util.keystr(p)] = host_local(leaf)
    out = path if path.endswith(".npz") else path + ".npz"
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    np.savez(out, **flat)
    return out


# ---------------------------------------------------------------------------
# DumpStream (DumpField/DumpParam channel + threads)
# ---------------------------------------------------------------------------

def _col_formatter(v):
    """Per-instance formatter for one dump column, run on the writer thread.

    Accepts a 1-D array (scalar per instance), a 2-D array (multi-value
    float slot — comma-joined), or an ``(ids, mask)`` pair (sparse slot —
    the masked ids comma-joined). Keeping the per-instance string work here
    is the point of the deferred job: the training thread never formats.
    """
    if isinstance(v, tuple):
        ids, mask = v
        return lambda i: ",".join(
            str(x) for x, ok in zip(ids[i], mask[i]) if ok)
    if getattr(v, "ndim", 1) >= 2:
        return lambda i: ",".join(f"{x:g}" for x in v[i])
    return lambda i: f"{v[i]}"


class DumpStream:
    """Background-thread line dumper.

    The trainer enqueues formatted lines per batch; a writer thread drains
    the queue to ``path`` — same shape as the reference's dump channel +
    dump_thread_num threads writing debug fields to (HDFS-bound) files
    (boxps_trainer.cc:96-108). Local filesystem here; pluggable later.
    The writer thread inherits the spawner's pass/step context
    (``monitor.context.spawn``) so its line counters and telemetry events
    are attributed to the pass being dumped.
    """

    def __init__(self, path: str, mode: str = "w"):
        self.path = path
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._q: queue.Queue[str | tuple | None] = queue.Queue(maxsize=4096)
        self._error: BaseException | None = None
        self._f = open(path, mode)
        self._thread = _mon_ctx.spawn(self._drain, name="pbtpu-dump-writer")
        self._thread.start()

    def _drain(self):
        from paddlebox_tpu.monitor.hub import _HUB
        while True:
            job = self._q.get()
            if job is None:
                break
            if self._error is not None:  # after a write error: keep
                continue                 # consuming so producers never block
            try:
                if isinstance(job, str):
                    self._f.write(job)
                    STATS.add("dump_stream.lines", 1)
                else:  # deferred field-formatting job (see write_fields)
                    step, preds, labels, cols = job
                    fmts = {k: _col_formatter(v) for k, v in cols.items()}
                    out = []
                    for i in range(len(preds)):
                        tail = "".join(f" {k}:{fmt(i)}"
                                       for k, fmt in fmts.items())
                        out.append(f"{step} {i} {preds[i]:.6f} "
                                   f"{labels[i]:g}{tail}\n")
                    self._f.write("".join(out))
                    STATS.add("dump_stream.lines", len(out))
                    if _HUB._enabled:    # tagged from THIS writer thread
                        _HUB.event("dump_fields_written", lines=len(out),
                                   dump_step=int(step))
            except BaseException as e:
                self._error = e

    def write(self, line: str) -> None:
        if not line.endswith("\n"):
            line += "\n"
        self._q.put(line)

    def write_fields(self, step: int, preds: Iterable[float],
                     labels: Iterable[float],
                     extra: dict[str, Iterable[Any]] | None = None) -> None:
        """Per-instance dump: ``step <i> pred label [k:v ...]`` lines —
        DumpField's instance-major text format. Only the (cheap) host
        conversion happens here; the per-instance string formatting runs on
        the writer thread so the training loop isn't serialized behind it."""
        preds = host_local(preds).reshape(-1)
        labels = host_local(labels).reshape(-1)

        def col(v):
            if isinstance(v, tuple):      # (ids, mask) sparse slot pair
                return tuple(host_local(x) for x in v)
            v = host_local(v)
            return v if getattr(v, "ndim", 1) >= 2 else v.reshape(-1)

        cols = {k: col(v) for k, v in (extra or {}).items()}
        self._q.put((int(step), preds, labels, cols))

    def close(self) -> None:
        self._q.put(None)
        self._thread.join()
        self._f.close()
        if self._error is not None:  # surface a mid-stream write failure
            raise RuntimeError(
                f"DumpStream writer failed for {self.path}") from self._error

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
