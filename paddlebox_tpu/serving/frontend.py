"""BatchingFrontend — the request-side batcher over a ServingServer.

The reference serves "heavy traffic from millions of users" by batching
request streams into the predictor's fixed batch shape (the inference
engine scores per-batch; PAPER.md's minutes-fresh models meet
milliseconds-level scoring). Here: callers :meth:`submit` single examples
and get a Future; a dispatcher thread coalesces up to ``max_batch``
requests (or whatever arrived within ``max_wait_s``), pads to the ONE
compiled batch shape — a varying batch size would recompile the jitted
forward mid-traffic — scores once, and scatters results.

Latency accounting is the product: per-request wall time (submit →
result) lands in a TIME-WINDOWED reservoir (``serving/obs.py`` —
ISSUE 19: a since-start blend hides a swap-induced p99 step behind
hours of pre-swap samples); :meth:`stats` reports recent-traffic
p50/p99/max, batch-size distribution, and failures.
``flags.serving_trace_sample`` opens a ``serve/wait`` span around every
Nth batch's coalesce window, splitting queue wait from score time in
the merged world trace.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from concurrent.futures import Future

import numpy as np

from paddlebox_tpu import monitor
from paddlebox_tpu.config import flags
from paddlebox_tpu.monitor import context as mon_ctx
from paddlebox_tpu.serving.obs import LatencyWindow


class _Request:
    __slots__ = ("ids", "mask", "dense", "future", "t0")

    def __init__(self, ids, mask, dense):
        self.ids = ids
        self.mask = mask
        self.dense = dense
        self.future: Future = Future()
        self.t0 = time.perf_counter()


class BatchingFrontend:
    def __init__(self, server, *, max_batch: int = 256,
                 max_wait_s: float = 0.002, max_latencies: int = 100_000,
                 window_s: float | None = None):
        self.server = server
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_s)
        self._q: queue.Queue[_Request | None] = queue.Queue()
        # windowed, not since-start: stats()/flight records must report
        # RECENT traffic (flags.serving_window_s; a 0 record cadence
        # still wants a sane stats window)
        self._lat = LatencyWindow(
            float(flags.serving_window_s or 30.0)
            if window_s is None else float(window_s),
            cap=int(max_latencies))
        self._lat_lock = threading.Lock()
        self._gathers = 0
        self._batches = 0
        self._batched_reqs = 0
        self._failures = 0
        self._inflight = 0                 # submitted, not yet resolved
        self._inflight_lock = threading.Lock()
        self._thread: threading.Thread | None = None
        self._stopping = False

    # ---- client side -----------------------------------------------------

    def submit(self, ids: np.ndarray, mask: np.ndarray,
               dense: np.ndarray | None = None) -> Future:
        """One example: ids uint64 (T,), mask bool (T,), dense f32 (F,).
        Resolves to the example's probability (scalar, or (tasks,) for
        multi-task models)."""
        if self._thread is None:
            raise RuntimeError("frontend not started (call start())")
        r = _Request(np.asarray(ids), np.asarray(mask, bool),
                     None if dense is None else np.asarray(dense,
                                                           np.float32))
        # inflight accounting rides the future's done-callback (fires
        # exactly once however the future resolves — result, exception,
        # or the stop()-drain failsafe), so the router's least-loaded
        # signal can never leak on a failure path. Registered BEFORE the
        # put: dispatch may resolve the future first.
        with self._inflight_lock:
            self._inflight += 1
        r.future.add_done_callback(self._dec_inflight)
        self._q.put(r)
        # stop() may have drained the queue between the thread check and
        # the put — a request landing in a dead queue would leave the
        # caller blocked on a forever-pending future
        if self._stopping:
            try:
                r.future.set_exception(
                    RuntimeError("frontend stopped before dispatch"))
            # pblint: disable=silent-except -- lost the resolve race:
            # drain/dispatch already set this future, which is the
            # outcome this failsafe exists to guarantee
            except Exception:   # noqa: BLE001
                pass
        return r.future

    def score(self, ids, mask, dense=None, timeout: float = 30.0):
        """Blocking convenience wrapper around :meth:`submit`."""
        return self.submit(ids, mask, dense).result(timeout=timeout)

    def _dec_inflight(self, _f) -> None:
        with self._inflight_lock:
            self._inflight -= 1

    @property
    def inflight(self) -> int:
        """Requests submitted but not yet resolved — the load signal the
        fleet router's two-choice least-loaded dispatch compares."""
        return self._inflight

    # ---- dispatcher ------------------------------------------------------

    def start(self) -> "BatchingFrontend":
        if self._thread is not None:
            return self
        self._stopping = False
        self._thread = mon_ctx.spawn(self._run, name="serving-frontend")
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stopping = True
        self._q.put(None)              # wake the dispatcher
        self._thread.join(timeout=30)
        self._thread = None
        # fail whatever is still queued — a stopped frontend must not
        # leave callers blocked on forever-pending futures
        while True:
            try:
                r = self._q.get_nowait()
            except queue.Empty:
                break
            if r is not None and not r.future.done():
                try:
                    r.future.set_exception(
                        RuntimeError("frontend stopped before dispatch"))
                # pblint: disable=silent-except -- lost the resolve race:
                # submit()'s failsafe already set this future; either
                # way the caller is unblocked
                except Exception:   # noqa: BLE001
                    pass

    def _gather(self) -> list[_Request]:
        """Block for the first request, then coalesce until max_batch or
        the max_wait deadline."""
        first = self._q.get()
        if first is None:
            return []
        # sampled request tracing: every Nth batch's coalesce window is
        # a serve/wait span — the queue-wait half of request latency
        # (serve/score is the server's half). 0 = one flag check.
        self._gathers += 1
        n = int(flags.serving_trace_sample)
        ctx = (monitor.span("serve/wait", max_batch=self.max_batch)
               if n > 0 and self._gathers % n == 0
               else contextlib.nullcontext())
        with ctx:
            batch = [first]
            deadline = time.perf_counter() + self.max_wait_s
            while len(batch) < self.max_batch:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    r = self._q.get(timeout=remaining)
                except queue.Empty:
                    break
                if r is None:
                    break
                batch.append(r)
        return batch

    def _run(self) -> None:
        while not self._stopping:
            batch = self._gather()
            if not batch:
                continue
            # dense presence changes the predict signature — a mixed
            # batch would silently drop one side's features (or crash the
            # stack); dispatch each homogeneous group on its own
            with_dense = [r for r in batch if r.dense is not None]
            without = [r for r in batch if r.dense is None]
            for group in (with_dense, without):
                if group:
                    self._dispatch(group)

    def _dispatch(self, batch: list[_Request]) -> None:
        # claim each future before scoring (executor-style): a fleet
        # router's hedge loser cancelled while still QUEUED here is a
        # PENDING future whose cancel() succeeded — fulfilling it would
        # raise InvalidStateError out of the dispatch thread. Claiming
        # drops it from the batch and makes any later cancel() a no-op.
        batch = [r for r in batch
                 if r.future.set_running_or_notify_cancel()]
        if not batch:
            return
        n = len(batch)
        try:
            ids = np.stack([r.ids for r in batch])
            mask = np.stack([r.mask for r in batch])
            dense = (np.stack([r.dense for r in batch])
                     if batch[0].dense is not None else None)
            if n < self.max_batch:
                # pad to the ONE compiled shape (zero ids + all-false
                # mask rows pull zeros; their scores are sliced off)
                pad = self.max_batch - n
                ids = np.concatenate(
                    [ids, np.zeros((pad, ids.shape[1]), ids.dtype)])
                mask = np.concatenate(
                    [mask, np.zeros((pad, mask.shape[1]), bool)])
                if dense is not None:
                    dense = np.concatenate(
                        [dense, np.zeros((pad, dense.shape[1]),
                                         np.float32)])
            out = self.server.predict(ids, mask, dense)[:n]
        except Exception as e:   # noqa: BLE001 — fail the batch, not the loop
            self._failures += n
            monitor.counter_add("serving.frontend_failures", n)
            for r in batch:
                if not r.future.done():
                    r.future.set_exception(e)
            return
        now = time.perf_counter()
        wall = time.time()
        lats = [(now - r.t0) * 1e3 for r in batch]
        with self._lat_lock:
            for ms in lats:
                self._lat.add(ms, now=wall)
        self._batches += 1
        self._batched_reqs += n
        monitor.counter_add("serving.frontend_requests", n)
        for i, r in enumerate(batch):
            r.future.set_result(out[i])

    # ---- accounting ------------------------------------------------------

    def stats(self) -> dict:
        """count/failures are cumulative; the percentiles are over the
        latency WINDOW (recent traffic only — an empty window after an
        idle spell reports count with no percentiles)."""
        with self._lat_lock:
            snap = self._lat.snapshot()
        if not snap["count"]:
            return {"count": 0, "failures": self._failures}
        return {
            "count": int(self._batched_reqs),
            "failures": int(self._failures),
            "batches": int(self._batches),
            "mean_batch": round(self._batched_reqs
                                / max(self._batches, 1), 2),
            "window_count": int(snap["count"]),
            "p50_ms": round(snap["p50_ms"], 3),
            "p99_ms": round(snap["p99_ms"], 3),
            "max_ms": round(snap["max_ms"], 3),
        }
