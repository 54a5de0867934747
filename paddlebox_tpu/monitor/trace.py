"""World trace — cross-rank distributed tracing over the telemetry hub.

The reference instruments every per-card stage (``log_for_profile``,
boxps_worker.cc:746-759) but only *per process*: an operator chasing a
slow pass across a fleet reads N disjoint logs and correlates them by
wall clock and eyesight. This module makes one pass ONE causal timeline:

- **Trace context** — inside a sampled pass (``flags.trace`` +
  ``flags.trace_sample_passes``) every hub record carries
  ``trace_id`` / ``span_id`` / ``parent_span_id``. The trace_id is
  deterministic (``<run>:<pass>``) so every rank of a run stamps the
  SAME id with zero coordination; span ids are process-unique. The
  span stack is a contextvar (threads spawned through
  ``monitor.context.spawn`` inherit it) with a pass-root fallback for
  plain threads — the same two-tier design as ``monitor.context``.
- **Flow points** — ``flow(kind, key, role)`` emits a ``trace.flow``
  event; points sharing ``(kind, key)`` across rank streams become
  Chrome flow arrows in the merged trace. The exchange stamps one per
  routed batch (key ``p<pass>.s<step>`` — deterministic, so no bytes
  cross the wire for tracing), and the publisher/serving pair stamps
  ``publish``/``v<version>`` so a serving swap links back to the
  ``end_pass`` that produced it (the trace ids also ride the donefile
  entry itself — the cross-process propagation).
- **Clock correction** — hosts disagree on wall time. The heartbeat
  plane (distributed/resilience.py) already round-trips through the
  rendezvous store; its payloads now carry publish wall-clock + an echo
  of each observed peer, which yields an NTP-style offset estimate per
  (observer, peer) pair, emitted as ``trace.clock_probe`` events.
  :func:`estimate_clock_offsets` reduces the probes to one offset per
  rank (relative to the lowest-named rank) and the merger shifts every
  rank's timestamps by it — skewed hosts land aligned.
- **Merged timeline** — :func:`merge_roots` turns N per-rank telemetry
  roots (local dirs or ``hdfs://`` roots, rotated segments — the same
  inputs as ``monitor/aggregate.py``) into ONE Chrome-trace-event JSON:
  rank → process, thread → thread, flight records as per-pass slices,
  spans as slices, flow arrows for the exchange and publish→swap edges.
  Open it in Perfetto (ui.perfetto.dev) or chrome://tracing.
- **Device capture** — ``flags.trace_device`` starts a ``jax.profiler``
  trace at every sampled ``begin_pass`` and stops it at ``end_pass`` or
  ``abort_pass`` (dump under ``trace_device_dir/pass-NNNNN``), on any
  backend. Every ``monitor.span`` and stage scope is in it as a
  ``pbtpu/<name>`` annotation with its ``pass_id`` and ``step``
  (``hub.annotate``), on the device events' clock. ``--device`` reads
  such a capture back (:func:`reduce_capture`): seconds and self
  seconds per span, launches and seconds per Pallas kernel
  (``names.KERNEL_NAMES``), the device's busy and idle time inside
  ``train_pass``, and each idle gap under the innermost span of the
  training thread that covers it. The capture's programs' device-scope
  table (``monitor/device_scopes.py``: which stage each instruction of
  each compiled program belongs to) is written beside it as
  ``device_scopes.json``, and ``--device`` joins the two: seconds,
  share and launches by scope and program.

Cost discipline: tracing disabled costs ONE module-flag check per scope
(``_ACTIVE``) — the same contract as the hub's disabled event path,
asserted by a micro-test. An unsampled pass pays one sampling decision
at ``begin_pass`` and nothing per step.

CLI::

    python -m paddlebox_tpu.monitor.trace RANK_DIR... \
        [-o world_trace.json] [--rank-names 4,5,7] [--json]
    python -m paddlebox_tpu.monitor.trace --device \
        <file.xplane.pb | trace_device_dir/pass-NNNNN> [--json] \
        [--scopes device_scopes.json]
"""

from __future__ import annotations

import bisect
import contextvars
import glob
import json
import os
import re
import sys
import uuid
import warnings
import zlib

from paddlebox_tpu.config import flags as config_flags
from paddlebox_tpu.monitor import aggregate as agg_lib
from paddlebox_tpu.monitor import device_scopes
from paddlebox_tpu.monitor.names import ANNOTATION_PREFIX as SPAN_PREFIX
from paddlebox_tpu.monitor.names import KERNEL_NAMES
from paddlebox_tpu.monitor.registry import STATS

# ---------------------------------------------------------------------------
# trace context (the write side)
# ---------------------------------------------------------------------------

# THE one-check gate: every per-record/per-scope helper returns
# immediately when this is False (the hub checks it inline too)
_ACTIVE = False

_TRACE_ID: str | None = None
_PASS_ROOT: str | None = None          # pass-root span id (plain-thread
                                       # fallback parent, like context._global)
_SID_PREFIX = f"{os.getpid() & 0xFFFFFF:06x}{uuid.uuid4().hex[:4]}"
_sid_counter = 0

# per-thread span stack (immutable tuple — pushes are context-local, so
# concurrent spans on the pack/feed/dump threads never interleave)
_stack: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "pbtpu_trace_spans", default=())

# device-capture state (one window per sampled pass)
_device_dir: str | None = None
_device_warned = False

# has this process EVER opened a pass scope? A training process owns
# the trace window via begin/end_pass sampling; a co-located serving
# poll must then never re-activate tracing between or inside passes
# (ensure_service is for pass-less standalone servers only)
_SAW_PASS = False


def _new_span_id() -> str:
    global _sid_counter
    _sid_counter += 1                 # GIL-atomic enough for an id
    return f"{_SID_PREFIX}-{_sid_counter}"


def active() -> bool:
    return _ACTIVE


def trace_id() -> str | None:
    return _TRACE_ID


def _run_id() -> str:
    return config_flags.trace_run_id or "run"


def on_begin_pass(pass_id: int, hub_enabled: bool) -> bool:
    """Hub hook at ``begin_pass``: decide sampling, open the pass-root
    span, and (``flags.trace_device``) start the device-capture window.
    Returns whether this pass is traced."""
    global _ACTIVE, _TRACE_ID, _PASS_ROOT, _SAW_PASS
    _SAW_PASS = True
    if not (config_flags.trace and hub_enabled):
        _ACTIVE = False
        return False
    n = max(1, int(config_flags.trace_sample_passes))
    if int(pass_id) % n != 0 and n > 1:
        _ACTIVE = False
        return False
    _TRACE_ID = f"{_run_id()}:{int(pass_id)}"
    _PASS_ROOT = _new_span_id()
    _ACTIVE = True
    _maybe_start_device_capture(int(pass_id))
    return True


def on_end_pass() -> None:
    """Hub hook at ``end_pass``/``abort_pass``: close the window."""
    global _ACTIVE, _TRACE_ID, _PASS_ROOT
    _stop_device_capture()
    _ACTIVE = False
    _TRACE_ID = None
    _PASS_ROOT = None


def ensure_service(name: str) -> bool:
    """Pass-less processes (the serving server) have no ``begin_pass``
    to sample at; with ``flags.trace`` on, activate a standing trace
    scope named after the service so swap-side records/flow points are
    stamped and mergeable. Returns whether tracing is active.

    In a process that ALSO trains (co-located publisher+server), the
    pass lifecycle owns the window — this is a no-op there, so a poll
    thread can never re-activate tracing inside an unsampled pass or
    stamp between-pass records into a bogus service trace (swap records
    of a co-located server are stamped by the enclosing traced pass
    instead)."""
    global _ACTIVE, _TRACE_ID, _PASS_ROOT
    if not config_flags.trace or _SAW_PASS:
        return _ACTIVE
    if not _ACTIVE:
        _TRACE_ID = f"{_run_id()}:{name}"
        _PASS_ROOT = _new_span_id()
        _ACTIVE = True
    return True


def push_span(name: str) -> tuple:
    """Open a span scope on this thread's stack; returns the token for
    :func:`pop_span`. (The hub's ``_Span`` drives this — instrumented
    code never calls it directly.)"""
    sid = _new_span_id()
    stack = _stack.get()
    token = _stack.set(stack + (sid,))
    return (sid, token)


def pop_span(handle: tuple) -> tuple:
    """Close the span scope; returns ``(span_id, parent_span_id)`` for
    the record stamp."""
    sid, token = handle
    stack = _stack.get()
    parent = stack[-2] if len(stack) >= 2 else _PASS_ROOT
    try:
        _stack.reset(token)
    except ValueError:         # popped from a different Context: best
        _stack.set(stack[:-1])  # effort — the stamp below is still right
    return sid, parent


def current_ids() -> tuple:
    """(trace_id, enclosing_span_id) at this point — the stamp for
    EVENT records (a point belongs to the span it fired inside; the
    pass root when no span is open on this thread)."""
    stack = _stack.get()
    return _TRACE_ID, (stack[-1] if stack else _PASS_ROOT)


def pass_root_id() -> str | None:
    return _PASS_ROOT


def flow(kind: str, key: str, role: str = "point", **fields) -> None:
    """Emit one flow point: records sharing ``(kind, key)`` across rank
    streams become ONE flow arrow in the merged trace (role ``src``
    anchors the arrow tail when present; otherwise the earliest
    corrected point does). No-op unless the pass is traced — one check."""
    if not _ACTIVE:
        return
    from paddlebox_tpu.monitor.hub import event as hub_event
    hub_event("trace.flow", type="flow", kind=str(kind), key=str(key),
              role=str(role), **fields)


def flow_propagated(kind: str, key: str, role: str,
                    parent: "dict | None", **fields) -> None:
    """Flow point activated by a PROPAGATED trace context (a donefile
    entry's ``{"trace_id", "span_id"}``) instead of the local window:
    the producing run traced this artifact, so the consumer-side point
    must emit even in a process with no trace scope of its own (a
    serving host with default flags, a co-located tailer polling
    between passes). The parent ids ride the fields — the merger pairs
    the edge under the PRODUCER's run and draws the parent link. No-op
    when there is neither a propagated parent nor a local window."""
    if not parent and not _ACTIVE:
        return
    parent = parent or {}
    from paddlebox_tpu.monitor.hub import event as hub_event
    hub_event("trace.flow", type="flow", kind=str(kind), key=str(key),
              role=str(role),
              parent_trace_id=parent.get("trace_id"),
              parent_span_id=parent.get("span_id"), **fields)


# ---------------------------------------------------------------------------
# device capture (flags.trace_device — per-pass jax.profiler window)
# ---------------------------------------------------------------------------

def _capture_failed(what: str, err: Exception) -> None:
    """Tracing must never take down the training it observes, and must
    not fail in silence either: every failure is counted, the first one
    of the process says why."""
    global _device_warned
    STATS.add("trace.device_capture_errors", 1)
    if not _device_warned:
        _device_warned = True
        warnings.warn(f"flags.trace_device: {what} failed ({err!r}); "
                      f"training goes on without the device capture",
                      RuntimeWarning, stacklevel=3)


def _maybe_start_device_capture(pass_id: int) -> None:
    global _device_dir
    if not config_flags.trace_device or _device_dir is not None:
        return
    try:
        import jax
        import tempfile
        root = config_flags.trace_device_dir or os.path.join(
            tempfile.gettempdir(), "pbtpu_device_trace")
        logdir = os.path.join(root, f"pass-{pass_id:05d}")
        jax.profiler.start_trace(logdir)
    except Exception as e:
        _capture_failed("start_trace", e)
        return
    _device_dir = logdir
    from paddlebox_tpu.monitor.hub import event as hub_event
    hub_event("trace.device_capture", type="flow", logdir=logdir,
              state="started")


def _stop_device_capture() -> None:
    global _device_dir
    if _device_dir is None:
        return
    logdir, _device_dir = _device_dir, None
    try:
        import jax
        jax.profiler.stop_trace()
    except Exception as e:
        _capture_failed("stop_trace", e)
        return
    if device_scopes.TABLE:
        # the programs' stages, beside the capture they are joined with
        try:
            with open(os.path.join(os.path.dirname(find_xplane(logdir)),
                                   device_scopes.TABLE_FILE), "w") as f:
                json.dump(device_scopes.TABLE, f)
        except OSError as e:
            _capture_failed("writing " + device_scopes.TABLE_FILE, e)
    from paddlebox_tpu.monitor.hub import event as hub_event
    hub_event("trace.device_capture", type="flow", logdir=logdir,
              state="stopped")


# ---------------------------------------------------------------------------
# clock-offset estimation (the read side of the heartbeat probes)
# ---------------------------------------------------------------------------

def ntp_offset(t0: float, t1: float, t2: float, t3: float
               ) -> tuple[float, float]:
    """The classic symmetric estimate from one heartbeat round-trip:
    observer publishes at ``t0`` (its clock), the peer reads that at
    ``t1`` and publishes its echo at ``t2`` (peer clock), the observer
    reads the echo at ``t3``. Returns ``(offset, rtt)`` where
    ``offset ~= peer_clock - observer_clock`` (delay asymmetry is the
    error term, bounded by rtt/2)."""
    offset = ((t1 - t0) + (t2 - t3)) / 2.0
    rtt = (t3 - t0) - (t2 - t1)
    return offset, rtt


def _median(vals: list[float]) -> float:
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2.0


def estimate_clock_offsets(probes: "list[dict]",
                           ranks: "list[int]") -> dict:
    """Per-rank clock offset (seconds, relative to the anchor = lowest
    rank) from ``trace.clock_probe`` samples.

    Each probe is ``{observer, peer, offset_s}`` with ``offset_s ~=
    clock(peer) - clock(observer)``. Pairwise medians (robust to the
    odd slow store round-trip) feed a BFS from the anchor, so
    multi-host chains resolve transitively; a rank no probe reaches
    keeps offset 0 (uncorrected — reported as such)."""
    pair: dict[tuple[int, int], list[float]] = {}
    for p in probes:
        try:
            obs, peer = int(p["observer"]), int(p["peer"])
            off = float(p["offset_s"])
        except (KeyError, TypeError, ValueError):
            continue
        pair.setdefault((obs, peer), []).append(off)
        pair.setdefault((peer, obs), []).append(-off)
    est = {k: _median(v) for k, v in pair.items()}
    offsets = {r: 0.0 for r in ranks}
    corrected = set()
    if not ranks:
        return {"offsets_s": offsets, "corrected": []}
    anchor = min(ranks)
    corrected.add(anchor)
    frontier = [anchor]
    while frontier:
        a = frontier.pop()
        for (obs, peer), off in est.items():
            if obs == a and peer in offsets and peer not in corrected:
                # clock(peer) = clock(obs) + off
                offsets[peer] = offsets[a] + off
                corrected.add(peer)
                frontier.append(peer)
    return {"offsets_s": {r: round(v, 6) for r, v in offsets.items()},
            "corrected": sorted(corrected)}


# ---------------------------------------------------------------------------
# stream reading + world merge (the read side)
# ---------------------------------------------------------------------------

# record kinds the merger keeps; everything else is counted only (a
# day-scale stream must merge in bounded memory)
KEEP_TYPES = ("span", "flight_record", "lifecycle", "flow")
MAX_RECORDS_PER_RANK = 200_000


def read_trace_records(root: str) -> dict:
    """One rank's trace-relevant records, in stream order (all rotated
    segments — the aggregate module's discovery/ordering rules)."""
    files = agg_lib.discover_stream_files(root)
    kept: list[dict] = []
    probes: list[dict] = []
    dropped = 0
    n = 0
    for path in files:
        for line in agg_lib._iter_lines(root, path):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue                 # schema errors are aggregate's job
            n += 1
            name = rec.get("name")
            if name == "trace.clock_probe":
                probes.append(rec.get("fields") or {})
                continue
            if rec.get("type") in KEEP_TYPES:
                if len(kept) >= MAX_RECORDS_PER_RANK:
                    dropped += 1
                    continue
                kept.append(rec)
    return {"root": root, "events": n, "records": kept,
            "clock_probes": probes, "dropped": dropped}


def _tid_for(thread_name: str, tids: dict) -> int:
    if thread_name not in tids:
        tids[thread_name] = len(tids) + 1   # 0 = the pass track
    return tids[thread_name]


def _flow_id(kind: str, key: str, n: int) -> int:
    return zlib.crc32(f"{kind}:{key}:{n}".encode()) & 0x7FFFFFFF


def merge_streams(streams: "list[dict]", labels: "list[int]") -> dict:
    """Merge per-rank record streams (:func:`read_trace_records` shapes)
    into one Chrome-trace-event JSON. Returns the trace dict with the
    machine summary under ``["pbtpu"]`` (Perfetto ignores foreign top-
    level keys): clock offsets applied, flow edges with corrected
    latencies, and per-rank record counts."""
    clock = estimate_clock_offsets(
        [p for st in streams for p in st["clock_probes"]], list(labels))
    offsets = clock["offsets_s"]

    events: list[dict] = []
    flow_points: dict[tuple, list] = {}
    spans = 0
    span_records = 0          # type=="span" only — "is there a trace
    t_min = None              # plane here at all" (flights always exist)
    # cross-process parent links (ISSUE 19): a serving request span
    # carries the producing run's ids in its FIELDS (propagated through
    # the donefile entry) — pair them with the parent span's merged
    # location to draw publish -> request arrows across process
    # boundaries
    span_locs: dict[str, dict] = {}
    linked: list[dict] = []

    def corrected(rank: int, ts: float) -> float:
        return float(ts) - offsets.get(rank, 0.0)

    # first sweep: find the global origin so Perfetto ts stay small
    for label, st in zip(labels, streams):
        for rec in st["records"]:
            ts = rec.get("ts")
            if not isinstance(ts, (int, float)):
                continue
            start = corrected(label, ts) - float(rec.get("dur_s") or
                                                 rec.get("seconds") or 0.0)
            t_min = start if t_min is None else min(t_min, start)
    t0 = t_min or 0.0

    def us(rank: int, ts: float, back_s: float = 0.0) -> float:
        return round((corrected(rank, ts) - back_s - t0) * 1e6, 3)

    for label, st in zip(labels, streams):
        tids: dict[str, int] = {}
        events.append({"name": "process_name", "ph": "M", "pid": label,
                       "args": {"name": f"rank {label}"}})
        events.append({"name": "process_sort_index", "ph": "M",
                       "pid": label, "args": {"sort_index": label}})
        events.append({"name": "thread_name", "ph": "M", "pid": label,
                       "tid": 0, "args": {"name": "pass"}})
        for rec in st["records"]:
            ts = rec.get("ts")
            if not isinstance(ts, (int, float)):
                continue
            typ = rec.get("type")
            name = rec.get("name")
            args = {k: rec.get(k) for k in
                    ("pass_id", "step", "trace_id", "span_id",
                     "parent_span_id") if rec.get(k) is not None}
            if rec.get("fields"):
                args.update(rec["fields"])
            if typ == "flight_record":
                dur = float(rec.get("seconds") or 0.0)
                events.append({
                    "name": f"pass {rec.get('pass_id')}", "ph": "X",
                    "pid": label, "tid": 0,
                    "ts": us(label, ts, dur), "dur": round(dur * 1e6, 3),
                    "args": args})
                spans += 1
            elif typ == "span":
                dur = float(rec.get("dur_s") or 0.0)
                tid = _tid_for(rec.get("thread") or "main", tids)
                start_us = us(label, ts, dur)
                events.append({
                    "name": name, "ph": "X", "pid": label, "tid": tid,
                    "ts": start_us, "dur": round(dur * 1e6, 3),
                    "args": args})
                spans += 1
                span_records += 1
                sid = rec.get("span_id")
                if isinstance(sid, str):
                    span_locs.setdefault(sid, {"rank": label, "tid": tid,
                                               "ts_us": start_us})
                f = rec.get("fields") or {}
                if isinstance(f.get("parent_span_id"), str):
                    linked.append({"name": name, "rank": label,
                                   "tid": tid, "ts_us": start_us,
                                   "parent_span_id": f["parent_span_id"],
                                   "parent_trace_id":
                                       f.get("parent_trace_id")})
            elif typ == "flow" and name == "trace.flow":
                f = rec.get("fields") or {}
                pt = {"rank": label,
                      "tid": _tid_for(rec.get("thread") or "main", tids),
                      "ts_us": us(label, ts),
                      "corrected_s": corrected(label, ts),
                      "role": f.get("role", "point"),
                      "fields": f, "args": args}
                # group key includes the RUN prefix of the trace_id
                # (trace_run_id) — two runs sharing a telemetry root
                # must never pair their flow points into phantom edges.
                # A propagated parent_trace_id wins: a consumer-side
                # point (the serving swap) pairs under the PRODUCER's
                # run, whatever the consumer's local flags say
                run = str(f.get("parent_trace_id")
                          or rec.get("trace_id") or "").split(":", 1)[0]
                flow_points.setdefault(
                    (str(f.get("kind")), str(f.get("key")), run),
                    []).append(pt)
            else:                        # lifecycle -> instant marker
                tid = _tid_for(rec.get("thread") or "main", tids)
                events.append({"name": name, "ph": "i", "s": "t",
                               "pid": label, "tid": tid,
                               "ts": us(label, ts), "args": args})
        for tname, tid in tids.items():
            events.append({"name": "thread_name", "ph": "M", "pid": label,
                           "tid": tid, "args": {"name": tname}})

    # flow arrows: per (kind, key) group, the src-role (else earliest)
    # point anchors; every other point is an arrow head. One id per
    # edge — chrome's s/f pairing is strictly 1:1.
    edges: list[dict] = []
    for (kind, key, run), pts in sorted(flow_points.items()):
        pts.sort(key=lambda p: p["ts_us"])
        srcs = [p for p in pts if p["role"] == "src"]
        src = srcs[0] if srcs else pts[0]
        n = 0
        for p in pts:
            if p is src:
                continue
            n += 1
            fid = _flow_id(kind, f"{run}/{key}", n)
            cat = f"flow.{kind}"
            events.append({"name": f"{kind}:{key}", "ph": "s", "id": fid,
                           "cat": cat, "pid": src["rank"],
                           "tid": src["tid"], "ts": src["ts_us"]})
            events.append({"name": f"{kind}:{key}", "ph": "f", "bp": "e",
                           "id": fid, "cat": cat, "pid": p["rank"],
                           "tid": p["tid"], "ts": p["ts_us"]})
            edges.append({
                "kind": kind, "key": key,
                "src_rank": src["rank"], "dst_rank": p["rank"],
                "latency_s": round(p["corrected_s"]
                                   - src["corrected_s"], 6),
                "fields": {k: v for k, v in p["fields"].items()
                           if k not in ("kind", "key", "role")}})
    # parent-link arrows (ISSUE 19): one s/f pair from the parent span
    # (the producing pass's publish) to each propagated-linked child
    # span (a serving request) — NOT a flow edge (the cross-rank-flow
    # doctor rule keys off flow() points only), so it gets its own
    # counter. Parents outside the merged roots still count as linked:
    # the ids are stamped either way.
    linked_edges = 0
    for n, lk in enumerate(sorted(linked, key=lambda p: p["ts_us"]), 1):
        src = span_locs.get(lk["parent_span_id"])
        if src is None:
            continue
        linked_edges += 1
        fid = _flow_id("parent", lk["parent_span_id"], n)
        events.append({"name": f"parent:{lk['name']}", "ph": "s",
                       "id": fid, "cat": "flow.parent",
                       "pid": src["rank"], "tid": src["tid"],
                       "ts": src["ts_us"]})
        events.append({"name": f"parent:{lk['name']}", "ph": "f",
                       "bp": "e", "id": fid, "cat": "flow.parent",
                       "pid": lk["rank"], "tid": lk["tid"],
                       "ts": lk["ts_us"]})
    events.sort(key=lambda e: (e.get("ts", -1), e.get("pid", 0)))
    summary = {
        "ranks": list(labels),
        "events": len(events),
        "spans": spans,
        "span_records": span_records,
        "linked_spans": len(linked),
        "linked_edges": linked_edges,
        "flow_points": sum(len(v) for v in flow_points.values()),
        "flow_edges": edges,
        "clock_offsets_s": {str(r): v
                            for r, v in offsets.items()},
        "clock_corrected_ranks": clock["corrected"],
        "records_dropped": sum(st["dropped"] for st in streams),
    }
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "pbtpu": summary}


def merge_roots(roots: "list[str]",
                rank_names: "list[int] | None" = None) -> dict:
    """N per-rank telemetry roots (local dirs / .jsonl files / hdfs://
    roots) -> one merged Chrome trace. Rank naming follows the
    aggregate/Heartbeat convention (``aggregate.rank_label``)."""
    streams = [read_trace_records(r) for r in roots]
    labels = [agg_lib.rank_label(r, i, rank_names)
              for i, r in enumerate(roots)]
    return merge_streams(streams, labels)


def write_trace(trace: dict, path: str) -> str:
    """Atomic write (tmp -> fsync -> replace): a monitoring cron must
    never ship a torn half-trace under the final name."""
    from paddlebox_tpu.utils.checkpoint import atomic_file
    with atomic_file(path) as tmp:
        with open(tmp, "w") as f:
            json.dump(trace, f)
    return path


def summarize(trace: dict) -> dict:
    """The embeddable machine summary of a merged trace (the doctor's
    cross-rank-flow rule reads it)."""
    return dict(trace.get("pbtpu") or {})


# ---------------------------------------------------------------------------
# in-memory capture (one process, no files)
# ---------------------------------------------------------------------------

def records_to_stream(records: "list[dict]") -> dict:
    """A :func:`read_trace_records`-shaped stream from in-memory hub
    records (a MemorySink ring)."""
    kept = [r for r in records if r.get("type") in KEEP_TYPES]
    probes = [r.get("fields") or {} for r in records
              if r.get("name") == "trace.clock_probe"]
    return {"root": "<memory>", "events": len(records), "records": kept,
            "clock_probes": probes, "dropped": 0}


# ---------------------------------------------------------------------------
# device-capture reader (what flags.trace_device, or any jax.profiler
# capture of the program, wrote)
# ---------------------------------------------------------------------------

ROOT_SPAN = "train_pass"
PATH_SEP = " > "
DEVICE_OPS_LINE = "XLA Ops"
DEVICE_MODULES_LINE = "XLA Modules"
NO_DEVICE_PLANE = "host hlo_op events (no device plane in the capture)"
# rows of the by-scope table that are no scope's: an instruction the table
# holds under no scope; an event whose program or instruction it lacks
UNSCOPED, UNKNOWN = "unscoped", "unknown"
_INSTRUCTION = re.compile(r"%?([\w.\-]+) = ")
_PROGRAM_RUN = re.compile(r"\(\d+\)$")


def find_xplane(path: str) -> str:
    """`path` if it is a file; else the newest ``.xplane.pb`` under it (a
    ``trace_device_dir/pass-NNNNN``, or any ``jax.profiler`` log dir)."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def kernel_launches(events) -> dict:
    """``{kernel: {"launches", "seconds"}}`` over a device line's
    ``(event name, seconds)``, for the ``names.KERNEL_NAMES`` kernels that
    ran. An ``XLA Ops`` event is named by its whole HLO instruction
    (``%jvp_pbtpu_attention_fwd_.1 = (bf16[...], ...) custom-call(...)``):
    the kernel's name is looked for in the instruction's own name, not in
    its operands."""
    out: dict[str, dict] = {}
    for name, seconds in events:
        head = name.split(" = ", 1)[0]
        kernel = next((k for k in KERNEL_NAMES if k in head), None)
        if kernel is not None:
            acc = out.setdefault(kernel, {"launches": 0, "seconds": 0.0})
            acc["launches"] += 1
            acc["seconds"] += seconds
    return out


def _own_time(events, into: dict) -> None:
    """Add one line's events ``(start, end, key)`` — they nest or follow,
    never cross — to ``into[key] = [launches, seconds]``, an enclosing
    event (a loop, a call) counting only what its children leave
    (:func:`nest_spans`' rule without its records: a token cell's capture
    holds a million events)."""
    stack: list[list] = []              # [end, key, own seconds]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _, key, own = stack.pop()
            acc = into.setdefault(key, [0, 0.0])
            acc[0] += 1
            acc[1] += own

    for a, b, key in sorted(events, key=lambda t: (t[0], -t[1])):
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, key, b - a])
    close(float("inf"))


def _by_program(ops: dict) -> dict:
    """``{(program, instruction): [launches, seconds]}`` ->
    ``{program: {instruction: [launches, seconds]}}`` (JSON's shape)."""
    out: dict[str, dict] = {}
    for (program, instruction), acc in ops.items():
        out.setdefault(program, {})[instruction] = acc
    return out


def read_capture(xplane_path: str) -> dict:
    """What the reduction needs, in seconds on the capture's one clock:
    ``threads`` — per host thread that holds any, its ``pbtpu/`` spans as
    ``(start, end, name)`` — ``device_ops`` — the intervals in which an
    operation ran on the first device (a TPU plane's ``XLA Ops`` line; on
    the CPU backend, where the device is the host, the host events that
    carry an ``hlo_op``) — ``kernels``, that device's
    :func:`kernel_launches` (empty off a TPU: the Pallas interpreter runs
    no operation under a kernel's name) — and ``ops``, that device's own
    time by program and instruction, ``{program: {instruction: [launches,
    seconds]}}``: an ``XLA Ops`` event is named by its instruction and
    belongs to the ``XLA Modules`` event it starts in (on the CPU backend
    the host event's ``hlo_op`` and ``hlo_module``), which is what
    :func:`by_scope` joins with the programs' device-scope table.
    ``device_source`` says which of the two was read: every report
    prints it, so host events never pass for the chip's."""
    import jax
    data = jax.profiler.ProfileData.from_file(xplane_path)
    threads, devices, host_lines = [], [], []   # lines: read twice at most
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if DEVICE_OPS_LINE in lines:
                events = list(lines[DEVICE_OPS_LINE].events)
                runs = sorted(
                    (e.start_ns, _PROGRAM_RUN.sub("", e.name))
                    for e in (lines[DEVICE_MODULES_LINE].events
                              if DEVICE_MODULES_LINE in lines else ()))
                starts = [a for a, _ in runs]

                def program(t, runs=runs, starts=starts):
                    k = bisect.bisect_right(starts, t) - 1
                    return runs[k][1] if k >= 0 else ""

                def instruction(name):
                    m = _INSTRUCTION.match(name)
                    return m.group(1) if m else name
                ops: dict = {}
                _own_time(((e.start_ns / 1e9,
                            (e.start_ns + e.duration_ns) / 1e9,
                            (program(e.start_ns), instruction(e.name)))
                           for e in events), ops)
                devices.append((plane.name, [
                    (e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                    for e in events], kernel_launches(
                        (e.name, e.duration_ns / 1e9) for e in events),
                    ops))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host_lines.append(ln)
                spans = [(e.start_ns / 1e9,
                          (e.start_ns + e.duration_ns) / 1e9,
                          e.name[len(SPAN_PREFIX):])
                         for e in ln.events
                         if e.name.startswith(SPAN_PREFIX)]
                if spans:
                    threads.append(spans)
    kernels: dict = {}
    ops: dict = {}
    if devices:
        plane_name, device_ops, kernels, ops = min(devices,
                                                   key=lambda d: d[0])
        source = f"{plane_name} {DEVICE_OPS_LINE}"
    else:
        source = NO_DEVICE_PLANE
        device_ops = []
        with warnings.catch_warnings():
            # jaxlib's stats iterator warns about its own missing
            # __module__ (DeprecationWarning) on first use
            warnings.simplefilter("ignore", DeprecationWarning)
            for ln in host_lines:
                held = []
                for e in ln.events:
                    stats = dict(e.stats) if e.duration_ns > 0 else {}
                    if "hlo_op" in stats:
                        held.append((e.start_ns / 1e9,
                                     (e.start_ns + e.duration_ns) / 1e9,
                                     (str(stats.get("hlo_module", "")),
                                      str(stats["hlo_op"]))))
                device_ops += [(a, b) for a, b, _ in held]
                _own_time(held, ops)
    return {"threads": threads, "device_ops": device_ops,
            "kernels": kernels, "devices": len(devices),
            "device_source": source, "ops": _by_program(ops)}


def nest_spans(spans: "list[tuple]") -> "tuple[list[dict], list[tuple]]":
    """Nest one thread's spans by time (the spans of a thread nest or
    follow, never cross; a child that ends a rounding error after its
    parent is cut to it). Returns the spans as records — ``name``,
    ``path`` (the names from the outermost span down), ``start``,
    ``end``, ``self_s`` (duration minus what the direct children cover) —
    and the thread's covered time as segments ``(start, end, path)``,
    each under the innermost span that covers it."""
    recs: list[dict] = []
    segs: list[tuple] = []
    stack: list[list] = []          # [record, start of its open segment]

    def close(upto: float) -> None:
        while stack and stack[-1][0]["end"] <= upto:
            rec, cursor = stack.pop()
            if rec["end"] > cursor:
                segs.append((cursor, rec["end"], rec["path"]))
            if stack:
                stack[-1][1] = rec["end"]

    for a, b, name in sorted(spans, key=lambda t: (t[0], -t[1])):
        close(a)
        path: tuple = (name,)
        if stack:
            parent, cursor = stack[-1]
            b = min(b, parent["end"])
            if a > cursor:
                segs.append((cursor, a, parent["path"]))
            parent["self_s"] -= b - a
            path = parent["path"] + path
        rec = {"name": name, "path": path, "start": a, "end": b,
               "self_s": b - a}
        recs.append(rec)
        stack.append([rec, a])
    close(float("inf"))
    return recs, sorted(segs)


def _union(intervals) -> "list[tuple]":
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce_capture(threads: "list[list[tuple]]",
                   device_ops: "list[tuple]", top: int = 10) -> dict:
    """From the spans and the device's operation intervals
    (:func:`read_capture` shapes) to the report: per span name count,
    seconds and self seconds over all threads; for the training thread
    (the one that holds ``train_pass``) the root's own account, the
    device's busy and idle seconds inside it, the idle seconds by the
    innermost span that covers them, and the longest idle gaps, each
    with the spans it ran under."""
    by_name: dict[str, dict] = {}
    main = None
    for spans in threads:
        recs, segs = nest_spans(spans)
        for r in recs:
            acc = by_name.setdefault(
                r["name"], {"count": 0, "seconds": 0.0, "self_s": 0.0})
            acc["count"] += 1
            acc["seconds"] += r["end"] - r["start"]
            acc["self_s"] += r["self_s"]
        if main is None and any(r["name"] == ROOT_SPAN for r in recs):
            main = (recs, segs)
    out: dict = {"spans": by_name}
    if main is None:
        return out
    recs, segs = main
    roots = [r for r in recs if r["name"] == ROOT_SPAN]
    root_paths = {r["path"] for r in roots}
    busy = _union(device_ops)
    idle: list[tuple] = []          # (start, end, root start)
    busy_s = 0.0
    for r in roots:
        edge = r["start"]
        for a, b in busy:
            a, b = max(a, r["start"]), min(b, r["end"])
            if b <= a:
                continue
            if a > edge:
                idle.append((edge, a, r["start"]))
            busy_s += b - a
            edge = max(edge, b)
        if r["end"] > edge:
            idle.append((edge, r["end"], r["start"]))
    by_span: dict[str, float] = {}
    gaps = []
    for a, b, root_start in idle:
        under: dict[str, float] = {}
        for sa, sb, path in segs:
            if sb <= a:
                continue
            if sa >= b:
                break
            key = PATH_SEP.join(path)
            under[key] = under.get(key, 0.0) + min(b, sb) - max(a, sa)
        for key, sec in under.items():
            by_span[key] = by_span.get(key, 0.0) + sec
        gaps.append({"at_s": a - root_start, "seconds": b - a,
                     "spans": sorted(([k, v] for k, v in under.items()),
                                     key=lambda kv: -kv[1])})
    seconds = sum(r["end"] - r["start"] for r in roots)
    out["train_pass"] = {
        "passes": len(roots), "seconds": seconds,
        "self_s": sum(r["self_s"] for r in roots),
        "longest_hole_s": max((sb - sa for sa, sb, path in segs
                               if path in root_paths), default=0.0),
        "device_busy_s": busy_s, "device_idle_s": seconds - busy_s}
    out["idle_by_span"] = dict(sorted(by_span.items(),
                                      key=lambda kv: -kv[1]))
    out["longest_gaps"] = sorted(gaps, key=lambda g: -g["seconds"])[:top]
    return out


def by_scope(ops: dict, table: dict) -> list[dict]:
    """The device's own time by device scope and program: `ops` is
    :func:`read_capture`'s ``{program: {instruction: [launches,
    seconds]}}``, `table` the programs' ``{program: {instruction:
    {"scope"}}}`` (``device_scopes.TABLE``, or the ``device_scopes.json``
    a capture was written with). Rows ``{"scope", "program", "launches",
    "seconds", "share"}``, the longest first; ``unscoped`` is an
    instruction the table holds under no scope, ``unknown`` an event
    whose program or instruction the table does not hold. The rows'
    seconds sum to the own time of every event read."""
    rows: dict[tuple, list] = {}
    for program, held in ops.items():
        known = table.get(program)
        for instruction, (launches, seconds) in held.items():
            row = (known or {}).get(instruction)
            scope = UNKNOWN if row is None else row["scope"] or UNSCOPED
            acc = rows.setdefault((scope, program), [0, 0.0])
            acc[0] += launches
            acc[1] += seconds
    total = sum(s for _, s in rows.values()) or 1.0
    return sorted(({"scope": scope, "program": program, "launches": n,
                    "seconds": s, "share": s / total}
                   for (scope, program), (n, s) in rows.items()),
                  key=lambda r: -r["seconds"])


def render_capture_text(report: dict) -> str:
    lines = [f"device intervals read from: {report['device_source']}",
             f"{'span':<24}{'count':>7}{'seconds':>12}{'self':>12}"]
    for name, acc in sorted(report["spans"].items(),
                            key=lambda kv: -kv[1]["seconds"]):
        lines.append(f"{name:<24}{acc['count']:>7}{acc['seconds']:>12.6f}"
                     f"{acc['self_s']:>12.6f}")
    if report.get("kernels"):
        lines.append(f"{'kernel':<28}{'launches':>9}{'seconds':>12}")
        for name, acc in sorted(report["kernels"].items(),
                                key=lambda kv: -kv[1]["seconds"]):
            lines.append(f"{name:<28}{acc['launches']:>9}"
                         f"{acc['seconds']:>12.6f}")
    if report.get("scopes"):
        lines.append(
            f"device time by scope and program (table: "
            f"{report['scopes_from']}; the whole capture, busy "
            f"{report['device_busy_capture_s']:.6f} s):")
        lines.append(f"{'scope':<14}{'program':<28}{'launches':>9}"
                     f"{'seconds':>12}{'share':>8}")
        for r in report["scopes"]:
            lines.append(f"{r['scope']:<14}{r['program']:<28}"
                         f"{r['launches']:>9}{r['seconds']:>12.6f}"
                         f"{100 * r['share']:>7.2f}%")
    else:
        lines.append(f"no {device_scopes.TABLE_FILE} beside the capture "
                     "(--scopes <file>): no device time by scope")
    tp = report.get("train_pass")
    if tp is None:
        lines.append(f"no {SPAN_PREFIX}{ROOT_SPAN} span in the capture: "
                     "nothing to attribute the device's idle time to")
        return "\n".join(lines)
    lines.append(
        f"{ROOT_SPAN}: {tp['passes']} pass(es), {tp['seconds']:.6f} s; "
        f"self {tp['self_s']:.6f} s, longest hole outside a child "
        f"{tp['longest_hole_s']:.6f} s; device busy "
        f"{tp['device_busy_s']:.6f} s, idle {tp['device_idle_s']:.6f} s")
    lines.append("device idle by innermost span of the training thread:")
    for path, sec in report["idle_by_span"].items():
        lines.append(f"  {sec:>10.6f}  {path}")
    lines.append("longest idle gaps (seconds, at, spans under it):")
    for g in report["longest_gaps"]:
        under = "; ".join(f"{p} {v:.6f}" for p, v in g["spans"][:4])
        if len(g["spans"]) > 4:
            under += f"; and {len(g['spans']) - 4} more (--json has all)"
        lines.append(f"  {g['seconds']:>10.6f}  +{g['at_s']:.6f}  {under}")
    return "\n".join(lines)


def device_main(argv: "list[str]") -> int:
    as_json = "--json" in argv
    scopes_path = None
    if "--scopes" in argv:
        i = argv.index("--scopes")
        scopes_path = argv[i + 1] if i + 1 < len(argv) else ""
        del argv[i:i + 2]
    paths = [a for a in argv if not a.startswith("-")]
    if len(paths) != 1 or scopes_path == "":
        print("usage: python -m paddlebox_tpu.monitor.trace --device "
              "<file.xplane.pb | trace_device_dir/pass-NNNNN> [--json] "
              "[--scopes device_scopes.json]", file=sys.stderr)
        return 2
    try:
        xplane = find_xplane(paths[0])
        capture = read_capture(xplane)
        if scopes_path is None:
            beside = os.path.join(os.path.dirname(xplane),
                                  device_scopes.TABLE_FILE)
            scopes_path = beside if os.path.isfile(beside) else None
        table = None
        if scopes_path is not None:
            with open(scopes_path) as f:
                table = json.load(f)
    except (OSError, ValueError) as e:
        print(f"trace: cannot read the capture: {e}", file=sys.stderr)
        return 2
    if not capture["threads"]:
        print(f"trace: no {SPAN_PREFIX} span in {xplane} (was the "
              "program running inside the capture?)", file=sys.stderr)
        return 2
    report = reduce_capture(capture["threads"], capture["device_ops"])
    report["xplane"] = xplane
    report["devices"] = capture["devices"]
    report["device_source"] = capture["device_source"]
    report["kernels"] = capture["kernels"]
    report["device_busy_capture_s"] = sum(
        b - a for a, b in _union(capture["device_ops"]))
    if table is not None:
        report["scopes"] = by_scope(capture["ops"], table)
        report["scopes_from"] = scopes_path
    print(json.dumps(report) if as_json
          else render_capture_text(report), flush=True)
    return 0


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def render_text(summary: dict, out_path: str | None) -> str:
    lines = [f"world trace: {summary['spans']} span(s), "
             f"{summary['flow_points']} flow point(s), "
             f"{len(summary['flow_edges'])} flow edge(s) across "
             f"ranks {summary['ranks']}"]
    offs = summary.get("clock_offsets_s") or {}
    if any(v for v in offs.values()):
        lines.append("clock offsets (s, vs anchor): "
                     + " ".join(f"rank{r}={v:+.6f}"
                                for r, v in sorted(offs.items())))
    for e in summary["flow_edges"][:16]:
        lines.append(f"  {e['kind']}:{e['key']} rank{e['src_rank']} -> "
                     f"rank{e['dst_rank']} ({e['latency_s'] * 1e3:.3f}ms)")
    if out_path:
        lines.append(f"wrote {out_path} — open it at ui.perfetto.dev "
                     "(or chrome://tracing)")
    return "\n".join(lines)


def main(argv: "list[str] | None" = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--device" in argv:
        return device_main([a for a in argv if a != "--device"])
    as_json = "--json" in argv
    argv = [a for a in argv if a != "--json"]
    out_path = None
    for opt in ("-o", "--out"):
        if opt in argv:
            i = argv.index(opt)
            try:
                out_path = argv[i + 1]
            except IndexError:
                print(f"{opt} wants a path", file=sys.stderr)
                return 2
            del argv[i:i + 2]
    rank_names = None
    if "--rank-names" in argv:
        i = argv.index("--rank-names")
        try:
            rank_names = [int(x) for x in argv[i + 1].split(",") if x]
        except (IndexError, ValueError):
            print("--rank-names wants a comma-separated int list",
                  file=sys.stderr)
            return 2
        del argv[i:i + 2]
    roots = [a for a in argv if not a.startswith("-")]
    if not roots:
        print("usage: python -m paddlebox_tpu.monitor.trace "
              "<telemetry_dir>... [-o world_trace.json] "
              "[--rank-names 4,5,7] [--json]", file=sys.stderr)
        return 2
    try:
        trace = merge_roots(roots, rank_names=rank_names)
    except (OSError, ValueError) as e:
        print(f"trace: cannot read telemetry roots: {e}", file=sys.stderr)
        return 2
    summary = summarize(trace)
    if summary["spans"] == 0 and not summary["flow_edges"]:
        print(f"trace: no trace records found under {roots} "
              "(was flags.trace on, and the pass sampled?)",
              file=sys.stderr)
        return 2
    if out_path is None:
        out_path = "world_trace.json"
    write_trace(trace, out_path)
    summary["out"] = out_path
    print(json.dumps(summary, default=str) if as_json
          else render_text(summary, out_path), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
