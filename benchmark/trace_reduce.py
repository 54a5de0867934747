"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read. Needs nothing but JAX (``jax.profiler.ProfileData``).

A TPU's plane is ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one event
per executed HLO operation (a Pallas kernel shows under its custom call's
name), ``XLA Modules`` one event per executed program. The host's plane
``/host:CPU`` holds, on the same clock, the harness's own spans as
``bench/<name>`` annotations and the program's (every ``monitor.span`` and
stage scope) as ``pbtpu/<name>``. From those:

  window_s     first start to last end of the ``bench/`` spans (of the
               device's events where there is no span)
  busy_s       union of the intervals in which an operation ran on the
               device, averaged over the devices
  by_op        seconds per operation name (own time: an operation that
               encloses others, a loop or a call, counts only what its
               children leave), averaged over the devices
  by_program   seconds per program name, from ``XLA Modules``
  gaps         the device's idle intervals inside the window (first
               device), cut at the edges of the spans of the harness's
               thread — the thread that drives the day loop, so the
               program's training thread — each piece under the innermost
               span, of either prefix, that covers it

The nesting rule is the one of the program's own reader
(``paddlebox_tpu.monitor.trace.nest_spans``), kept here as the benchmark's
copy: what a trace is reduced to is the yardstick's to say.
"""

from __future__ import annotations

import glob
import os
import re

HARNESS_PREFIX, PROGRAM_PREFIX = "bench/", "pbtpu/"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _intervals(line, label=str) -> list[tuple[float, float, str]]:
    return [(e.start_ns, e.start_ns + e.duration_ns, label(e.name))
            for e in line.events]


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted((a, b) for a, b, *_ in intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(merged) -> float:
    return sum(b - a for a, b in merged)


def _clip(merged, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in merged
            if min(b, hi) > max(a, lo)]


def own_time_by_name(intervals) -> dict[str, float]:
    """Seconds per name, an enclosing event counting only what the events
    nested in it leave (events of one line nest or follow, never cross)."""
    out: dict[str, float] = {}
    stack: list[list] = []          # [end, name, own_ns]

    def close(upto: float):
        while stack and stack[-1][0] <= upto:
            end, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + own / 1e9

    for a, b, name in sorted(intervals, key=lambda t: (t[0], -t[1])):
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, name, b - a])
    close(float("inf"))
    return out


def op_label(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction; keep the
    instruction's name and the shape it produces:
    ``%concatenate.7 = f32[29360128,15]{0,1:T(8,128)} concatenate(...)``
    -> ``concatenate.7 f32[29360128,15]``. A Pallas kernel keeps the name
    the program gave it (``pbtpu_binned_merge_acc.1 f32[3670016,128]``)."""
    m = re.match(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?", event_name)
    if not m:
        return event_name[:80]
    return m.group(1) + (f" {m.group(2)}" if m.group(2) else "")


def program_name(event_name: str) -> str:
    """``jit_step_flat(123456)`` -> ``jit_step_flat``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def innermost_segments(spans) -> list[tuple[float, float, str]]:
    """One thread's spans ``(start, end, name)`` — they nest or follow,
    never cross; a child that ends a rounding error after its parent is
    cut to it — as the thread's covered time in segments ``(start, end,
    name)``, each named by the innermost span that covers it."""
    segs: list[tuple[float, float, str]] = []
    stack: list[list] = []          # [end, name, start of its open segment]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            end, name, cursor = stack.pop()
            if end > cursor:
                segs.append((cursor, end, name))
            if stack:
                stack[-1][2] = end

    for a, b, name in sorted(spans, key=lambda t: (t[0], -t[1])):
        close(a)
        if stack:
            b = min(b, stack[-1][0])
            if a > stack[-1][2]:
                segs.append((stack[-1][2], a, stack[-1][1]))
        stack.append([b, name, a])
    close(float("inf"))
    return sorted(segs)


def label_gaps(busy, lo: float, hi: float, segments
               ) -> list[tuple[str, float]]:
    """The idle intervals that the merged `busy` intervals leave in
    [lo, hi], cut at the edges of `segments` (sorted, disjoint): (name,
    seconds) a piece, ``outside`` where no span covers it."""
    gaps, edge, k = [], lo, 0
    for a, b in list(busy) + [(hi, hi)]:
        while edge < a:
            while k < len(segments) and segments[k][1] <= edge:
                k += 1
            if k == len(segments) or segments[k][0] >= a:
                name, upto = "outside", a
            elif segments[k][0] > edge:
                name, upto = "outside", segments[k][0]
            else:
                name, upto = segments[k][2], min(segments[k][1], a)
            gaps.append((name, (upto - edge) / 1e9))
            edge = upto
        edge = max(edge, b)
    return gaps


def reduce(xplane_path: str) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(xplane_path)
    devices, threads = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                devices.append((plane.name,
                                _intervals(lines[OPS_LINE], op_label),
                                _intervals(lines[MODULES_LINE])
                                if MODULES_LINE in lines else []))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in ln.events
                         if e.name.startswith((HARNESS_PREFIX,
                                               PROGRAM_PREFIX))]
                if spans:
                    threads.append(spans)
    # the harness's thread, with the program's spans that ran on it; the
    # window is the harness's own spans'
    harness = next((t for t in threads if any(
        name.startswith(HARNESS_PREFIX) for _, _, name in t)), [])
    window = [s for s in harness if s[2].startswith(HARNESS_PREFIX)]
    spans = sorted((a, b, name.split("/", 1)[1]) for a, b, name in harness)
    out = {"devices": len(devices), "spans": spans}
    if not devices:
        return out
    every = [iv for _, ops, _ in devices for iv in ops]
    lo = min(a for a, _, _ in (window or every))
    hi = max(b for _, b, _ in (window or every))
    n = len(devices)
    busy, by_op, by_program = 0.0, {}, {}
    for _, ops, modules in devices:
        busy += _length(_clip(union(ops), lo, hi)) / 1e9 / n
        for name, s in own_time_by_name(ops).items():
            by_op[name] = by_op.get(name, 0.0) + s / n
        for a, b, name in modules:
            name = program_name(name)
            by_program[name] = by_program.get(name, 0.0) + (b - a) / 1e9 / n
    out.update(
        window_s=(hi - lo) / 1e9, busy_s=busy, by_op=by_op,
        by_program=by_program,
        gaps=label_gaps(_clip(union(devices[0][1]), lo, hi), lo, hi,
                        innermost_segments(spans)))
    return out


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    time, and the idle time by what the host was doing (totals per harness
    span first, then the longest single gaps)."""
    ops = sorted(reduced["by_op"].items(), key=lambda kv: -kv[1])[:top]
    per_span: dict[str, float] = {}
    for label, s in reduced["gaps"]:
        per_span[label] = per_span.get(label, 0.0) + s
    idle = sorted(per_span.items(), key=lambda kv: -kv[1])
    longest = sorted(reduced["gaps"], key=lambda g: -g[1])
    idle += [(f"{label}.longest_gap_{k}", s)
             for k, (label, s) in enumerate(longest[:top - len(idle)])]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle[:top]]}
