"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read. Needs nothing but JAX (``jax.profiler.ProfileData``).

A TPU's plane is ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one event
per executed HLO operation (a Pallas kernel shows under its custom call's
name), ``XLA Modules`` one event per executed program. The host's plane
``/host:CPU`` holds the harness's own spans as ``bench/<name>``
annotations, on the same clock. From those:

  window_s     first start to last end of the ``bench/`` spans (of the
               device's events where there is no span)
  busy_s       union of the intervals in which an operation ran on the
               device, averaged over the devices
  by_op        seconds per operation name (own time: an operation that
               encloses others, a loop or a call, counts only what its
               children leave), averaged over the devices
  by_program   seconds per program name, from ``XLA Modules``
  gaps         the device's idle intervals inside the window, cut at the
               harness spans' edges, each piece with the innermost span
               that covers it (first device)
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench/"
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


def reduce(xplane_path: str) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(xplane_path)
    devices, spans = [], []
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
                spans += [(e.start_ns, e.start_ns + e.duration_ns,
                           e.name[len(SPAN_PREFIX):])
                          for e in ln.events
                          if e.name.startswith(SPAN_PREFIX)]
    out = {"devices": len(devices), "spans": sorted(spans)}
    if not devices:
        return out
    every = [iv for _, ops, _ in devices for iv in ops]
    lo = min(a for a, _, _ in (spans or every))
    hi = max(b for _, b, _ in (spans or every))
    n = len(devices)
    busy, by_op, by_program = 0.0, {}, {}
    for _, ops, modules in devices:
        busy += _length(_clip(union(ops), lo, hi)) / 1e9 / n
        for name, s in own_time_by_name(ops).items():
            by_op[name] = by_op.get(name, 0.0) + s / n
        for a, b, name in modules:
            name = program_name(name)
            by_program[name] = by_program.get(name, 0.0) + (b - a) / 1e9 / n
    merged = _clip(union(devices[0][1]), lo, hi)
    gaps, edge = [], lo
    for a, b in merged + [(hi, hi)]:
        if a > edge:
            # a gap that runs across harness spans is cut at their edges,
            # each piece under the innermost span that covers it
            cuts = sorted({edge, a} | {t for s in spans for t in s[:2]
                                       if edge < t < a})
            for ga, gb in zip(cuts[:-1], cuts[1:]):
                mid = (ga + gb) / 2
                cover = [s for s in spans if s[0] <= mid <= s[1]]
                label = min(cover, key=lambda s: s[1] - s[0])[2] \
                    if cover else "outside"
                gaps.append((label, (gb - ga) / 1e9))
        edge = max(edge, b)
    out.update(
        window_s=(hi - lo) / 1e9, busy_s=busy, by_op=by_op,
        by_program=by_program, gaps=gaps)
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
