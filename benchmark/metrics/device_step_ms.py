"""Layer device step: milliseconds the device spends per training step in
the step programs — the forward/backward program and, where the push is
deferred, the table-apply program — from the trace's ``XLA Modules`` line,
divided by the steps of the traced pass. The programs are found by the
names JAX gives the trainer's jitted step functions."""

import re

STEP_PROGRAMS = re.compile(r"^jit_(step|step_flat|superstep|apply)$")


def step_seconds(record):
    trace = record.get("trace")
    steps = sum(p["steps"] for p in record["passes"])
    if not trace or not trace.get("devices") or not steps:
        return None
    total = sum(s for name, s in trace["by_program"].items()
                if STEP_PROGRAMS.match(name))
    return total / steps if total > 0 else None


def read(record):
    s = step_seconds(record)
    return None if s is None else s * 1e3
