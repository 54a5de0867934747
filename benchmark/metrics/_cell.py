"""Not a metric: what the readers of the kernel metrics share — the
running cell's configuration, found from the record, and its own
reference's counts of a kernel's work. A reader names no configuration, so
a new configuration lists its cell under a metric's ``workloads`` in
``BENCHMARK.json`` and brings the counts in its reference file."""

import json
import os

from benchmark import work
from benchmark.reference.steps import model_reference

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")


def cell_config(record, metric: str):
    """The configuration the record's run ran: of the cells
    ``BENCHMARK.json`` lists under `metric` (every cell where it lists
    none), the one whose step's operations (``work.step_flops``) are the
    record's. None where none is — a record knows its work, not its name."""
    if "work" not in record:
        return None
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        if cell["name"] not in entry.get("workloads", [cell["name"]]):
            continue
        with open(os.path.join(_ROOT, files[cell["config"]])) as f:
            cfg = json.load(f)
        if work.step_flops(cfg) == record["work"]["flops"]:
            return cfg
    return None


def reference_count(record, metric: str, count: str):
    """(the running cell's configuration, its reference's `count` of it);
    (None, None) where the record is of no cell `metric` lists or the
    cell's reference counts no such work."""
    cfg = cell_config(record, metric)
    counted = cfg and getattr(model_reference(cfg), count, None)
    return (cfg, counted(cfg)) if counted else (None, None)


def step_flops(macs_per_example: float, cfg: dict) -> float:
    """Forward and backward: 6 operations a multiply-add, a batch."""
    return 6.0 * cfg["trainer"]["global_batch_size"] * macs_per_example


def peak_share_pct(record, metric: str, seconds, count: str):
    """A kernel's share of the chip's peak: the running cell's reference's
    `count` of one example's multiply-adds, as a step's operations, over
    the published peak, over the kernel's `seconds` a step. None where
    there is no time, no peak, or no such count to read."""
    if seconds is None or "peaks" not in record:
        return None
    cfg, macs = reference_count(record, metric, count)
    if cfg is None:
        return None
    return (100.0 * step_flops(macs, cfg)
            / record["peaks"]["flops_per_s"] / seconds)
