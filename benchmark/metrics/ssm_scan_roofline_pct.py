"""Layer kernels: the scan kernels' share of their roofline — the least
time the chip could take for one step's scans, the larger of their
multiply-adds (the cell's reference's ``ssm_scan_macs`` x 6 x the batch,
forward and backward, recomputation not counted) over the published peak
and their bytes (``ssm_scan_bytes``) over the published bandwidth, over
the kernels' seconds a step. None where no such kernel ran.

The configuration and the reference are the running cell's own, found
from the record (``cell_config``), so one reader serves every
configuration whose reference counts a scan."""

import json
import os

from benchmark import work
from benchmark.metrics.attention_ms_per_step import kernel_seconds
from benchmark.reference.steps import model_reference

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
_NAME = os.path.splitext(os.path.basename(__file__))[0]


def cell_config(record, metric: str):
    """The configuration the record's run ran: of the cells
    ``BENCHMARK.json`` lists under `metric` (every cell where it lists
    none), the one whose step's operations (``work.step_flops``) are the
    record's. None where none is — a record knows its work, not its name."""
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


def read(record):
    s = kernel_seconds(record, "pbtpu_ssm")
    if s is None or "peaks" not in record:
        return None
    cfg = cell_config(record, _NAME)
    if cfg is None:
        return None
    reference = model_reference(cfg)
    flops = 6.0 * cfg["trainer"]["global_batch_size"] \
        * reference.ssm_scan_macs(cfg)
    least = max(flops / record["peaks"]["flops_per_s"],
                reference.ssm_scan_bytes(cfg)
                / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / s
