"""Layer kernels: the scan kernels' share of their roofline — the least
time the chip could take for one step's scans, the larger of their
multiply-adds (the cell's reference's ``ssm_scan_macs`` x 6 x the batch,
forward and backward, recomputation not counted) over the published peak
and their bytes (``ssm_scan_bytes``) over the published bandwidth, over
the kernels' seconds a step. None where no such kernel ran.

The configuration and the reference are the running cell's own, found
from the record (``_cell.cell_config``), so one reader serves every
configuration whose reference counts a scan."""

from benchmark.metrics import _cell
from benchmark.metrics._cell import cell_config  # noqa: F401  (its old home)
from benchmark.metrics.attention_ms_per_step import kernel_seconds
from benchmark.reference.steps import model_reference

_NAME = __name__.rpartition(".")[2]


def read(record):
    s = kernel_seconds(record, "pbtpu_ssm")
    if s is None or "peaks" not in record:
        return None
    cfg = cell_config(record, _NAME)
    if cfg is None:
        return None
    reference = model_reference(cfg)
    flops = _cell.step_flops(reference.ssm_scan_macs(cfg), cfg)
    least = max(flops / record["peaks"]["flops_per_s"],
                reference.ssm_scan_bytes(cfg)
                / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / s
