"""Layer kernels: the delta rule's kernels' share of their roofline — the
least time the chip could take for one step's delta rules, the larger of
their multiply-adds (the cell's reference's ``kda_macs`` x 6 x the batch,
forward and backward, recomputation not counted) over the published peak
and their bytes (``kda_bytes``) over the published bandwidth, over the
kernels' seconds a step. None where no such kernel ran, or where the
running cell's reference counts no delta rule.

The configuration and the reference are the running cell's own, found
from the record (``_cell.reference_count``), so one reader serves every
configuration whose reference counts a delta rule."""

from benchmark.metrics import _cell
from benchmark.metrics.attention_ms_per_step import kernel_seconds

_NAME = __name__.rpartition(".")[2]


def read(record):
    s = kernel_seconds(record, "pbtpu_kda")
    if s is None or "peaks" not in record:
        return None
    cfg, macs = _cell.reference_count(record, _NAME, "kda_macs")
    _, moved = _cell.reference_count(record, _NAME, "kda_bytes")
    if cfg is None or moved is None:
        return None
    least = max(_cell.step_flops(macs, cfg) / record["peaks"]["flops_per_s"],
                moved / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / s
