"""Layer kernels: the gated short convolution's kernels' share of their
roofline — the least time the chip could take for one step's operators,
the larger of their bytes (the cell's reference's ``short_conv_bytes``,
forward and backward, recomputation not counted) over the published
bandwidth and their multiply-adds (``short_conv_macs`` x 6 x the batch)
over the published peak, over the kernels' seconds a step. None where no
such kernel ran.

The configuration and the reference are the running cell's own, found
from the record (``_cell.cell_config``), so one reader serves every
configuration whose reference counts a short convolution."""

from benchmark.metrics import _cell
from benchmark.metrics.attention_ms_per_step import kernel_seconds
from benchmark.reference.steps import model_reference

_NAME = __name__.rpartition(".")[2]


def read(record):
    s = kernel_seconds(record, "pbtpu_short_conv")
    if s is None or "peaks" not in record:
        return None
    cfg = _cell.cell_config(record, _NAME)
    reference = cfg and model_reference(cfg)
    if not hasattr(reference, "short_conv_bytes"):
        return None
    flops = _cell.step_flops(reference.short_conv_macs(cfg), cfg)
    least = max(reference.short_conv_bytes(cfg)
                / record["peaks"]["hbm_bytes_per_s"],
                flops / record["peaks"]["flops_per_s"])
    return 100.0 * least / s
