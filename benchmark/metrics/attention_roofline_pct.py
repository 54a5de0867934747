"""Layer kernels: the attention kernels' share of the chip's peak — the
scores' and values' multiply-adds of one step, forward and backward, the
masked part not counted (the running cell's own reference's
``attention_macs`` x 6 x the batch), over the published peak, over the
kernels' seconds a step. The configuration is found from the record
(``_cell.cell_config``): the reader names none."""

from benchmark.metrics import _cell
from benchmark.metrics.attention_ms_per_step import kernel_seconds

_NAME = __name__.rpartition(".")[2]


def read(record):
    return _cell.peak_share_pct(
        record, _NAME, kernel_seconds(record, "pbtpu_attention"),
        "attention_macs")
