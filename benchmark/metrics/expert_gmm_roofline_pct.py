"""Layer kernels: the grouped matrix products' share of the chip's peak —
the expected held share of the routed multiply-adds of one step, forward
and backward (the running cell's own reference's ``expert_gmm_macs`` x 6 x
the batch), over the published peak, over their seconds a step. The
configuration is found from the record (``_cell.cell_config``): the reader
names none."""

from benchmark.metrics import _cell
from benchmark.metrics.attention_ms_per_step import kernel_seconds
from benchmark.metrics.expert_gmm_ms_per_step import GMM

_NAME = __name__.rpartition(".")[2]


def read(record):
    return _cell.peak_share_pct(record, _NAME, kernel_seconds(record, *GMM),
                                "expert_gmm_macs")
