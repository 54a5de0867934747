"""Layer kernels: the grouped matrix products' share of the chip's peak —
the expected held share of the routed multiply-adds of one step, forward
and backward (the running cell's own reference's ``expert_gmm_macs`` x 6 x
the batch), over the published peak, over their seconds a step, whichever
implements them (``grouped_product_ms_per_step.PRODUCTS``). The work is
the algorithm's, not a kernel's: a tile visited twice, or a recomputed
forward, counts once. The configuration is found from the record
(``_cell.cell_config``): the reader names none."""

from benchmark.metrics import _cell
from benchmark.metrics.attention_ms_per_step import kernel_seconds
from benchmark.metrics.grouped_product_ms_per_step import PRODUCTS

_NAME = __name__.rpartition(".")[2]


def read(record):
    return _cell.peak_share_pct(record, _NAME,
                                kernel_seconds(record, *PRODUCTS),
                                "expert_gmm_macs")
