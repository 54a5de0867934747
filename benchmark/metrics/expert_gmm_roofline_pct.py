"""Layer kernels: the grouped matrix products' share of the chip's peak —
the expected held share of the routed multiply-adds of one step, forward
and backward (``reference/smallthinker.py::expert_gmm_macs`` x 6 x the
batch x the layers), over the published peak, over their seconds a step."""

from benchmark.metrics import _smallthinker as smallthinker_work
from benchmark.metrics.attention_ms_per_step import kernel_seconds
from benchmark.metrics.expert_gmm_ms_per_step import GMM


def read(record):
    s = kernel_seconds(record, *GMM)
    if s is None or "peaks" not in record:
        return None
    return (100.0 * smallthinker_work.expert_flops()
            / record["peaks"]["flops_per_s"] / s)
