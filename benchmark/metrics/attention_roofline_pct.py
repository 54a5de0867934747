"""Layer kernels: the attention kernels' share of the chip's peak — the
scores' and values' multiply-adds of one step, forward and backward, the
masked part not counted (``reference/smallthinker.py::attention_macs`` x 6
x the batch), over the published peak, over the kernels' seconds a step."""

from benchmark.metrics import _smallthinker as smallthinker_work
from benchmark.metrics.attention_ms_per_step import kernel_seconds


def read(record):
    s = kernel_seconds(record, "pbtpu_attention")
    if s is None or "peaks" not in record:
        return None
    return (100.0 * smallthinker_work.attention_flops()
            / record["peaks"]["flops_per_s"] / s)
