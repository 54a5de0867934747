"""Layer kernels: milliseconds a training step spends in the delta rule's
kernels, forward, recomputed forward and backward (``pbtpu_kda_fwd`` /
``pbtpu_kda_bwd``, ``ops/kda.py``), from the trace's ``XLA Ops`` line over
the steps of the traced pass. None where no such kernel ran."""

from benchmark.metrics.attention_ms_per_step import kernel_seconds


def read(record):
    s = kernel_seconds(record, "pbtpu_kda")
    return None if s is None else s * 1e3
