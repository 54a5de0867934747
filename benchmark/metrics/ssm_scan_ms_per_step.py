"""Layer kernels: milliseconds a training step spends in the state-space
scan's kernels, forward, recomputed forward and backward (``pbtpu_ssm_fwd``
/ ``pbtpu_ssm_bwd``, ``ops/ssm_scan.py``), from the trace's ``XLA Ops``
line over the steps of the traced pass. None where no such kernel ran."""

from benchmark.metrics.attention_ms_per_step import kernel_seconds


def read(record):
    s = kernel_seconds(record, "pbtpu_ssm")
    return None if s is None else s * 1e3
