"""Layer kernels: milliseconds a training step spends in the grouped
matrix products of the held experts, forward and backward (XLA's
``ragged-dot`` kernels under ``parallel/expert.py::held_expert_ffn``; the
tile metadata they compute first is the route's). None where none ran."""

from benchmark.metrics.attention_ms_per_step import kernel_seconds

GMM = ("ragged-dot-none",)


def read(record):
    s = kernel_seconds(record, *GMM)
    return None if s is None else s * 1e3
