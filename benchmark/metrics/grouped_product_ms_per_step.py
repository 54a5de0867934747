"""Layer kernels: milliseconds a training step spends in the grouped
matrix products of the held experts, forward and backward, whichever
implements them under ``parallel/expert.py::held_expert_ffn``: XLA's
``ragged-dot`` kernels (the program before ``ops/grouped_matmul.py``) or
the repo's own ``pbtpu_gmm`` / ``pbtpu_tgmm``. The tile metadata they need
is the route's on both sides. None where none ran."""

from benchmark.metrics.attention_ms_per_step import kernel_seconds

PRODUCTS = ("ragged-dot-none", "pbtpu_gmm", "pbtpu_tgmm")


def read(record):
    s = kernel_seconds(record, *PRODUCTS)
    return None if s is None else s * 1e3
