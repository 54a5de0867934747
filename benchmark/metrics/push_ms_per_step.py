"""Layer kernels: milliseconds a training step spends under the program's
device scope ``push``: the merged update written to the table: the deferred
``jit_apply`` whole, or the push's tail inside the step.
From the traced cycle's ``by_op`` joined with the program's own table of
its instructions' stages (``_scopes.py``). None where the program has no
table or nothing ran under the scope."""

from benchmark.metrics import _scopes


def read(record):
    return _scopes.ms_per_step(record, "push")
