"""Layer kernels: milliseconds a training step spends under the program's
device scope ``dense_mlp``: a dense MLP or shared expert that every token
goes through.
From the traced cycle's ``by_op`` joined with the program's own table of
its instructions' stages (``_scopes.py``). None where the program has no
table or nothing ran under the scope."""

from benchmark.metrics import _scopes


def read(record):
    return _scopes.ms_per_step(record, "dense_mlp")
