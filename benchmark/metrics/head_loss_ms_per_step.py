"""Layer kernels: milliseconds a training step spends under the program's
device scope ``head_loss``: a token tower's final norm, head and next-token
loss in chunks (``models/nn.py::next_token_loss``).
From the traced cycle's ``by_op`` joined with the program's own table of
its instructions' stages (``_scopes.py``). None where the program has no
table or nothing ran under the scope."""

from benchmark.metrics import _scopes


def read(record):
    return _scopes.ms_per_step(record, "head_loss")
