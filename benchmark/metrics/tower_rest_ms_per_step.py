"""Layer device step: milliseconds a training step spends under the program's
device scope ``tower``: the model's loss, forward and backward, that no finer
scope claims: a CTR tower whole; of a token tower the norms, the residual
stream and the token gradients.
From the traced cycle's ``by_op`` joined with the program's own table of
its instructions' stages (``_scopes.py``). None where the program has no
table or nothing ran under the scope."""

from benchmark.metrics import _scopes


def read(record):
    return _scopes.ms_per_step(record, "tower")
