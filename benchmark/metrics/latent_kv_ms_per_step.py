"""Layer kernels: milliseconds a training step spends under the program's
device scope ``latent``: multi-head latent attention's path to its keys
and values — the latent's projection and norm, its expansion to the
heads, the rotations, the shared rotary key's broadcast, q's split and
concat — inside the attention half.
From the traced cycle's ``by_op`` joined with the program's own table of
its instructions' stages (``_scopes.py``). None where the program has no
table, no such scope, or nothing ran under it."""

from benchmark.metrics import _scopes


def read(record):
    return _scopes.ms_per_step(record, "latent")
