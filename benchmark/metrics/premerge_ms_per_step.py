"""Layer kernels: milliseconds a training step spends under the program's
device scope ``premerge``: the token gradients, shows and clicks merged onto the
host plan's unique lanes (``sharded.plan_premerge``; in a deferred step
the push's operands).
From the traced cycle's ``by_op`` joined with the program's own table of
its instructions' stages (``_scopes.py``). None where the program has no
table or nothing ran under the scope."""

from benchmark.metrics import _scopes


def read(record):
    return _scopes.ms_per_step(record, "premerge")
