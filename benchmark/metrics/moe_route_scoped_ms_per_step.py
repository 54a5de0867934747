"""Layer kernels: milliseconds a training step spends under the program's
device scope ``route``: the experts' routing: the router's logits and rule,
the sort, the rows moved into and out of the sorted copy, the ladder's
switch (``parallel/expert.py``), by the program's own scope, where
``moe_route_ms_per_step`` goes by shapes.
From the traced cycle's ``by_op`` joined with the program's own table of
its instructions' stages (``_scopes.py``). None where the program has no
table or nothing ran under the scope."""

from benchmark.metrics import _scopes


def read(record):
    return _scopes.ms_per_step(record, "route")
