"""Layer device: the share of the traced cycle's device seconds that the
program's table of its instructions' stages puts under no scope of its
own — an instruction outside every scope, a label two programs give
different scopes, an event of no instruction the table holds — over all
``by_op`` seconds (``_scopes.py``). What the other scope metrics cannot
see. None where the program has no table."""

from benchmark.metrics import _scopes

NO_SCOPE = (_scopes.UNSCOPED, _scopes.AMBIGUOUS, _scopes.UNKNOWN)


def read(record):
    by_scope = _scopes.seconds_by_scope(record)
    total = sum(by_scope.values()) if by_scope else 0.0
    if not total:
        return None
    return 100.0 * sum(by_scope.get(k, 0.0) for k in NO_SCOPE) / total
