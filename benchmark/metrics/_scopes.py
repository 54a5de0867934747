"""Not a metric: what the readers of the device-scope metrics share — the
traced cycle's device seconds by the program's own stages. The program
writes its step under named scopes and says, for every program it
compiled, which stage each instruction belongs to
(``paddlebox_tpu.monitor.device_scopes.TABLE``: ``{module: {instruction:
{"result", "scope"}}}``, built under the traced run's own capture); the
record's ``trace["by_op"]`` gives seconds by ``<instruction> <first result
shape>``. The join forms that label for every row of the table with
``trace_reduce.op_label`` and sums a label's seconds under its row's
scope.

This is the benchmark's second touch point with the program after
``sut.py``: a ``benchmark`` PR may move it there and name it in
``benchmark/README.md`` (the PR that brought it, ISSUE 38, could edit
neither). A program from before the table has no such module: every
reader here then reads nothing."""

from benchmark import trace_reduce

# what a label's seconds count under where no one scope is its own: the
# table's row has no scope; two programs give the label different scopes;
# no row gives the label
UNSCOPED, AMBIGUOUS, UNKNOWN = "unscoped", "ambiguous", "unknown"


def table():
    try:
        from paddlebox_tpu.monitor import device_scopes
    except ImportError:
        return {}
    return device_scopes.TABLE


def seconds_by_scope(record):
    """``{scope: seconds}`` over the record's ``by_op``, with the rows
    ``unscoped``, ``ambiguous`` and ``unknown`` beside the program's
    scopes; the values sum to ``by_op``'s. None where the record has no
    device trace or the program no table."""
    trace = record.get("trace")
    rows = table()
    if not trace or not trace.get("devices") or not rows:
        return None
    scopes_of: dict[str, set] = {}
    for module in rows.values():
        for name, row in module.items():
            label = trace_reduce.op_label(f"%{name} = {row['result']}")
            scopes_of.setdefault(label, set()).add(row["scope"])
    out: dict[str, float] = {}
    for label, seconds in trace["by_op"].items():
        held = scopes_of.get(label)
        scope = (UNKNOWN if held is None else AMBIGUOUS if len(held) > 1
                 else next(iter(held)) or UNSCOPED)
        out[scope] = out.get(scope, 0.0) + seconds
    return out


def ms_per_step(record, scope: str):
    """Milliseconds a step of the traced pass under `scope`; None where
    :func:`seconds_by_scope` reads nothing or nothing ran under it."""
    by_scope = seconds_by_scope(record)
    steps = sum(p["steps"] for p in record["passes"])
    if by_scope is None or not steps or not by_scope.get(scope):
        return None
    return by_scope[scope] * 1e3 / steps
