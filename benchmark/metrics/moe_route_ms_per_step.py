"""Layer kernels: milliseconds a training step spends routing — what of
the expert layer is neither matrix product: the top-k, the sort of the
(token, choice) assignments by held expert, the grouped products' tile
metadata, and the gathers and the combine that move rows into and out of
the sorted order. Found in the trace's ``XLA Ops`` by name (``sort``,
``topk``, ``ragged-dot-metadata``) and by the one shape only the route
has: a chunk's assignments, ``expert_chunk_tokens x experts_per_token``
rows. None where none ran."""

import re

from benchmark.metrics import _smallthinker as smallthinker_work

NAMED = ("sort", "topk", "top_k", "top-k", "ragged-dot-metadata")


def read(record):
    trace = record.get("trace")
    steps = sum(p["steps"] for p in record["passes"])
    if not trace or not trace.get("devices") or not steps:
        return None
    rows = smallthinker_work.route_rows()
    total = 0.0
    for label, s in trace["by_op"].items():
        name, _, shape = label.partition(" ")
        if "ragged-dot-none" in name:
            continue
        lead = re.match(r"\w+\[(\d+)", shape)
        if any(n in name for n in NAMED) or (lead and int(lead[1]) == rows):
            total += s
    return total * 1e3 / steps if total > 0 else None
