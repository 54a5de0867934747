"""Layer kernels: milliseconds a training step spends routing — what of
the expert layer is neither matrix product: the top-k, the sort of the
(token, choice) assignments by held expert, the grouped products' tile
metadata, and the gathers, masks and sums that move rows into and out of
the sorted copy. Found in the trace's ``XLA Ops`` by name (``sort``,
``topk``, ``ragged-dot-metadata``) and by the leading dimensions only the
route has in the running cell: a whole chunk's assignments (the cell's
own reference's ``route_rows``) and each static bound the program may
give a chunk's sorted copy (``sut.route_rungs``) that some grouped
product of the trace ran at — a bound no chunk took leaves nothing to
find, and whatever else of the model is that long is then not the
route's — and the group sizes' count, an integer vector one longer than
the held experts. What another kernel metric counts (the grouped products, the
attention and scan kernels) is not counted again. The configuration is
found from the record (``_cell.cell_config``): the reader names none.
None where nothing of the route ran."""

import re

from benchmark import sut
from benchmark.metrics import _cell
from benchmark.metrics.expert_gmm_ms_per_step import GMM

_NAME = __name__.rpartition(".")[2]
NAMED = ("sort", "topk", "top_k", "top-k", "ragged-dot-metadata")
OTHER_METRICS = GMM + ("pbtpu_attention", "pbtpu_ssm")


def _lead(shape: str):
    lead = re.match(r"\w+\[(\d+)", shape)
    return int(lead[1]) if lead else None


def read(record):
    trace = record.get("trace")
    steps = sum(p["steps"] for p in record["passes"])
    if not trace or not trace.get("devices") or not steps:
        return None
    cfg, shape = _cell.reference_count(record, _NAME, "route_rows")
    if cfg is None:
        return None
    ops = [(*label.partition(" ")[::2], s)
           for label, s in trace["by_op"].items()]
    ran = {_lead(shape_) for name, shape_, _ in ops
           if any(n in name for n in GMM)}
    rows = {shape[0]} | (set(sut.route_rungs(*shape)) & ran)
    sizes = re.compile(r"[su]32\[%d\]$" % (shape[1] + 1))
    total = sum(s for name, shape_, s in ops
                if not any(n in name for n in OTHER_METRICS)
                and (any(n in name for n in NAMED) or _lead(shape_) in rows
                     or sizes.match(shape_)))
    return total * 1e3 / steps if total > 0 else None
