"""Layer device step: the whole step's share of the chip's peak — the
model's forward and backward FLOPs for one batch (``benchmark/work.py``)
over the published peak, over the device seconds per step. Counts the
algorithm's work, so it stays valid when a kernel is swapped or removed."""

from benchmark.metrics.device_step_ms import step_seconds


def read(record):
    s = step_seconds(record)
    if s is None or "work" not in record:
        return None
    return 100.0 * record["work"]["flops"] / record["peaks"]["flops_per_s"] / s
