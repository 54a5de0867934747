"""Layer kernels: the step's share of the HBM roofline — the bytes the
step's sparse work and tower must move whatever implements them
(``benchmark/work.py``: pull, push, tower) over the published bandwidth,
over the device seconds per step."""

from benchmark.metrics.device_step_ms import step_seconds


def read(record):
    s = step_seconds(record)
    if s is None or "work" not in record:
        return None
    return (100.0 * record["work"]["bytes"]["total"]
            / record["peaks"]["hbm_bytes_per_s"] / s)
