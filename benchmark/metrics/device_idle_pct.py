"""Layer device: share of the traced pass cycle (load to end_pass) in
which no operation ran on the device: 1 - union of the device's operation
intervals over the traced window."""


def read(record):
    trace = record.get("trace")
    if not trace or not trace.get("devices"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
