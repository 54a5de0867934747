"""Layer kernels: milliseconds a training step spends in the attention
kernels, forward and backward (``pbtpu_attention_fwd`` / ``_dq`` /
``_dkv``, ``ops/flash_attention.py``), from the trace's ``XLA Ops`` line
over the steps of the traced pass. None where no such kernel ran."""


def kernel_seconds(record, *needles):
    """Seconds per step of the device operations whose name holds one of
    `needles`; None where the trace has none."""
    trace = record.get("trace")
    steps = sum(p["steps"] for p in record["passes"])
    if not trace or not trace.get("devices") or not steps:
        return None
    total = sum(s for name, s in trace["by_op"].items()
                if any(n in name.split(" ")[0] for n in needles))
    return total / steps if total > 0 else None


def read(record):
    s = kernel_seconds(record, "pbtpu_attention")
    return None if s is None else s * 1e3
