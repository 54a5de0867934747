"""Layer H2D stage: milliseconds per step the training thread spends in
``Trainer._stage_device`` (one ``device_put`` of the packed batch and its
plan; the dispatch, not the transfer's landing). Source: the program's
stage timer ``Trainer.timers`` ``h2d``."""


def read(record):
    passes = [p for p in record["passes"] if "h2d" in p["timers"]]
    steps = sum(p["steps"] for p in passes)
    if not steps:
        return None
    return 1e3 * sum(p["timers"]["h2d"] for p in passes) / steps
