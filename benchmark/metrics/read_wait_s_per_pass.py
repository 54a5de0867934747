"""Layer pack: seconds per pass the training thread waited for the pack
thread's next batch (translate + plan + pack) — the device starved by the
host. Source: the program's stage timer ``Trainer.timers`` ``read``."""


def read(record):
    passes = [p for p in record["passes"] if "read" in p["timers"]]
    if not passes:
        return None
    return sum(p["timers"]["read"] for p in passes) / len(passes)
