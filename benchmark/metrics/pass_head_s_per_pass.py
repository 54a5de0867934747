"""Layer pass boundary: seconds from the entry of ``Trainer.train_pass``
to the dispatch of the pass's first step, mean over the measured passes —
``unique_keys``, the boundary, ``preplan`` and the first batch's pack wait
and H2D, through all of which the device idles. Source: the program's
stage timer ``Trainer.timers`` ``head``."""


def stage_mean(record, stage):
    """Mean seconds a pass of `stage`, over the passes whose program has
    that stage; None where none has (a program from before the stage)."""
    passes = [p for p in record["passes"] if stage in p["timers"]]
    if not passes:
        return None
    return sum(p["timers"][stage] for p in passes) / len(passes)


def read(record):
    return stage_mean(record, "head")
