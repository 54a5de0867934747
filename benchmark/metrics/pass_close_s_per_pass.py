"""Layer pass boundary: seconds from the end of the step loop to the
pass's numbers — the pending table apply, ``feed_mgr.end_pass``, the drain
(the training thread waiting for every queued step) and the AUC read —
mean over the measured passes. Source: the program's stage timer
``Trainer.timers`` ``close``."""

from benchmark.metrics.pass_head_s_per_pass import stage_mean


def read(record):
    return stage_mean(record, "close")
