"""Layer pass boundary: seconds of ``dataset.unique_keys()`` at the head
of ``train_pass`` (the pass's tokens deduplicated into the key set the
boundary diffs), mean over the measured passes. Source: the program's
stage timer ``Trainer.timers`` ``unique_keys``."""

from benchmark.metrics.pass_head_s_per_pass import stage_mean


def read(record):
    return stage_mean(record, "unique_keys")
