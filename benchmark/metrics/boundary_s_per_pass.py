"""Layer pass boundary: seconds the trainer's feed manager took to build
the pass's working set on the device (key diff, host fetch of fresh rows,
H2D, combine with the resident rows), mean over the measured passes.
Source: the program's counter ``feed_mgr.last_boundary_seconds``."""


def read(record):
    passes = record["passes"]
    return sum(p["boundary_s"] for p in passes) / len(passes) if passes \
        else None
