"""Layer pass boundary: megabytes moved host to device to build the
pass's working set, mean over the measured passes. A count: it repeats
exactly for one seed. Source: the program's counter
``feed_mgr.last_h2d_bytes``."""


def read(record):
    passes = record["passes"]
    if not passes:
        return None
    return sum(p["boundary_h2d_bytes"] for p in passes) / len(passes) / 1e6
