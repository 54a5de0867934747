"""Layer pack: seconds of the pass's head that the first batch takes —
the pack thread's first translate + plan + pack, the training thread's
wait for it, and its H2D — mean over the measured passes: ``head`` less
``unique_keys``, ``preplan`` and the boundary. Source: the program's stage
timers ``Trainer.timers`` and ``feed_mgr.last_boundary_seconds``."""


def read(record):
    passes = [p for p in record["passes"]
              if {"head", "unique_keys", "preplan"} <= set(p["timers"])]
    if not passes:
        return None
    return sum(p["timers"]["head"] - p["timers"]["unique_keys"]
               - p["timers"]["preplan"] - p["boundary_s"]
               for p in passes) / len(passes)
