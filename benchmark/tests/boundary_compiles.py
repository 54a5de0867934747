"""By hand, in the sandbox (no chip, no time): what the pass boundary of
one cell compiles after its warm-up, for one seed a process.

    JAX_PLATFORMS=cpu python3 benchmark/tests/boundary_compiles.py <workload> <seed>

The cell's own traffic at its own size (pass sets A and B of the seed)
drives the program's ``FeedPassManager`` alone — the table at its full
width, no tower — through the benchmark's order: A, B as the warm-up,
then A, B, A as a window would. Prints one JSON line: the fresh and the
retiring rows of every boundary and the programs compiled after the
warm-up, which must be none (``run.py`` exits 4 on one inside a window).
The step programs' shapes do not depend on the seed; the boundary's did,
through the counts of fresh and retiring rows.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(workload: str, seed: int) -> int:
    import numpy as np
    from benchmark import datagen, run
    from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
    from paddlebox_tpu.embedding.feed_pass import FeedPassManager
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.utils.compile_cache import CompileMeter

    _, _, cfg, mix = run.load_cell(workload, os.environ.get("WAITING"))
    n_sparse, dense_dim = datagen.slot_counts(cfg)
    batch = cfg["trainer"]["global_batch_size"]
    passes = datagen.make_passes(mix, n_sparse, dense_dim, batch, seed)
    keys = [p.unique_keys() for p in passes]
    store = HostEmbeddingStore(EmbeddingConfig(**cfg["embedding"],
                                               seed=seed))
    mgr = FeedPassManager(store, make_mesh(1))
    meter = CompileMeter()
    boundaries, warm = [], None
    for k in range(run.WARMUP_PASSES + 3):
        if k == run.WARMUP_PASSES:
            warm = meter.snapshot()
        ws = mgr.begin_pass(keys[k % 2])
        mgr.pass_opened()
        ws.touched[1:1 + ws.num_keys] = True     # a pass trains every row
        mgr.pass_closed()
        mgr.end_pass(ws)
        boundaries.append({"fresh": int(mgr.last_fresh_rows),
                           "reused": int(mgr.last_reused_rows),
                           "rows": int(ws.padded_rows)})
    after = meter.since(warm)
    print(json.dumps({"workload": workload, "seed": seed,
                      "keys": [len(k) for k in keys],
                      "a_not_b": int(len(np.setdiff1d(*keys))),
                      "b_not_a": int(len(np.setdiff1d(*keys[::-1]))),
                      "boundaries": boundaries,
                      "compiled_after_warmup": after}), flush=True)
    return 1 if after["compilations"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2])))
