"""By hand, on the chip: ``control_readings.py`` for a tower whose
snapshots do not fit the host twice over — one planted variant a process.

    python3 benchmark/tests/control_one.py <workload> <seed> <variant>

`variant`: ``control_bfloat16`` | ``fault_half_batch`` |
``fault_state_unchanged``. The float32 reference is followed first and
only the leaves the comparison reads are kept (the first gradient's
moments, the last step's parameters, the rows); then the variant is
followed and compared exactly as ``control_readings.py`` does, judged by
the cell's own limits. One JSON line; exit 1 if it comes out correct.
(36 B a parameter of snapshots for the reference and the variant side by
side, beside the compile's own memory, pass 40 GiB at 5.6e8 parameters.)
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

VARIANTS = {"control_bfloat16": {"dtype": "bfloat16"},
            "fault_half_batch": {"fault": "half_batch"},
            "fault_state_unchanged": {"fault": "state_unchanged"}}


def main(workload: str, seed: int, variant: str) -> int:
    import gc
    import jax
    import jax.numpy as jnp
    from benchmark import correct, datagen, run
    from benchmark.reference import steps

    _, _, cfg, mix = run.load_cell(workload, os.environ.get("WAITING"))
    batch = cfg["trainer"]["global_batch_size"]
    n_sparse, dense_dim = datagen.slot_counts(cfg)
    hot = datagen.slot_hotness(mix, n_sparse)
    n = run.FOLLOWED_STEPS
    batches = datagen.make_passes(mix, n_sparse, dense_dim, batch,
                                  seed)[0].batches(batch, n)
    params0 = steps.initial_params(cfg, seed)
    ref = steps.follow(cfg, params0, batches, hot, seed)
    # what correct.compare reads of the reference, and no more
    ref["after"][1].pop("params")
    ref["after"][n].pop("m")
    gc.collect()
    kw = dict(VARIANTS[variant])
    if "dtype" in kw:
        kw["dtype"] = getattr(jnp, kw["dtype"])
    got = steps.follow(cfg, params0, batches, hot, seed, **kw)
    got["after"][1].pop("params")
    got["after"][n].pop("m")
    got.pop("params0")
    del params0
    gc.collect()
    numbers, notes = correct.compare(got, ref, cfg["embedding"]["dim"])
    numbers["ingest_mismatch"] = numbers["window_counter_mismatch"] = 0
    ok, table, _ = correct.judge(numbers, mix["limits"])
    failing = [k for k, row in table.items()
               if not row["value"] <= row["limit"]]
    print(json.dumps({"workload": workload, "seed": seed,
                      "platform": jax.devices()[0].platform,
                      variant: {**numbers, **notes, "fails": failing}}),
          flush=True)
    return 1 if ok else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]), sys.argv[3]))
