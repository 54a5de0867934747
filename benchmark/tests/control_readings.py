"""By hand, on the chip: the readings the limits' upper ends are set from.

    python3 benchmark/tests/control_readings.py <workload> <seed> [<seed> ...]

At the cell's own size (its batch, widths and hotness; the first followed
batches of its own traffic) and with no program in the loop: the plain
reference is followed in float32, then put in the program's place as the
control (every value and operation in bfloat16) and with each fault
planted (half of the batch left out, the mean over the rest; a state left
unchanged reads 1 by the measure and is run only to show it). Each is
compared with the float32 reference exactly as a run compares the
program and judged by the cell's own limits; the numbers, and which of
them fail, are printed one JSON line a seed. The script fails if the
control or a fault comes out correct on any seed.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(workload: str, seeds: list[int]) -> None:
    import jax
    import jax.numpy as jnp
    from benchmark import correct, datagen, run
    from benchmark.reference import steps

    _, _, cfg, mix = run.load_cell(workload, os.environ.get("WAITING"))
    batch = cfg["trainer"]["global_batch_size"]
    n_sparse, dense_dim = datagen.slot_counts(cfg)
    hot = datagen.slot_hotness(mix, n_sparse)
    n = run.FOLLOWED_STEPS
    dim = cfg["embedding"]["dim"]
    passed = []
    for seed in seeds:
        batches = datagen.make_passes(mix, n_sparse, dense_dim, batch,
                                      seed)[0].batches(batch, n)
        params0 = steps.initial_params(cfg, seed)
        ref = steps.follow(cfg, params0, batches, hot, seed)
        line = {"workload": workload, "seed": seed,
                "platform": jax.devices()[0].platform}
        for name, kw in (("control_bfloat16", {"dtype": jnp.bfloat16}),
                         ("fault_half_batch", {"fault": "half_batch"}),
                         ("fault_state_unchanged",
                          {"fault": "state_unchanged"})):
            got = steps.follow(cfg, params0, batches, hot, seed, **kw)
            numbers, notes = correct.compare(got, ref, dim)
            # what the control cannot show is taken as sound
            numbers["ingest_mismatch"] = numbers["window_counter_mismatch"] = 0
            ok, table, _ = correct.judge(numbers, mix["limits"])
            failing = [k for k, row in table.items()
                       if not row["value"] <= row["limit"]]
            line[name] = {**numbers, **notes, "fails": failing}
            if ok:
                passed.append((seed, name))
        print(json.dumps(line), flush=True)
    if passed:
        sys.exit(f"came out correct: {passed}")


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:]])
