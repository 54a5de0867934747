"""What decides ``correct``, at a size a test run can hold.

* the program through ``--rehearse`` agrees with the plain reference;
* the control — the reference in the program's place, computed in
  bfloat16 — comes out not correct under the cell's own limits;
* the rest of a run driven with the timed path broken underneath comes
  out not correct, once for each fault a one-chip training cell can have.
"""

import argparse
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WAITING = "benchmark/waiting_cells.json"    # cells kept ready, not added yet
CELLS = []
for _name in ("BENCHMARK.json", WAITING):
    with open(os.path.join(ROOT, _name)) as _f:
        CELLS += [w["name"] for w in json.load(_f)["workloads"]]


def _args(cell, seed):
    return argparse.Namespace(workload=cell, seed=seed, seconds=0.2, trace=0,
                              rehearse=True, keep_trace=None,
                              waiting=WAITING)


@pytest.mark.parametrize("cell", CELLS)
def test_program_agrees_with_reference(cell):
    from benchmark import run
    code, result = run.run(_args(cell, 2 ** 31 + 101))
    assert code == 0 and result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0


def _followed(cell, seed):
    from benchmark import datagen, run
    from benchmark.reference import steps
    cfg, mix = run.rehearsal_sizes(*run.load_cell(cell, WAITING)[2:])
    batch = cfg["trainer"]["global_batch_size"]
    n_sparse, dense_dim = datagen.slot_counts(cfg)
    hot = datagen.slot_hotness(mix, n_sparse)
    batches = datagen.make_passes(mix, n_sparse, dense_dim, batch,
                                  seed)[0].batches(batch, run.FOLLOWED_STEPS)
    return cfg, mix, hot, batches, steps.initial_params(cfg, seed)


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_is_not_correct(cell):
    import jax.numpy as jnp
    from benchmark import correct
    from benchmark.reference import steps
    seed = 2 ** 31 + 202
    cfg, mix, hot, batches, params0 = _followed(cell, seed)
    ref = steps.follow(cfg, params0, batches, hot, seed)
    ctl = steps.follow(cfg, params0, batches, hot, seed, dtype=jnp.bfloat16)
    numbers, _ = correct.compare(ctl, ref, cfg["embedding"]["dim"])
    ok, table, _ = correct.judge(numbers, mix["limits"])
    assert not ok, table
    # and the reference against itself passes every limit
    numbers, _ = correct.compare(ref, ref, cfg["embedding"]["dim"])
    numbers["ingest_mismatch"] = numbers["window_counter_mismatch"] = 0
    assert correct.judge(numbers, mix["limits"])[0]


def _unchanged_state(monkeypatch):
    import optax
    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)


def _half_batch(monkeypatch):
    import jax.numpy as jnp
    import optax
    real = optax.sigmoid_binary_cross_entropy

    def first_half_only(logits, labels):
        n = logits.shape[0]
        keep = (jnp.arange(n) < n // 2).astype(logits.dtype)
        return real(logits, labels) * keep * (n / (n // 2))

    monkeypatch.setattr(optax, "sigmoid_binary_cross_entropy",
                        first_half_only)


def _write_back_dropped(monkeypatch):
    """From the end of the followed steps on, nothing the device held
    reaches the host store: rows that retire at a boundary, and what is
    flushed at the end, keep the store's old bytes."""
    from benchmark.tests.fault_readings import drop_write_back_after_probe
    drop_write_back_after_probe(monkeypatch.setattr)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _write_back_dropped])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    """The rest of a run — traffic, set-up, window, probe, reference,
    comparison — with the program's step broken underneath."""
    from benchmark import run
    fault(monkeypatch)
    code, result = run.run(_args(cell, 2 ** 31 + 303))
    assert code == 0 and result["correct"] is False, result["compared"]
    if fault is _write_back_dropped:
        # the first steps are sound: only what the passes left shows it
        failing = {k for k, row in result["compared"].items()
                   if not row["value"] <= row["limit"]}
        assert failing == {"window_counter_mismatch"}


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_faults_planted_in_the_reference_are_not_correct(fault):
    from benchmark import correct
    from benchmark.reference import steps
    seed = 2 ** 31 + 404
    cfg, mix, hot, batches, params0 = _followed(CELLS[0], seed)
    ref = steps.follow(cfg, params0, batches, hot, seed)
    bad = steps.follow(cfg, params0, batches, hot, seed, fault=fault)
    numbers, _ = correct.compare(bad, ref, cfg["embedding"]["dim"])
    assert not correct.judge(numbers, mix["limits"])[0]


@pytest.mark.parametrize("hotness,cap", [(1, 300), (3, 5000), (3, None)])
def test_window_counters_are_the_generators_own_counts(hotness, cap):
    """The expected show and click counts against a count by hand, and a
    miscount of one row is seen."""
    import numpy as np
    from benchmark import correct, datagen, run
    _, _, cfg, mix = run.load_cell(CELLS[0], WAITING)
    mix = {**mix, "steps_per_pass": 2, "hotness": hotness,
           "len": "uniform_1_to_hotness", "max_ind_range": cap}
    passes = datagen.make_passes(mix, 26, 13, 512, 2 ** 31 + 505)
    keys = correct.sample_keys(passes, 200, 2 ** 31 + 505)
    rows = np.zeros((len(keys), 5), np.float32)
    for i, key in enumerate(keys):
        hits = [(p.ids == key).sum(axis=1) for p in passes]
        rows[i, 0] = 3 * hits[0].sum() + 2 * hits[1].sum()
        rows[i, 1] = 3 * (hits[0] * passes[0].labels).sum() \
            + 2 * (hits[1] * passes[1].labels).sum()
    bad, kinds = correct.window_counter_mismatch(rows, keys, passes, [3, 2])
    assert bad == 0 and min(kinds["in_a_alone"], kinds["in_b_alone"]) > 0
    rows[7, 0] += 1
    assert correct.window_counter_mismatch(rows, keys, passes, [3, 2])[0] == 1
