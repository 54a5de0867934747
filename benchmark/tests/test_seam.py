"""The seam that lets a configuration bring its own loss over ordered
tokens as files only (``benchmark/README.md``), carrying weight:

(a) a fixture that states the default step's own arithmetic (pool, CVM,
    the pooled tower's logits, the sigmoid cross entropy) through
    ``example_losses`` follows the steps to what ``reference/deepfm.py``
    gives through the default, and runs a whole ``--rehearse`` — program
    and all — as a ``--waiting`` cell made of added files only, at the
    rehearsal sizes its own files state;
(b) a fixture of one ordered slot, a causal block and a next-token cross
    entropy over its vocabulary follows its steps in blocks of examples
    to the unblocked result, changes its loss when two positions swap,
    and fails the bfloat16 control and both planted faults under its own
    limits. The reference half only: the program has no tower that reads
    ordered tokens yet, so its whole run waits for the ``model_config`` PR
    that brings one.

Every fixture is under ``benchmark/tests/fixtures/``: nothing in
``configs/``, ``workloads/`` or ``reference/`` is a test's.
"""

import argparse
import copy

import numpy as np
import pytest

WAITING = "benchmark/tests/fixtures/waiting.json"
POOLED, ORDERED = "own_loss_pooled.onehot", "own_loss_ordered.ordered"


def _followed(cell, seed, rehearse=True, **cfg_over):
    from benchmark import datagen, run
    from benchmark.reference import steps
    _, _, cfg, mix = run.load_cell(cell, WAITING)
    if rehearse:
        cfg, mix = run.rehearsal_sizes(cfg, mix)
    cfg = {**cfg, **cfg_over}
    batch = cfg["trainer"]["global_batch_size"]
    n_sparse, dense_dim = datagen.slot_counts(cfg)
    hot = datagen.slot_hotness(mix, n_sparse)
    batches = datagen.make_passes(mix, n_sparse, dense_dim, batch,
                                  seed)[0].batches(batch, run.FOLLOWED_STEPS)
    return cfg, mix, hot, batches, steps.initial_params(cfg, seed)


def _judged(got, ref, cfg, mix):
    from benchmark import correct
    numbers, _ = correct.compare(got, ref, cfg["embedding"]["dim"])
    # what a followed reference cannot show is taken as sound
    numbers["ingest_mismatch"] = numbers["window_counter_mismatch"] = 0
    return correct.judge(numbers, mix["limits"])


# ---- (a) the default's arithmetic through the seam ----------------------

def test_rehearsal_sizes_are_the_cells_own():
    from benchmark import run
    _, _, cfg, mix = run.load_cell(POOLED, WAITING)
    small, small_mix = run.rehearsal_sizes(cfg, mix)
    assert small["trainer"]["global_batch_size"] == 128
    assert small["model_args"]["hidden"] == [64, 32]
    assert small["model_args"]["emb_dim"] == cfg["model_args"]["emb_dim"]
    assert small["trainer"]["dense_lr"] == cfg["trainer"]["dense_lr"]
    assert (small_mix["steps_per_pass"], small_mix["max_ind_range"]) \
        == (5, 1024)
    assert cfg["trainer"]["global_batch_size"] == 8192     # not edited
    # a cell whose files state none rehearses at the defaults, as before
    _, _, cfg, mix = run.load_cell("dlrm_mlperf.onehot")
    small, small_mix = run.rehearsal_sizes(cfg, mix)
    assert small == {**cfg, "trainer": {
        **cfg["trainer"], "global_batch_size": run.REHEARSAL_BATCH}}
    assert small_mix == {**mix, **run.REHEARSAL}


def test_own_loss_follows_the_steps_as_the_default_does():
    from benchmark.reference import steps
    seed = 2 ** 31 + 606
    cfg, mix, hot, batches, params0 = _followed(POOLED, seed)
    own = steps.follow(cfg, params0, batches, hot, seed)
    default_cfg = {k: v for k, v in cfg.items() if k != "reference"}
    assert steps.model_reference(default_cfg).__name__ \
        == "benchmark.reference.deepfm"
    assert not hasattr(steps.model_reference(default_cfg), "example_losses")
    default = steps.follow(default_cfg, params0, batches, hot, seed)
    np.testing.assert_allclose(own["losses"], default["losses"], rtol=1e-6)
    for k in default["after"]:
        for part in ("params", "m"):
            a, b = own["after"][k][part], default["after"][k][part]
            for x, y in zip(*(steps.jax.tree.leaves(t) for t in (a, b))):
                np.testing.assert_allclose(x, y, rtol=2e-4, atol=1e-7)
        np.testing.assert_allclose(own["after"][k]["rows"],
                                   default["after"][k]["rows"],
                                   rtol=2e-4, atol=1e-7)
    ok, table, _ = _judged(own, default, cfg, mix)
    assert ok, table
    assert max(table[k]["value"] for k in ("loss_gap_1", "grad_gap",
                                           "change_gap")) < 1e-4, table


def test_added_files_alone_make_a_waiting_cell_that_rehearses():
    """Traffic, set-up, the program's passes, the probe, the reference
    through ``example_losses`` and the comparison: a whole run."""
    from benchmark import run
    code, result = run.run(argparse.Namespace(
        workload=POOLED, seed=2 ** 31 + 707, seconds=0.2, trace=0,
        rehearse=True, keep_trace=None, waiting=WAITING))
    assert code == 0 and result["correct"], result["compared"]
    assert result["failed"] == 0
    # 5 steps of 128 a pass, the fixture's own rehearsal: two warm-up
    # passes and at least one measured
    assert result["attempted"] % (5 * 128) == 0 and result["attempted"] > 0


def test_a_slots_further_keys_reach_the_programs_slot():
    import numpy as np
    from benchmark import sut
    cfg = {"trainer": {"global_batch_size": 8}, "slots": [
        {"name": "label", "kind": "float", "max_len": 1},
        {"name": "price", "kind": "float", "max_len": 1,
         "args": {"is_dense": True}},
        {"name": "tokens", "kind": "sparse", "max_len": 1,
         "args": {"is_used": True}}]}
    schema = sut.build_schema(cfg, np.array([32]))
    assert [s.is_dense for s in schema.slots] == [False, True, False]
    assert schema.slots[2].max_len == 32         # the mix's hotness
    cfg["slots"][2]["args"] = {"no_such_attribute": 1}
    with pytest.raises(TypeError):
        sut.build_schema(cfg, np.array([32]))


# ---- the window's rule, and which seed makes what ------------------------

@pytest.mark.parametrize("elapsed,seconds,started,cap,another", [
    (0.0, 50.0, 0, None, True),      # the first pass always starts
    (49.9, 50.0, 4, None, True),     # no cap: time alone, as before
    (50.0, 50.0, 2, None, False),
    (39.0, 50.0, 2, 3, True),        # time left and the cap not reached
    (39.0, 50.0, 3, 3, False),       # time left, the cap reached
    (50.8, 50.0, 2, 3, False),       # the cap not reached, no time left
    (0.0, 1.0, 0, 3, True),          # --seconds shorter than one pass:
    (17.0, 1.0, 1, 3, False),        # one pass, whatever the cap
])
def test_a_pass_starts_while_the_window_has_time_and_the_cap_has_room(
        elapsed, seconds, started, cap, another):
    from benchmark import run
    assert run.window_has_room(elapsed, seconds, started, cap) is another


class _Stop(Exception):
    pass


def _handed_to_the_program(monkeypatch, cell, seeds):
    """What ``run.py`` makes from the seeds and hands over, a run a seed,
    up to the moment the program would be built: the pass files' ids, the
    seed and the dense weights the program gets, the rows the reference
    gives new keys."""
    from benchmark import datagen, run, sut
    from benchmark.reference import steps
    runs = []
    make = datagen.make_passes

    def passes(*a):
        made = make(*a)
        runs.append({"ids": [p.ids.copy() for p in made]})
        return made

    def system(cfg, hot, seed, dense_params=None, **_kw):
        runs[-1].update(cfg=cfg, seed=seed, params0=dense_params)
        raise _Stop

    monkeypatch.setattr(datagen, "make_passes", passes)
    monkeypatch.setattr(sut, "System", system)
    for seed in seeds:
        with pytest.raises(_Stop):
            run.run(argparse.Namespace(
                workload=cell, seed=seed, seconds=0.2, trace=0,
                rehearse=True, keep_trace=None, waiting=None))
    keys = (np.uint64(1) << np.uint64(27)) + np.arange(1, 65, dtype=np.uint64)
    for seen in runs:
        seen["rows"] = steps.init_rows(keys, seen["cfg"]["embedding"],
                                       seen["seed"])
    return runs


@pytest.mark.parametrize("cell,own_weights", [
    ("smallthinker_21b_ep4.seq8k", True),
    ("nemotron3_nano_ep16.seq4k", True),
    ("dlrm_mlperf.onehot", False)])
def test_the_run_seeds_the_traffic_and_the_configuration_the_weights(
        monkeypatch, cell, own_weights):
    """Under two ``--seed``s a configuration that states ``weights_seed``
    starts from the same dense weights and rows over different pass
    files; one that states none follows ``--seed`` in both."""
    import jax
    a, b = _handed_to_the_program(monkeypatch, cell,
                                  (2 ** 31 + 808, 2 ** 31 + 809))
    assert ("weights_seed" in a["cfg"]) is own_weights
    assert not all(np.array_equal(x, y) for x, y in zip(a["ids"], b["ids"]))
    same = (a["seed"] == b["seed"]
            and all(np.array_equal(x, y) for x, y in zip(
                jax.tree.leaves(a["params0"]), jax.tree.leaves(b["params0"]))))
    assert same is own_weights
    assert np.array_equal(a["rows"], b["rows"]) is own_weights
    if own_weights:
        assert a["seed"] == a["cfg"]["weights_seed"]
    else:
        assert (a["seed"], b["seed"]) == (2 ** 31 + 808, 2 ** 31 + 809)


# ---- (b) ordered tokens and a loss of the configuration's own -----------

@pytest.fixture(scope="module")
def ordered():
    from benchmark.reference import steps
    seed = 2 ** 31 + 808
    cfg, mix, hot, batches, params0 = _followed(ORDERED, seed, rehearse=False)
    assert cfg["reference_block_examples"] == 16 and hot.tolist() == [32]
    ref = steps.follow(cfg, params0, batches, hot, seed)
    return seed, cfg, mix, hot, batches, params0, ref


def test_blocks_of_examples_give_the_unblocked_result(ordered):
    from benchmark.reference import steps
    seed, cfg, mix, hot, batches, params0, ref = ordered
    whole_cfg = {k: v for k, v in cfg.items()
                 if k != "reference_block_examples"}
    whole = steps.follow(whole_cfg, params0, batches, hot, seed)
    np.testing.assert_allclose(ref["losses"], whole["losses"], rtol=1e-6)
    for k in whole["after"]:
        for part in ("params", "m"):
            for name, x in ref["after"][k][part].items():
                np.testing.assert_allclose(
                    x, whole["after"][k][part][name], rtol=0, atol=1e-6)
        np.testing.assert_allclose(ref["after"][k]["rows"],
                                   whole["after"][k]["rows"],
                                   rtol=0, atol=1e-6)
    # the start the caller keeps is not the buffer a blocked step reuses
    for name, x in ref["params0"].items():
        np.testing.assert_array_equal(x, np.asarray(params0[name]))
        assert np.any(ref["after"][1]["params"][name] != x)
    with pytest.raises(ValueError, match="does not divide"):
        steps.follow({**cfg, "reference_block_examples": 24}, params0,
                     batches, hot, seed)


def test_the_loss_reads_its_tokens_in_order(ordered):
    from benchmark.reference import steps
    seed, cfg, mix, hot, batches, params0, ref = ordered
    swapped = copy.deepcopy(batches[:1])
    swapped[0]["ids"][:, [3, 17]] = swapped[0]["ids"][:, [17, 3]]
    assert np.array_equal(np.sort(swapped[0]["ids"], axis=1),
                          np.sort(batches[0]["ids"], axis=1))
    # the table's row count follows the tokens, so one batch is a new
    # shape: follow the one unswapped batch too
    first = steps.follow(cfg, params0, batches[:1], hot, seed)["losses"][0]
    other = steps.follow(cfg, params0, swapped, hot, seed)["losses"][0]
    assert first == pytest.approx(ref["losses"][0], rel=1e-6)
    assert abs(other - first) / first > 1e-3


def test_local_index_inverts_the_key_format(ordered):
    from benchmark import datagen
    ids = ordered[4][0]["ids"]
    local = datagen.local_index(ids)
    assert local.min() >= 0 and local.max() < 512
    assert np.array_equal(((0 + 1) << datagen.SLOT_SHIFT) | (local + 1), ids)
    assert datagen.local_index(np.array([0, (3 << 27) | 7])).tolist() == [0, 6]


@pytest.mark.parametrize("how", [{"dtype": "bfloat16"},
                                 {"fault": "half_batch"},
                                 {"fault": "state_unchanged"}],
                         ids=["control_bfloat16", "half_batch",
                              "state_unchanged"])
def test_control_and_faults_are_not_correct_under_its_own_limits(ordered, how):
    import jax.numpy as jnp
    from benchmark.reference import steps
    seed, cfg, mix, hot, batches, params0, ref = ordered
    assert _judged(ref, ref, cfg, mix)[0]
    if "dtype" in how:
        how = {"dtype": jnp.bfloat16}
    got = steps.follow(cfg, params0, batches, hot, seed, **how)
    ok, table, _ = _judged(got, ref, cfg, mix)
    assert not ok, table
