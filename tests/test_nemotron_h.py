"""The Nemotron-H tower on the normal path, at a small size on the CPU:
the program's loss and gradients against the plain reference
(``benchmark/reference/nemotron_h.py``) on seeded random weights, one case
a block kind and one for the whole MEMEM*EME tower (outside a trainer, so
the scan and attention kernels run in the Pallas interpreter against the
reference's literal recurrence); through ``Trainer.train_pass`` for two
passes; and what the model declares (its loss, no prediction, the routing
and scan statistics, one of them a ``*_min`` gauge)."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddlebox_tpu.models import MODEL_REGISTRY, base     # noqa: E402
from paddlebox_tpu.monitor import names                   # noqa: E402

from token_tower_common import (follow_two_passes,    # noqa: E402
                                rehearsal_cell, tower)

CELL = "nemotron3_nano_ep16.seq4k"


def _cell():
    return rehearsal_cell(CELL)


def _model_and_reference(pattern, seed=0):
    return tower(CELL, seed, block_pattern=pattern)


@pytest.mark.parametrize("pattern", ["M", "E", "*", "MEMEM*EME"])
def test_model_loss_and_gradients_equal_the_reference(pattern):
    cfg, ref, model, params, pulled, ids = _model_and_reference(pattern)
    mask = jnp.ones(ids.shape, bool)
    labels = jnp.zeros((ids.shape[0],))

    def mine(p, x):
        return model.loss(p, x, mask, None, labels, ids)[0]

    def theirs(p, x):
        return jnp.mean(ref.example_losses(p, x, mask, None, labels, ids,
                                           cfg))

    with jax.default_matmul_precision("highest"):
        a, ga = jax.jit(jax.value_and_grad(mine, argnums=(0, 1)))(
            params, pulled)
        b, gb = jax.jit(jax.value_and_grad(theirs, argnums=(0, 1)))(
            params, pulled)
    # float32 throughout, sums in another order: the chunked scan against
    # 32 single steps, blocked attention against whole rows, sorted grouped
    # products against a masked scan over experts
    np.testing.assert_allclose(a, b, rtol=2e-6)
    flat = lambda g: jax.tree_util.tree_flatten_with_path(g)[0]
    for (path, x), (_, y) in zip(flat(ga), flat(gb)):
        np.testing.assert_allclose(
            x, y, atol=3e-5 * max(float(jnp.abs(y).max()), 1.0),
            err_msg=jax.tree_util.keystr(path))
    # dense: every leaf gets a gradient but the correction bias; rows:
    # w, show and clk are not read, the embedding is
    for path, g in flat(ga[0]):
        name = jax.tree_util.keystr(path)
        assert (float(jnp.abs(g).max()) == 0.0) == ("b_corr" in name), name
    assert float(jnp.abs(ga[1][..., :3]).max()) == 0.0
    assert float(jnp.abs(ga[1][..., 3:]).max()) > 0.0


def test_order_matters_and_the_declaration():
    cfg, ref, model, params, pulled, ids = _model_and_reference("M*", 1)
    mask = jnp.ones(ids.shape, bool)
    labels = jnp.zeros((ids.shape[0],))
    loss, preds, stats = model.loss(params, pulled, mask, None, labels, ids)
    perm = np.arange(ids.shape[1])
    perm[[3, 11]] = perm[[11, 3]]
    swapped = model.loss(params, pulled[:, perm], mask, None, labels,
                         ids[:, perm])[0]
    assert abs(float(swapped) - float(loss)) > 1e-4
    assert preds is None and stats.shape == (len(model.stat_names),)
    assert not base.predicts(model)
    assert set(model.stat_names) <= set(names.MODEL_STAT_NAMES)
    got = dict(zip(model.stat_names, np.asarray(stats)))
    a = cfg["model_args"]
    tokens = ids.size
    assert got["ssm.tokens"] == tokens            # one 'M' block
    assert got["ssm.chunks"] == tokens // a["chunk_size"]
    assert got["ssm.decay_log_min"] < 0
    assert got["moe.assignments"] == 0 == got["moe.expert_load_max"]
    with pytest.raises(ValueError, match="kinds"):
        MODEL_REGISTRY["nemotron_h"](**{**a, "block_pattern": "MXE"})


def test_min_gauges_reduce_by_min_and_publish_the_smallest_step():
    class Scan:
        name = "scan"
        stat_names = ("ssm.tokens", "ssm.decay_log_min",
                      "moe.expert_load_max")
    per_step = np.array([[8.0, -3.0, 5.0], [8.0, -7.5, 2.0]])
    out = base.publish_stats(Scan(), per_step)
    assert out == {"ssm.tokens": 16.0, "ssm.decay_log_min": -7.5,
                   "moe.expert_load_max": 5.0}
    mesh = jax.make_mesh((2,), ("dp",), devices=jax.devices()[:2])
    reduced = jax.shard_map(
        lambda s: base.reduce_stats(Scan.stat_names, s[0], ("dp",)),
        mesh=mesh, in_specs=jax.sharding.PartitionSpec("dp"),
        out_specs=jax.sharding.PartitionSpec())(jnp.asarray(per_step))
    np.testing.assert_array_equal(reduced, [16.0, -7.5, 5.0])


@pytest.fixture(scope="module")
def followed():
    """Two passes (files A, then B) through ``Trainer.train_pass``; the
    first pass's three first steps followed by the reference, as run.py
    follows them (rehearsal sizes: T 32, 512 ids, 16 experts with 2 held,
    4 Mamba heads of 8 in 2 groups, chunks of 8)."""
    return follow_two_passes(CELL, 32001)


def test_program_follows_the_reference_through_train_pass(followed):
    n = followed["numbers"]
    assert n["ingest_mismatch"] == 0          # order kept, parser to packer
    assert n["counter_mismatch"] == 0         # the rows' show and clk
    # float32 on both sides (the trainer's scan on a CPU mesh is the
    # literal recurrence too): round-off of sums taken in another order
    assert n["loss_gap_1"] < 1e-5 and n["loss_gap_3"] < 1e-4
    assert n["grad_gap"] < 1e-4               # first gradient, every leaf
    assert n["change_gap"] < 1e-3             # three steps' change
    left_out = followed["notes"]["leaves_left_out_of_change"]
    assert "table.w" in left_out              # w is not read by the tower
    assert sum("b_corr" in leaf for leaf in left_out) == 4
    tr = followed["trainer"]
    assert followed["engines"]["pull_engine"] == "gather_seqpool"
    assert tr.schema.has_sequence and not tr._feeds_auc


def test_two_passes_train_and_their_statistics_reach_the_flight_record(
        followed):
    cfg, recs = followed["cfg"], followed["recs"]
    a = cfg["model_args"]
    assert [r["steps"] for r in recs] == [6, 6]
    assert all(np.isfinite(r["losses"]).all() for r in recs)
    steps_run = sum(r["steps"] for r in recs)
    tokens = cfg["trainer"]["global_batch_size"] * a["seq_len"]
    n_m, n_e = (a["block_pattern"].count(k) for k in "ME")
    st = followed["stats"]
    assert st["ssm.tokens"] == steps_run * tokens * n_m
    assert st["ssm.chunks"] == steps_run * tokens // a["chunk_size"] * n_m
    assert st["moe.assignments"] == (steps_run * tokens
                                     * a["experts_per_token"] * n_e)
    assert 0 < st["moe.held_assignments"] < st["moe.assignments"]
    # the ladder's counters, after two passes: the sorted copies held every
    # held assignment and never more than the whole chunks' rows
    assert st["moe.held_assignments"] <= st["moe.route_rows"] \
        <= st["moe.assignments"]
    assert 0 <= st["moe.whole_chunk_routes"] <= steps_run * n_e
    # the gauge: a pass's most negative chunk of Delta A (dt up to 0.1, A
    # down to -16, 8 positions a chunk)
    assert -8 * 0.2 * 16 < followed["snapshot"]["ssm.decay_log_min"] < 0
    assert recs[1]["timers"]["extras"] > 0
