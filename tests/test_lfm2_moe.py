"""The LFM2-MoE tower on the normal path, at a small size on the CPU: the
program's loss and gradients against the plain reference
(``benchmark/reference/lfm2_moe.py``) on seeded random weights, one case a
layer kind and one for the five-layer tower (outside a trainer, so the
short-convolution and attention kernels run in the Pallas interpreter
against the reference's shifted products and whole rows); the order of the
tokens; through ``Trainer.train_pass`` for two passes with the reference
followed step by step; and what the model declares (its loss, no
prediction, the five routing statistics)."""

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

CELL = "lfm2_24b_a2b_ep8.seq8k"
TOWER = ("conv", "full_attention", "conv", "conv", "conv")
# (layer_types, dense_layers): one case a layer kind, then the cell's tower
CASES = {"conv_dense": (("conv",), 1), "conv_experts": (("conv",), 0),
         "attention_experts": (("full_attention",), 0), "tower": (TOWER, 1)}


def _cell():
    return rehearsal_cell(CELL)


def _model_and_reference(layer_types, dense_layers, seed=0):
    return tower(CELL, seed, layer_types=list(layer_types),
                 dense_layers=dense_layers)


@pytest.mark.parametrize("case", list(CASES))
def test_model_loss_and_gradients_equal_the_reference(case):
    cfg, ref, model, params, pulled, ids = _model_and_reference(*CASES[case])
    mask = jnp.ones(ids.shape, bool)
    labels = jnp.zeros((ids.shape[0],))
    # the program's own initial state has the reference's names and shapes
    mine0 = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda x: x.shape, mine0) \
        == jax.tree.map(lambda x: x.shape, params)

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
    # float32 throughout, sums in another order: blocked attention against
    # whole rows, sorted grouped products against a masked scan over
    # experts, the MLP in two chunks of tokens against one
    np.testing.assert_allclose(a, b, rtol=2e-6)
    flat = lambda g: jax.tree_util.tree_flatten_with_path(g)[0]
    for (path, x), (_, y) in zip(flat(ga), flat(gb)):
        np.testing.assert_allclose(
            x, y, atol=3e-5 * max(float(jnp.abs(y).max()), 1.0),
            err_msg=jax.tree_util.keystr(path))
    # dense: every leaf gets a gradient but the expert bias; rows: w, show
    # and clk are not read, the embedding is
    for path, g in flat(ga[0]):
        name = jax.tree_util.keystr(path)
        assert (float(jnp.abs(g).max()) == 0.0) == ("expert_bias" in name), \
            name
    assert float(jnp.abs(ga[1][..., :3]).max()) == 0.0
    assert float(jnp.abs(ga[1][..., 3:]).max()) > 0.0


@pytest.mark.parametrize("mixer", ["conv", "full_attention"])
def test_order_matters_and_the_declaration(mixer):
    cfg, ref, model, params, pulled, ids = _model_and_reference(
        (mixer, "conv"), 1, seed=1)
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
    assert model.stat_names == names.MODEL_STAT_NAMES[:5]
    got = dict(zip(model.stat_names, np.asarray(stats)))
    a = cfg["model_args"]
    # one expert layer (the second; the first is dense)
    assert got["moe.assignments"] == ids.size * a["experts_per_token"]
    assert 0 < got["moe.held_assignments"] <= got["moe.route_rows"] \
        <= got["moe.assignments"]
    assert got["moe.expert_load_max"] <= got["moe.held_assignments"]
    with pytest.raises(ValueError, match="kinds"):
        MODEL_REGISTRY["lfm2_moe"](**{**a, "layer_types": ("conv", "scan")})
    with pytest.raises(ValueError, match="dense layers"):
        MODEL_REGISTRY["lfm2_moe"](**{**a, "dense_layers": 9})


def test_a_tower_of_dense_layers_alone_counts_no_assignment():
    _, _, model, params, pulled, ids = _model_and_reference(("conv",), 1)
    stats = model.loss(params, pulled, jnp.ones(ids.shape, bool), None,
                       jnp.zeros((ids.shape[0],)), ids)[2]
    np.testing.assert_array_equal(stats, np.zeros(5))


@pytest.fixture(scope="module")
def followed():
    """Two passes (files A, then B) through ``Trainer.train_pass``; the
    first pass's three first steps followed by the reference, as run.py
    follows them (rehearsal sizes: T 32, 512 ids, 16 experts with 2 held,
    4 / 2 heads of 16, the MLPs' tokens in two chunks)."""
    return follow_two_passes(CELL, 36001)


def test_program_follows_the_reference_through_train_pass(followed):
    n = followed["numbers"]
    assert n["ingest_mismatch"] == 0          # order kept, parser to packer
    assert n["counter_mismatch"] == 0         # the rows' show and clk
    # float32 on both sides (the trainer's convolution and attention on a
    # CPU mesh are the plain twins): round-off of sums in another order
    assert n["loss_gap_1"] < 1e-5 and n["loss_gap_3"] < 1e-4
    assert n["grad_gap"] < 1e-4               # first gradient, every leaf
    assert n["change_gap"] < 1e-3             # three steps' change
    left_out = followed["notes"]["leaves_left_out_of_change"]
    assert "table.w" in left_out              # w is not read by the tower
    assert sum("expert_bias" in leaf for leaf in left_out) == 4
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
    n_e = len(a["layer_types"]) - a["dense_layers"]
    st = followed["stats"]
    assert st["moe.assignments"] == (steps_run * tokens
                                     * a["experts_per_token"] * n_e)
    assert 0 < st["moe.held_assignments"] < st["moe.assignments"]
    # the ladder's counters, after two passes: the sorted copies held every
    # held assignment and never more than the whole chunks' rows
    assert st["moe.held_assignments"] <= st["moe.route_rows"] \
        <= st["moe.assignments"]
    chunks = tokens // a["expert_chunk_tokens"]
    assert 0 <= st["moe.whole_chunk_routes"] <= steps_run * n_e * chunks
    # this tower reports the five routing statistics and no other
    assert not any(k.startswith("ssm.") and v for k, v in st.items())
    assert recs[1]["timers"]["extras"] > 0
