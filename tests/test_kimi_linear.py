"""The Kimi Linear tower (KDA mixers, a NoPE latent-attention layer, a
leading dense layer, held and shared experts) on the normal path, at the
cell's rehearsal sizes on the CPU: the program's loss and gradients
against the plain reference (``benchmark/reference/kimi_linear.py``) on
seeded random weights, outside a trainer so the KDA and attention kernels
run in the Pallas interpreter; its chunk-decay gauge against the
reference's; an expert layer cut over all eight chips of a small
deployment adding up to the uncut reference layer; through ``Trainer.train_pass`` for two passes with the
reference followed step by step; and what the model declares."""

import importlib
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
from paddlebox_tpu.models.kimi_linear import KimiLinearModel  # noqa: E402
from paddlebox_tpu.models.nn import rms_norm              # noqa: E402
from paddlebox_tpu.monitor import names                   # noqa: E402

from token_tower_common import (follow_two_passes,       # noqa: E402
                                rehearsal_cell, tower)

CELL = "kimi_linear_48b_a3b_ep32.seq16k"
# (num_layers, dense_layers, model_args over the cell's rehearsal ones):
# the whole cut — KDA (dense), KDA, KDA, latent attention, KDA
CASES = {"tower": (5, 1, {})}


@pytest.mark.parametrize("case", list(CASES))
def test_model_loss_and_gradients_equal_the_reference(case):
    n, dense, over = CASES[case]
    cfg, ref, model, params, pulled, ids = tower(
        CELL, 0, num_layers=n, dense_layers=dense, **over)
    assert model.kinds == ("kda", "kda", "kda", "mla", "kda")[:n]
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
        a_, ga = jax.jit(jax.value_and_grad(mine, argnums=(0, 1)))(
            params, pulled)
        b_, gb = jax.jit(jax.value_and_grad(theirs, argnums=(0, 1)))(
            params, pulled)
    # float32 throughout, sums in another order: the delta rule in chunks
    # against a position at a time, blocked attention against whole rows,
    # sorted grouped products against a masked scan over experts
    np.testing.assert_allclose(a_, b_, rtol=2e-6)
    flat = lambda g: jax.tree_util.tree_flatten_with_path(g)[0]
    for (path, x), (_, y) in zip(flat(ga), flat(gb)):
        np.testing.assert_allclose(
            x, y, atol=3e-5 * max(float(jnp.abs(y).max()), 1.0),
            err_msg=jax.tree_util.keystr(path))
    # dense: every leaf gets a gradient but the correction bias; rows: w,
    # show and clk are not read, the embedding is
    for path, g in flat(ga[0]):
        name = jax.tree_util.keystr(path)
        assert (float(jnp.abs(g).max()) == 0.0) \
            == ("e_score_correction_bias" in name), name
    assert float(jnp.abs(ga[1][..., :3]).max()) == 0.0
    assert float(jnp.abs(ga[1][..., 3:]).max()) > 0.0


def test_the_chunk_decay_gauge_and_the_declaration():
    cfg, ref, model, params, pulled, ids = tower(CELL, 1)
    a = cfg["model_args"]
    mask = jnp.ones(ids.shape, bool)
    labels = jnp.zeros((ids.shape[0],))
    loss_of = jax.jit(lambda p, x, ids: model.loss(p, x, mask, None,
                                                   labels, ids))
    loss, preds, stats = loss_of(params, pulled, ids)
    assert preds is None and stats.shape == (len(model.stat_names),)
    assert not base.predicts(model)
    assert model.stat_names == names.MODEL_STAT_NAMES[:5] + (
        "kda.chunk_decay_log_min", "kda.pair_subchunks")
    assert set(model.stat_names) <= set(names.MODEL_STAT_NAMES)
    got = dict(zip(model.stat_names, np.asarray(stats)))
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(lambda p, x: ref.chunk_decay_log_min(
            p, x, cfg))(params, pulled))
    # the least sum over a chunk of 16 positions, four KDA layers, two
    # sequences: some channel's decay passes e^-10 inside a chunk
    assert want < -10.0
    np.testing.assert_allclose(got["kda.chunk_decay_log_min"], want,
                               rtol=1e-5)
    # no fall near LIMIT at the initial weights: no pair-by-pair visit
    assert got["kda.pair_subchunks"] == 0
    # four expert layers after the dense one
    assert got["moe.assignments"] == ids.size * a["experts_per_token"] * 4
    assert 0 < got["moe.held_assignments"] <= got["moe.route_rows"] \
        <= got["moe.assignments"]
    # the order of the tokens matters
    perm = np.arange(ids.shape[1])
    perm[[3, 11]] = perm[[11, 3]]
    swapped = loss_of(params, pulled[:, perm], ids[:, perm])[0]
    assert abs(float(swapped) - float(loss)) > 1e-4
    with pytest.raises(ValueError, match="exactly one"):
        MODEL_REGISTRY["kimi_linear"](**{**a, "kda_layers": (1, 2, 3, 4)})
    with pytest.raises(ValueError, match="exactly one"):
        MODEL_REGISTRY["kimi_linear"](**{**a, "full_attn_layers": ()})


def test_the_pair_subchunk_counter_counts_what_numpy_counts():
    """Every KDA layer's decay raised 400-fold (``A_log`` + log 400), so
    channels fall by more than ``LIMIT`` across half a sub-chunk: the
    step's ``kda.pair_subchunks`` is the sum over sequences and KDA layers
    of what numpy counts from the reference's own gates, at the head block
    the kernels' rule picks."""
    from test_kda import _numpy_pairs
    from paddlebox_tpu.ops import kda as kd
    cfg, ref, model, params, pulled, ids = tower(CELL, 1)
    a = cfg["model_args"]
    params = {**params, "layers": [
        {**p, "A_log": p["A_log"] + np.log(400.0)} if "A_log" in p else p
        for p in params["layers"]]}
    mask = jnp.ones(ids.shape, bool)
    labels = jnp.zeros((ids.shape[0],))
    stats = jax.jit(lambda p, x, ids: model.loss(p, x, mask, None, labels,
                                                 ids)[2])(params, pulled, ids)
    got = dict(zip(model.stat_names, np.asarray(stats)))
    H, K = a["kda_num_heads"], a["kda_head_dim"]
    C = min(a["kda_chunk"], a["seq_len"])
    c = min(kd.SUB, C)
    hb = kd.kda_head_block(H, C, K, K)
    want = 0
    with jax.default_matmul_precision("highest"):
        for e in pulled[..., 3:]:
            h = e
            for p, kind, dense in zip(params["layers"], model.kinds,
                                      [i < model.dense_layers for i in
                                       range(len(model.kinds))]):
                if kind == "kda":
                    log_a, _ = ref._kda_gates(
                        p, rms_norm(h, p["attn_norm"], model.eps), a)
                    want += _numpy_pairs(
                        jnp.transpose(log_a, (1, 0, 2))[None], C, c, hb)
                h = ref._layer(p, h, kind, dense, a)
    assert want > 0
    assert got["kda.pair_subchunks"] == want


def test_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """One KDA layer with experts cut over all the chips of a small
    deployment (8 chips of 4 of 32 routed experts each, ``first_expert``
    0, 4, ..., 28; 8 a token): SwiGLU bodies, sigmoid scores renormalised
    by their sum + 1e-20 and scaled by 2.446, a correction bias that moves
    choices. The mixer and the shared expert, which every chip computes
    alike, counted once, the held parts add up to what the plain reference
    gives for the whole layer with every expert held."""
    ref = importlib.import_module("benchmark.reference.kimi_linear")
    cfg, _ = rehearsal_cell(CELL)
    args = {**cfg["model_args"], "num_layers": 1, "dense_layers": 0,
            "router_experts": 32, "experts_per_token": 8, "experts_held": 32,
            "seq_len": 16}
    layer = ref.init_params(jax.random.PRNGKey(12), {"model_args": args}
                            )["layers"][0]
    layer["e_score_correction_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(13), (32,))
    h = jax.random.normal(jax.random.PRNGKey(14), (2, 16, 64)) * 0.5
    whole = KimiLinearModel(**args)

    @jax.jit
    def parts_of(layer, h):
        """(what every chip computes alike — the residual, the mixer, the
        shared expert, the held experts' part set to 0 —, each chip's
        held part, each chip's load)."""
        none = {**layer, "w_down": jnp.zeros_like(layer["w_down"])}
        rest_alone = whole._mixed_layer(none, h, "kda", False)[0]
        mixed = h + whole._kda_mixer(
            layer, rms_norm(h, layer["attn_norm"], whole.eps))[0]
        m = rms_norm(mixed, layer["ffn_norm"], whole.eps).reshape(32, 64)
        parts, loads = [], []
        for first in range(0, 32, 4):
            share = KimiLinearModel(**{**args, "experts_held": 4,
                                       "first_expert": first})
            mine = {**layer, **{k: layer[k][first:first + 4]
                                for k in ("w_gate", "w_up", "w_down")}}
            y, (load, _) = share._experts(mine, m)
            y0, _ = share._experts(
                {**mine, "w_down": jnp.zeros_like(mine["w_down"])}, m)
            parts.append((y - y0).reshape(h.shape))
            loads.append(load)
        return rest_alone, mixed, parts, loads

    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([ref._layer(layer, h[b], "kda", False, args)
                           for b in range(2)])
        rest_alone, mixed, parts, loads = parts_of(layer, h)
    np.testing.assert_allclose(rest_alone + sum(parts), uncut, atol=5e-5)
    assert float(jnp.abs(sum(parts)).max()) > 1e-2
    assert float(jnp.abs(rest_alone - mixed).max()) > 1e-2
    # nothing dropped: every (token, choice) fell on exactly one share
    assert int(sum(jnp.sum(l) for l in loads)) == 2 * 16 * 8


@pytest.fixture(scope="module")
def followed():
    """Two passes (files A, then B) through ``Trainer.train_pass``; the
    first pass's three first steps followed by the reference, as run.py
    follows them (rehearsal sizes: T 32 in chunks of 16, 512 ids, 16
    experts with 4 held, KDA heads of 16, latent attention of 24 / 16)."""
    return follow_two_passes(CELL, 44001)


def test_program_follows_the_reference_through_train_pass(followed):
    n = followed["numbers"]
    assert n["ingest_mismatch"] == 0          # order kept, parser to packer
    assert n["counter_mismatch"] == 0         # the rows' show and clk
    # float32 on both sides (the trainer's KDA and attention on a CPU mesh
    # are the plain twins): round-off of sums in another order
    assert n["loss_gap_1"] < 1e-5 and n["loss_gap_3"] < 1e-4
    assert n["grad_gap"] < 1e-4               # first gradient, every leaf
    assert n["change_gap"] < 1e-3             # three steps' change
    left_out = followed["notes"]["leaves_left_out_of_change"]
    assert "table.w" in left_out              # w is not read by the tower
    assert sum("e_score_correction_bias" in leaf for leaf in left_out) == 4
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
    st = followed["stats"]
    assert st["moe.assignments"] == (steps_run * tokens
                                     * a["experts_per_token"] * 4)
    assert 0 < st["moe.held_assignments"] < st["moe.assignments"]
    # the gauge is a pass's least step: below zero, and no counter
    assert followed["snapshot"]["kda.chunk_decay_log_min"] < 0
    # a counter: no sub-chunk of the initial weights' steps fell far
    assert st["kda.pair_subchunks"] == 0
