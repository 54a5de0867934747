"""The SmallThinker tower on the normal path, at a small size on the CPU:
the program through ``Trainer.train_pass`` against the plain reference
(``benchmark/reference/smallthinker.py``) on seeded random weights; the
attention kernel's two kinds, blocked against unblocked; the order of the
tokens; what the model declares (its loss, no prediction, its routing
statistics) and what a sequence slot does to the pull."""

import os
import shutil
import sys
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddlebox_tpu import monitor                         # noqa: E402
from paddlebox_tpu.config import flags                    # noqa: E402
from paddlebox_tpu.data.schema import (DataFeedSchema, Slot,  # noqa: E402
                                       SlotType)
from paddlebox_tpu.models import MODEL_REGISTRY, base     # noqa: E402
from paddlebox_tpu.models.dlrm import DLRMModel           # noqa: E402
from paddlebox_tpu.ops import flash_attention as fa       # noqa: E402

from token_tower_common import tower                      # noqa: E402

CELL = "smallthinker_21b_ep4.seq8k"


def _cell():
    from benchmark import run
    _, _, cfg, mix = run.load_cell(CELL)
    return run.rehearsal_sizes(cfg, mix)


@pytest.fixture(scope="module")
def followed():
    """Three steps of the first pass, program and reference, as run.py
    follows them (rehearsal sizes: T 32, 512 ids, 8 experts with 2 held)."""
    from benchmark import correct, datagen, sut
    from benchmark.reference import steps
    cfg, mix = _cell()
    seed, n = 28001, 3
    batch = cfg["trainer"]["global_batch_size"]
    hot = datagen.slot_hotness(mix, 1)
    passes = datagen.make_passes(mix, 1, 0, batch, seed)
    tmp = tempfile.mkdtemp(prefix="pbtpu_st_")
    try:
        files = datagen.write_pass(tmp, "A", passes[0], 2)
        batches = passes[0].batches(batch, n)
        params0 = steps.initial_params(cfg, seed)
        system = sut.System(cfg, hot, seed, dense_params=params0)
        keys = np.unique(np.concatenate(
            [b["ids"][b["mask"]] for b in batches]))
        probe = sut.StepProbe(keys, (1, n))
        probe.attach(system.trainer, system.box)
        stats0 = monitor.STATS.snapshot()
        rec = system.run_pass(files, keep_batches=n)
        stats1 = monitor.STATS.snapshot()
        got = {"losses": rec["losses"][:n], "after": probe.after}
        ref = steps.follow(cfg, params0, batches, hot, seed)
        numbers, notes = correct.compare(got, ref, cfg["embedding"]["dim"])
        numbers["ingest_mismatch"] = correct.ingest_mismatch(
            rec["first_batches"], batches, [rec["examples"]],
            [passes[0].num])
        return {"cfg": cfg, "numbers": numbers, "notes": notes, "rec": rec,
                "engines": system.engines(), "batches": batches,
                "params0": params0, "trainer": system.trainer,
                "stats": {k: stats1.get(k, 0) - stats0.get(k, 0)
                          for k in stats1}}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def test_program_follows_the_reference_through_train_pass(followed):
    n = followed["numbers"]
    assert n["ingest_mismatch"] == 0          # order kept, parser to packer
    assert n["counter_mismatch"] == 0         # the rows' show and clk
    assert n["loss_gap_1"] < 1e-5 and n["loss_gap_3"] < 1e-4
    assert n["grad_gap"] < 1e-4               # first gradient, every leaf
    assert n["change_gap"] < 1e-3             # three steps' change
    # w is not read by the tower: its gradient is zero and it is left out
    assert "table.w" in followed["notes"]["leaves_left_out_of_change"]


def test_sequence_slot_is_never_pooled_and_state_keeps_its_tree(followed):
    tr = followed["trainer"]
    assert followed["engines"]["pull_engine"] == "gather_seqpool"
    assert tr.schema.has_sequence and not tr._feeds_auc
    # a plane table: 64 is no lane tile, the rehearsal's is one array;
    # the published width is planes
    from paddlebox_tpu.embedding import EmbeddingConfig, working_set
    assert working_set.plane_layout(EmbeddingConfig(dim=2560))
    old = flags.fused_gather_pool
    flags.fused_gather_pool = "on"
    try:
        with pytest.raises(ValueError, match="sequence"):
            tr._select_pull_engine()
    finally:
        flags.fused_gather_pool = old
    with pytest.raises(NotImplementedError, match="no prediction"):
        tr.eval_pass(None)


def test_routing_statistics_reach_the_flight_record(followed):
    cfg, rec = followed["cfg"], followed["rec"]
    a = cfg["model_args"]
    steps_run = rec["steps"]
    tokens = cfg["trainer"]["global_batch_size"] * a["seq_len"]
    want = steps_run * tokens * a["experts_per_token"] * len(
        a["layer_kinds"])
    st = followed["stats"]
    assert st["moe.assignments"] == want
    assert 0 < st["moe.held_assignments"] < want
    # the ladder's counters: the sorted copies held every held assignment,
    # in whole row tiles, and never more than the whole chunk's rows
    assert st["moe.held_assignments"] <= st["moe.route_rows"] <= want
    assert 0 <= st["moe.whole_chunk_routes"] <= steps_run * len(
        a["layer_kinds"])
    assert rec["timers"]["extras"] > 0
    from paddlebox_tpu.monitor import names
    assert set(MODEL_REGISTRY["smallthinker"].stat_names) <= set(
        names.MODEL_STAT_NAMES)
    assert names.is_registered("stage/extras")


def _model_and_reference(seed=0):
    return tower(CELL, seed)


def test_model_loss_equals_reference_and_order_matters():
    cfg, ref, model, params, pulled, ids = _model_and_reference()
    mask = jnp.ones(ids.shape, bool)
    labels = jnp.zeros((ids.shape[0],))

    def mine(p, x):
        return model.loss(p, x, mask, None, labels, ids)[0]

    def theirs(p, x):
        return jnp.mean(ref.example_losses(p, x, mask, None, labels, ids,
                                           cfg))

    with jax.default_matmul_precision("highest"):
        a, ga = jax.value_and_grad(mine, argnums=(0, 1))(params, pulled)
        b, gb = jax.value_and_grad(theirs, argnums=(0, 1))(params, pulled)
        np.testing.assert_allclose(a, b, rtol=1e-6)
        for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
            np.testing.assert_allclose(x, y, atol=2e-5)
        # w, show, clk are not read: no gradient reaches them
        assert float(jnp.abs(ga[1][..., :3]).max()) == 0.0
        # two positions swapped: another text, another loss
        perm = np.arange(ids.shape[1])
        perm[[3, 11]] = perm[[11, 3]]
        swapped = model.loss(params, pulled[:, perm], mask, None, labels,
                             ids[:, perm])[0]
    assert abs(float(swapped) - float(a)) > 1e-4
    loss, preds, stats = model.loss(params, pulled, mask, None, labels, ids)
    assert preds is None and stats.shape == (len(model.stat_names),)


@pytest.mark.parametrize("D,Dv", [(16, 16), (64, 64), (24, 16)])
@pytest.mark.parametrize("window", [None, 5, 12])
def test_blocked_kernel_equals_unblocked_and_the_mask_says_where(window, D,
                                                                 Dv):
    """Grouped heads (4 over 2), full and windowed, forward and backward
    (``dq``, ``dk``, ``dv``); a head of 64 channels is half a lane tile on
    the chip (its blocks take the whole head size), one of 16 stands for
    the whole tiles, and 24 against values of 16 for multi-head latent
    attention's 192 against 128."""
    B, H, KV, T = 2, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, H, T, D))
    k = jax.random.normal(ks[1], (B, KV, T, D))
    v = jax.random.normal(ks[2], (B, KV, T, Dv))
    with jax.default_matmul_precision("highest"):
        blocked = fa.attention(q, k, v, window=window, block=8)
        whole = fa.attention_reference(q, k, v, window=window)
        full = fa.attention_reference(q, k, v)
        f = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                argnums=(0, 1, 2))(q, k, v)
        g_blocked = f(lambda *a: fa.attention(*a, window=window, block=8))
        g_whole = f(lambda *a: fa.attention_reference(*a, window=window))
    assert blocked.shape == (B, H, T, Dv)
    np.testing.assert_allclose(blocked, whole, atol=2e-6)
    for x, y, like in zip(g_blocked, g_whole, (q, k, v)):
        assert x.shape == like.shape
        np.testing.assert_allclose(x, y, atol=5e-6)
    # window and full attention agree on the positions whose window still
    # reaches the first token, and differ on every later one
    differs = np.asarray(jnp.abs(whole - full).max(axis=(0, 1, 3)) > 1e-6)
    if window is None:
        assert not differs.any()
    else:
        np.testing.assert_array_equal(differs, np.arange(T) >= window)


def test_skipped_blocks_are_the_masked_ones():
    """The block ranges the kernels visit hold every unmasked entry and no
    block without one."""
    bq = bk = 8
    T = 64
    nq = T // bq
    for window in (None, 5, 16, 20):
        t, c = np.arange(T)[:, None], np.arange(T)[None, :]
        mask = (c <= t) & ((c > t - window) if window else True)
        tiles = mask.reshape(nq, bq, nq, bk).any(axis=(1, 3))
        for i in range(nq):
            lo, hi = (int(x) for x in fa._kv_range(i, bq, bk, window))
            np.testing.assert_array_equal(
                tiles[i], (np.arange(nq) >= lo) & (np.arange(nq) <= hi))
        for j in range(nq):
            lo, hi = (int(x) for x in fa._q_range(j, bq, bk, nq, window))
            np.testing.assert_array_equal(
                tiles[:, j], (np.arange(nq) >= lo) & (np.arange(nq) <= hi))


def test_default_declaration_is_the_sigmoid_cross_entropy_bit_for_bit():
    import optax
    model = DLRMModel(3, 8, 2, (8,), (8,))
    schema = DataFeedSchema.ctr(3, 2, batch_size=4)
    from paddlebox_tpu.data.slot_record import SparseLayout
    lay = SparseLayout.from_schema(schema)
    params = model.init(jax.random.PRNGKey(0))
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    pulled = jax.random.normal(ks[0], (4, 3, 11))
    dense = jax.random.normal(ks[1], (4, 2))
    labels = (jax.random.uniform(ks[2], (4,)) > 0.5).astype(jnp.float32)
    mask = jnp.ones((4, 3), bool)
    assert base.predicts(model) and base.stat_names(model) == ()
    loss, (preds, stats) = base.declared_loss(
        model, lay.segment_ids, lay.num_slots)(params, pulled, mask, dense,
                                               labels)
    logits = model.apply(params, pulled, mask, dense, lay.segment_ids,
                         lay.num_slots)
    assert stats == ()
    np.testing.assert_array_equal(
        loss, jnp.mean(optax.sigmoid_binary_cross_entropy(logits, labels)))
    np.testing.assert_array_equal(preds, jax.nn.sigmoid(logits))


def test_a_sequence_slot_must_be_sparse_and_unknown_stats_are_refused():
    with pytest.raises(ValueError, match="sequence"):
        Slot("x", SlotType.FLOAT, sequence=True)
    assert DataFeedSchema([Slot("label", SlotType.FLOAT),
                           Slot("t", max_len=4, sequence=True)]).has_sequence

    class Odd:
        name = "odd"
        stat_names = ("moe.not_a_name",)
    with pytest.raises(ValueError, match="MODEL_STAT_NAMES"):
        base.stat_names(Odd())
