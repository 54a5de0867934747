"""The host plan's lanes follow the batch's distinct rows, not its tokens.

Where the dedup plan carries no kernel windows (one shard, the scatter
engines: what a plane table takes), `Trainer._host_plan` ships `uniq` and
`segend` with L lanes, L = min(n, bucket_size(the most distinct rows a
batch of this trainer has had)), grow-only. These tests hold: (a) the
plan's contract at every L; (b) that the merged operands and the table do
not depend on L to the bit; (c) growth inside a pass against a run whose
plan keeps a lane a token, and what the counters read; (d) a pass resumed
mid-way by a fresh trainer, whose L starts over.
"""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddlebox_tpu import monitor
from paddlebox_tpu.config import flags
from paddlebox_tpu.data import DataFeedSchema
from paddlebox_tpu.data.dataset import SlotDataset
from paddlebox_tpu.data.slot_record import SlotRecordBatch
from paddlebox_tpu.embedding import HostEmbeddingStore, quant, sharded
from paddlebox_tpu.embedding.working_set import bucket_size
from paddlebox_tpu.models import DLRMModel
from paddlebox_tpu.monitor import names
from paddlebox_tpu.native.key_index import dedup_plan, dedup_plan_counted
from paddlebox_tpu.parallel import make_mesh
from paddlebox_tpu.train import Trainer, TrainerConfig
from tests.test_table_planes import (_as_rows, _cfg, _host_rows, _tokens,
                                     one_array)

NUM_SLOTS, BATCH, DIM = 4, 16, 128
N_TOK = NUM_SLOTS * BATCH
# ids a slot draws from, batch by batch: the distinct rows of a batch
# climb over the rungs 16 | 24 | 40 | 64 and fall back
GROWING = (2, 2, 5, 3, 9, 2, 400, 4)


@pytest.fixture(autouse=True)
def forced_plan():
    """The chip's path, here: the forced fused engine turns the host plan
    and the premerge on off-TPU (no kernel windows at that engine)."""
    old = flags.push_engine, flags.push_overlap
    flags.push_engine = "scatter_accumulate"
    yield
    flags.push_engine, flags.push_overlap = old


def _dataset(vocabs, seed=0):
    """One batch of BATCH examples per entry of `vocabs`, each slot's ids
    drawn from that many values: a batch has at most NUM_SLOTS x vocab
    distinct rows."""
    schema = DataFeedSchema.ctr(num_sparse=NUM_SLOTS, num_float=2,
                                batch_size=BATCH, max_len=1)
    rng = np.random.default_rng(seed)
    n_ex = len(vocabs) * BATCH
    hi = np.repeat(np.asarray(vocabs), BATCH)
    offs = np.arange(n_ex + 1, dtype=np.int64)
    ds = SlotDataset(schema)
    ds.records = SlotRecordBatch(
        schema=schema, num=n_ex,
        sparse_values=[((1 + rng.integers(0, 1 << 30, n_ex) % hi
                         ).astype(np.int64)
                        | (np.int64(s + 1) << np.int64(40)))
                       for s in range(NUM_SLOTS)],
        sparse_offsets=[offs.copy() for _ in range(NUM_SLOTS)],
        float_values=[(rng.random(n_ex) < 0.3).astype(np.float32),
                      rng.normal(size=n_ex).astype(np.float32),
                      rng.normal(size=n_ex).astype(np.float32)],
        ins_id=np.zeros(n_ex, dtype=np.uint64),
        search_id=np.zeros(n_ex, dtype=np.uint64),
        rank=np.zeros(n_ex, dtype=np.int32),
        cmatch=np.zeros(n_ex, dtype=np.int32))
    return ds, schema


def _distinct_per_batch(ds):
    vals = np.stack(ds.records.sparse_values, axis=1)
    return [len(np.unique(vals[b * BATCH:(b + 1) * BATCH]))
            for b in range(len(vals) // BATCH)]


def _lanes_per_batch(distinct):
    """What the rule gives: a bucket over the running maximum."""
    return [min(N_TOK, bucket_size(m))
            for m in np.maximum.accumulate(distinct)]


def _trainer(schema, seed=3):
    store = HostEmbeddingStore(_cfg("adagrad", dim=DIM))
    model = DLRMModel(num_slots=NUM_SLOTS, emb_dim=DIM, dense_dim=2,
                      bottom_hidden=(16,), top_hidden=(16, 8),
                      use_cvm=False)
    tr = Trainer(model, store, schema, make_mesh(1),
                 TrainerConfig(global_batch_size=BATCH),
                 seed=seed)
    assert tr._use_plan
    return tr, store


def _train(vocabs, monkeypatch=None, untrimmed=False):
    ds, schema = _dataset(vocabs)
    tr, store = _trainer(schema)
    if untrimmed:       # the plan as it was: one lane a token
        monkeypatch.setattr(Trainer, "_plan_lane_count",
                            lambda self, n_uniq, n_tokens: n_tokens)
    s0 = monitor.STATS.snapshot()
    out = tr.train_pass(ds)
    tr.flush_sparse()
    s1 = monitor.STATS.snapshot()
    delta = {k: s1.get(k, 0.0) - s0.get(k, 0.0)
             for k in names.PLAN_COUNTER_NAMES}
    keys = np.sort(np.unique(ds.unique_keys()))
    params = jax.tree.map(np.asarray, tr.params)
    return out, store.peek_rows(keys), params, delta, tr, ds


def _assert_same_run(a, b):
    assert a[0]["losses"] == b[0]["losses"]
    np.testing.assert_array_equal(a[1], b[1])
    for x, y in zip(jax.tree.leaves(a[2]), jax.tree.leaves(b[2])):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# (a) the plan's contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("vocabs,seed", [
    (GROWING, 0), ((400,) * 3, 1), ((1, 1, 1), 2), ((3, 7, 2, 12, 5), 3)])
def test_plan_contract_over_batches(vocabs, seed):
    ds, schema = _dataset(vocabs, seed)
    tr, _ = _trainer(schema)
    ws = tr.feed_mgr.begin_pass(ds.unique_keys())
    want = _lanes_per_batch(_distinct_per_batch(ds))
    lanes_before = 0
    for pb, L in zip(ds.batches(BATCH), want):
        idx = ws.translate(pb.ids, pb.mask)
        order, rstart, endb, uniq, segend = tr._host_plan(ws, idx)
        rows = np.unique(idx)
        assert len(uniq) == len(segend) == L == tr._plan_lanes
        assert L >= len(rows) and L >= lanes_before     # never shrinks
        lanes_before = L
        assert len(order) == idx.size and not len(rstart) and not len(endb)
        np.testing.assert_array_equal(np.sort(order), np.arange(idx.size))
        np.testing.assert_array_equal(uniq[:len(rows)], rows)
        pads = uniq[len(rows):]
        assert (pads >= ws.padded_rows).all() and (np.diff(uniq) > 0).all()
        # segments: the sorted tokens of lane i are its row's; pads are
        # zero-width at the stream's end
        starts = np.concatenate([[0], segend[:-1]])
        assert (segend[len(rows):] == idx.size).all()
        assert (starts[len(rows):] == idx.size).all()
        flat = idx.reshape(-1)[order]
        for i in (0, len(rows) // 2, len(rows) - 1):
            assert (flat[starts[i]:segend[i]] == rows[i]).all()
            assert segend[i] > starts[i]
        # the lanes are a prefix of the full-length plan
        full = dedup_plan(idx.reshape(-1), ws.padded_rows, ws.padded_rows, 1)
        np.testing.assert_array_equal(uniq, full[1][:L])
        np.testing.assert_array_equal(segend, full[2][:L])


def test_counted_plan_hands_on_the_distinct_rows():
    rng = np.random.default_rng(5)
    idx = rng.integers(-2, 70, 300).astype(np.int32)    # some out of range
    plan, n_uniq = dedup_plan_counted(idx, 64, 64, 1)
    assert n_uniq == len(np.unique(idx[(idx >= 0) & (idx < 64)]))
    for a, b in zip(plan, dedup_plan(idx, 64, 64, 1)):
        np.testing.assert_array_equal(a, b)
    assert (plan[1][n_uniq:] >= 64).all() and (plan[1][:n_uniq] < 64).all()


# ---------------------------------------------------------------------------
# (b) the merged operands and the table do not depend on L
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("table_kind", ["planes", "one_array"])
@pytest.mark.parametrize("optimizer", ["adagrad", "adam"])
def test_bit_identity_in_lanes(table_kind, optimizer):
    cfg = _cfg(optimizer, dim=DIM)
    n_rows, n_tok = 96, 200
    host = _host_rows(cfg, n_rows, seed=1)
    idx, grads, shows, clks = _tokens(cfg, n_rows, n_tok, seed=4)
    (o, u, s, _, _), m = dedup_plan_counted(idx, n_rows, n_rows, 1)
    assert m == len(np.unique(idx)) < bucket_size(m) < n_tok
    Z = np.zeros(0, np.int32)
    results = []
    for L in (n_tok, bucket_size(m), m):
        table = (quant.device_planes(host, cfg, None)
                 if table_kind == "planes" else jnp.asarray(host))
        uniq, mg, ms, mc, kplan = sharded.plan_premerge(
            *map(jnp.asarray, (idx, grads, shows, clks)),
            tuple(map(jnp.asarray, (o, Z, Z, u[:L], s[:L]))))
        assert kplan is None and uniq.shape == (L,) and mg.shape[0] == L
        table = sharded.push(table, uniq, mg, ms, mc, cfg, premerged=True)
        results.append((_as_rows(table, cfg),
                        [np.asarray(x)[:m] for x in (mg, ms, mc)],
                        [np.asarray(x)[m:] for x in (mg, ms, mc)]))
    rows0, merged0, _ = results[0]
    assert not np.array_equal(rows0, host)
    for rows, merged, pads in results:
        np.testing.assert_array_equal(rows, rows0)
        for a, b in zip(merged, merged0):
            np.testing.assert_array_equal(a, b)
        assert all(not p.any() for p in pads)           # pads merge nothing


# ---------------------------------------------------------------------------
# (c) growth inside a pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("table_kind", ["planes", "one_array"])
def test_growth_inside_a_pass_matches_a_lane_a_token(table_kind,
                                                     monkeypatch):
    with (one_array(monkeypatch) if table_kind == "one_array"
          else contextlib.nullcontext()):
        got = _train(GROWING)
        with monkeypatch.context() as m:
            ref = _train(GROWING, m, untrimmed=True)
        tr, ds = got[4], got[5]
        assert quant.is_planes(tr.feed_mgr._current.table) is (
            table_kind == "planes")
        assert tr.engines()["push_engine"] == "scatter_accumulate"
        assert got[0]["steps"] == len(GROWING)
        _assert_same_run(got, ref)
        distinct = _distinct_per_batch(ds)
        lanes = _lanes_per_batch(distinct)
        assert len(set(lanes)) >= 4 and lanes[-1] == N_TOK == tr._plan_lanes
        assert got[3] == {
            "trainer.plan_tokens": N_TOK * len(GROWING),
            "trainer.plan_unique_tokens": sum(distinct),
            "trainer.plan_lanes": sum(lanes),
            "trainer.plan_lane_grows": len(set(lanes))}
        assert ref[3]["trainer.plan_lanes"] == N_TOK * len(GROWING)
        assert ref[3]["trainer.plan_lane_grows"] == 0
        # the rung stays: a second pass of small batches keeps the lanes
        s0 = monitor.STATS.snapshot()
        small, _ = _dataset((2, 3), seed=9)
        tr.train_pass(small)
        s1 = monitor.STATS.snapshot()
        assert tr._plan_lanes == N_TOK
        assert (s1["trainer.plan_lanes"] - s0["trainer.plan_lanes"]
                == 2 * N_TOK)
        assert (s1["trainer.plan_lane_grows"]
                == s0["trainer.plan_lane_grows"])


def test_counters_reach_the_flight_record():
    from paddlebox_tpu.fleet import BoxPS
    ds, schema = _dataset((2, 9, 3))
    tr, store = _trainer(schema)
    box = BoxPS(store)
    box.begin_pass()
    tr.train_pass(ds)
    delta = box.end_pass()["flight_record"]["stats_delta"]
    distinct = _distinct_per_batch(ds)
    lanes = _lanes_per_batch(distinct)
    assert delta["trainer.plan_tokens"] == 3 * N_TOK
    assert delta["trainer.plan_unique_tokens"] == sum(distinct)
    assert delta["trainer.plan_lanes"] == sum(lanes)
    assert delta["trainer.plan_lane_grows"] == len(set(lanes))
    assert set(names.PLAN_COUNTER_NAMES) <= set(delta)


# ---------------------------------------------------------------------------
# (d) a pass resumed mid-way: the fresh trainer's lanes start over
# ---------------------------------------------------------------------------

def test_resumed_pass_lands_on_the_uninterrupted_table(tmp_path):
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.utils.pass_ckpt import PassCheckpointer
    vocabs = (9, 400, 2, 3, 5, 2)       # the widest batches come first

    def job(seed):
        ds, schema = _dataset(vocabs)
        tr, store = _trainer(schema, seed=seed)
        return ds, tr, store, BoxPS(store)

    ds, tr, store, box = job(3)
    ck = PassCheckpointer(str(tmp_path / "ck"), keep_last_n=6, base_every=4)
    tr.enable_midpass_snapshots(ck, 2, box)
    box.begin_pass()
    tr.train_pass(ds)
    box.end_pass(checkpointer=ck, trainer=tr, dataset=ds)
    tr.flush_sparse()
    keys = np.sort(np.asarray(ds.unique_keys(), np.uint64))
    want_rows = store.get_rows(keys)
    want_params = jax.tree.map(np.asarray, tr.params)
    assert tr._plan_lanes == N_TOK and (0, 4) in ck.intact_cursors()

    ds2, tr2, store2, box2 = job(99)
    cursor = PassCheckpointer(str(tmp_path / "ck"), keep_last_n=6,
                              base_every=4).resume(tr2, box=box2, at=(0, 4))
    assert cursor["mid_steps"] == 4 and tr2._plan_lanes == 0
    box2.begin_pass()
    out = tr2.train_pass(ds2, skip_steps=cursor["mid_steps"])
    box2.end_pass()
    tr2.flush_sparse()
    assert out["steps"] == 2
    np.testing.assert_array_equal(want_rows, store2.get_rows(keys))
    for a, b in zip(jax.tree.leaves(want_params),
                    jax.tree.leaves(jax.tree.map(np.asarray, tr2.params))):
        np.testing.assert_array_equal(a, b)
    assert tr2.global_step == tr.global_step
