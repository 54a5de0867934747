"""FeedPassManager: incremental + overlapped pass-boundary transfer.

Covers the BoxPS FeedPass model (box_wrapper.h:994-1072: background
BeginFeedPass/WaitFeedPassDone; box_wrapper.h:423: EndPass moves only the
pass delta): resident-row reuse, dirty-row-only D2H, background staging,
and invalidation when the store mutates (shrink).
"""

import numpy as np
import pytest

import jax

from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
from paddlebox_tpu.embedding.feed_pass import FeedPassManager
from paddlebox_tpu.embedding.working_set import bucket_size
from paddlebox_tpu.parallel import make_mesh


def cfg_small(**kw):
    kw.setdefault("dim", 4)
    kw.setdefault("optimizer", "adagrad")
    kw.setdefault("learning_rate", 0.1)
    return EmbeddingConfig(**kw)


def _keys(lo, hi):
    return np.arange(lo, hi, dtype=np.uint64) * np.uint64(2654435761) + 1


def test_bucket_size_monotonic_bounded():
    prev = 0
    for x in [1, 3, 16, 17, 100, 1000, 12345, 1 << 20]:
        b = bucket_size(x)
        assert b >= x
        assert b <= max(16, x + (x // 4) + 4)   # ≤ ~25% waste
        assert b >= prev or x < prev
        prev = b
    # buckets collapse many sizes onto few shapes
    assert len({bucket_size(x) for x in range(1000, 1100)}) <= 2


def test_reuse_moves_only_delta_bytes():
    """VERDICT round-1 'done' bar: two consecutive passes with 90% key
    overlap must move <20% of the table's bytes across the boundary."""
    c = cfg_small()
    store = HostEmbeddingStore(c)
    mgr = FeedPassManager(store)
    base = _keys(0, 1000)
    ws1 = mgr.begin_pass(base)
    full_bytes = mgr.last_h2d_bytes
    assert full_bytes > 0
    # train pass 1: touch every key, bump w column
    idx = ws1.translate(base)
    t = np.array(ws1.table)
    t[idx, 2] += 1.0
    assert mgr.end_pass(ws1, jax.numpy.asarray(t)) == 0   # lazy: no D2H
    # pass 2: 90% overlap (drop 100 keys, add 100 new)
    nxt = np.concatenate([base[100:], _keys(5000, 5100)])
    ws2 = mgr.begin_pass(nxt)
    assert mgr.last_fresh_rows == 100
    assert mgr.last_reused_rows == 900
    # boundary traffic = fresh H2D + retiring-row D2H, both O(churn)
    moved = mgr.last_h2d_bytes + mgr.last_d2h_bytes
    table_bytes = ws2.padded_rows * c.row_width * 4
    assert moved < 0.2 * (2 * table_bytes), (moved, table_bytes)
    # the 100 retired keys' trained values reached the store
    np.testing.assert_allclose(store.get_rows(base[:100])[:, 2], 1.0)
    # reused rows carry the POST-pass-1 values (w == 1), not store inits
    idx2 = ws2.translate(base[100:200])
    np.testing.assert_allclose(np.asarray(ws2.table)[idx2, 2], 1.0)
    # a flush materializes the rest for checkpoint/serving consumers
    mgr.flush()
    np.testing.assert_allclose(store.get_rows(base[100:])[:, 2], 1.0)


def test_dirty_row_writeback_only_touched():
    c = cfg_small()
    store = HostEmbeddingStore(c)
    mgr = FeedPassManager(store)
    keys = _keys(0, 50)
    ws = mgr.begin_pass(keys)
    touched_keys = keys[:10]
    idx = ws.translate(touched_keys)
    t = np.array(ws.table)
    t[:, 2] = 9.0                        # mutate EVERY row on device
    mgr.end_pass(ws, jax.numpy.asarray(t))
    mgr.flush()
    np.testing.assert_allclose(store.get_rows(touched_keys)[:, 2], 9.0)
    # untouched rows kept their host values (delta-only EndPass)
    assert not np.any(store.get_rows(keys[10:])[:, 2] == 9.0)
    # and the flush hook fires automatically on save_delta: dirty mask
    # covers exactly the touched rows
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        f = store.save_delta(os.path.join(d, "delta"))
        z = np.load(f)
        assert set(z["keys"].tolist()) <= set(keys.tolist())


def test_background_feed_pass_overlap():
    c = cfg_small()
    store = HostEmbeddingStore(c)
    mgr = FeedPassManager(store)
    p1 = _keys(0, 400)
    ws1 = mgr.begin_pass(p1)
    ws1.translate(p1)
    # stage pass 2 while "training" pass 1
    p2 = np.unique(np.concatenate([p1[50:], _keys(9000, 9050)]))
    mgr.begin_feed_pass(p2)
    mgr.wait_feed_pass_done()
    mgr.end_pass(ws1, ws1.table)
    ws2 = mgr.begin_pass(p2)
    assert mgr.last_fresh_rows == 50     # staged feed was consumed
    assert set(ws2.sorted_keys.tolist()) == set(p2.tolist())
    # staged fresh rows match deterministic store init
    fresh = _keys(9000, 9050)
    idxf = ws2.translate(fresh)
    np.testing.assert_allclose(
        np.asarray(ws2.table)[idxf, :c.row_width],
        store.get_rows(fresh), rtol=1e-6)


def test_stale_staging_discarded_on_key_mismatch():
    c = cfg_small()
    store = HostEmbeddingStore(c)
    mgr = FeedPassManager(store)
    p1 = _keys(0, 100)
    ws1 = mgr.begin_pass(p1)
    ws1.translate(p1)
    mgr.end_pass(ws1, ws1.table)
    mgr.begin_feed_pass(_keys(100, 200))       # staged for the wrong keys
    actual = _keys(200, 300)
    ws2 = mgr.begin_pass(actual)               # different keys arrive
    assert set(ws2.sorted_keys.tolist()) == set(actual.tolist())
    idx = ws2.translate(actual)
    np.testing.assert_allclose(
        np.asarray(ws2.table)[idx, :c.row_width],
        store.get_rows(actual), rtol=1e-6)


def test_shrink_invalidates_resident_reuse():
    c = cfg_small()
    store = HostEmbeddingStore(c)
    mgr = FeedPassManager(store)
    keys = _keys(0, 64)
    ws1 = mgr.begin_pass(keys)
    idx = ws1.translate(keys)
    t = np.array(ws1.table)
    t[idx, 2] = 5.0
    mgr.end_pass(ws1, jax.numpy.asarray(t))
    evicted = store.shrink(min_show=0.5)       # all shows are 0 → all out
    assert evicted == len(keys)
    ws2 = mgr.begin_pass(keys)                 # must NOT reuse stale rows
    assert mgr.last_fresh_rows == len(keys)
    idx2 = ws2.translate(keys)
    rows = np.asarray(ws2.table)[idx2]
    np.testing.assert_allclose(rows[:, 2], 0.0)  # fresh init, not 5.0


def test_eval_pass_reuses_but_never_inserts_or_retains():
    c = cfg_small()
    store = HostEmbeddingStore(c)
    mgr = FeedPassManager(store)
    train_keys = _keys(0, 100)
    ws1 = mgr.begin_pass(train_keys)
    idx = ws1.translate(train_keys)
    t = np.array(ws1.table)
    t[idx, 2] = 7.0
    mgr.end_pass(ws1, jax.numpy.asarray(t))
    n_before = len(store)
    eval_keys = np.concatenate([train_keys[:50], _keys(7000, 7020)])
    ws_eval = mgr.begin_pass(eval_keys, test_mode=True)
    assert len(store) == n_before              # unseen keys NOT inserted
    # resident rows visible to eval carry trained values
    idxe = ws_eval.translate(train_keys[:50])
    np.testing.assert_allclose(np.asarray(ws_eval.table)[idxe, 2], 7.0)
    assert mgr.last_reused_rows == 50
    # eval did not replace the retained train working set
    ws3 = mgr.begin_pass(train_keys)
    assert mgr.last_fresh_rows == 0
    assert mgr.last_reused_rows == len(train_keys)


def test_reuse_on_sharded_mesh():
    mesh = make_mesh(4)
    c = cfg_small()
    store = HostEmbeddingStore(c)
    mgr = FeedPassManager(store, mesh)
    p1 = _keys(0, 300)
    ws1 = mgr.begin_pass(p1)
    assert ws1.n_shards == 4
    idx = ws1.translate(p1)
    t = np.array(ws1.table)
    t[idx, 2] += 2.0
    mgr.end_pass(ws1, jax.device_put(t, ws1.table.sharding))
    p2 = np.concatenate([p1[30:], _keys(8000, 8030)])
    ws2 = mgr.begin_pass(p2)
    assert ws2.n_shards == 4
    idx2 = ws2.translate(p1[30:])
    np.testing.assert_allclose(np.asarray(ws2.table)[idx2, 2], 2.0)
    np.testing.assert_allclose(
        np.asarray(ws2.table)[ws2.translate(_keys(8000, 8030)),
                              :c.row_width],
        store.get_rows(_keys(8000, 8030)), rtol=1e-6)


def test_feed_error_surfaces_at_wait():
    c = cfg_small()
    store = HostEmbeddingStore(c)
    mgr = FeedPassManager(store)
    ws = mgr.begin_pass(_keys(0, 10))
    ws.translate(_keys(0, 10))
    mgr.end_pass(ws, ws.table)
    bad = np.array([1], dtype=np.float64)      # wrong dtype → astype ok...
    # simulate a failing store fetch by closing over a poisoned store call
    orig = store.lookup_or_init

    def boom(keys):
        raise RuntimeError("feed fetch failed")

    store.lookup_or_init = boom
    try:
        mgr.begin_feed_pass(_keys(10, 20))
        with pytest.raises(RuntimeError, match="feed fetch failed"):
            mgr.wait_feed_pass_done()
    finally:
        store.lookup_or_init = orig


def test_a_full_build_and_an_incremental_boundary_size_the_table_alike():
    """A vocabulary that every pass fills (8192 keys): the first build and
    every boundary after it give the table the same rows, so the window
    meets no table shape the warm-up did not (a bucket of 10,240 rows is
    no multiple of the 4,096-row alignment a full build applies)."""
    from paddlebox_tpu.embedding.working_set import shard_rows
    cfg = EmbeddingConfig(dim=8, optimizer="adagrad", learning_rate=0.05)
    store = HostEmbeddingStore(cfg)
    mgr = FeedPassManager(store, make_mesh(1))
    keys = np.arange(1, 8193, dtype=np.uint64)
    rows = []
    for _ in range(3):
        ws = mgr.begin_pass(keys)
        mgr.pass_opened()
        mgr.pass_closed()
        mgr.end_pass(ws)
        rows.append(int(ws.padded_rows))
    assert mgr.last_reused_rows == 8192 and mgr.last_fresh_rows == 0
    assert rows == [12288] * 3 == [shard_rows(cfg, 8193, 1, 8)] * 3
    # both token cells' sizes, and DLRM's, are multiples already
    assert [shard_rows(cfg, n + 1, 1, 8) for n in (37984, 16384, 2516000)] \
        == [40960, 20480, 2621440]
