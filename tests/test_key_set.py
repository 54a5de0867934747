"""The pass's key set as a product of its load (ISSUE 31): sorted runs a
file on the loader's threads, one merge, a memo tied to the records'
version. ``SlotDataset.unique_keys()`` must equal the plain definition,
``np.unique(np.concatenate(records.sparse_values))``, in value, dtype and
order whatever happened to the records, and the counters must say whether
the load's set answered or a rebuild did."""

import concurrent.futures
import copy
import os
import sys

import numpy as np
import pytest

from paddlebox_tpu import monitor
from paddlebox_tpu.data import (DataFeedSchema, Slot, SlotDataset,
                                SlotRecordBatch, SlotType,
                                parse_multislot_lines)
from paddlebox_tpu.monitor import names
from paddlebox_tpu.native import key_index

COUNTERS = ("dataset.key_runs", "dataset.key_set_reused",
            "dataset.key_set_rebuilt")


def _schema(kind: str) -> DataFeedSchema:
    if kind == "onehot":
        return DataFeedSchema.ctr(num_sparse=5, num_float=2, batch_size=4,
                                  max_len=1)
    if kind == "multihot":
        return DataFeedSchema.ctr(num_sparse=3, num_float=1, batch_size=4,
                                  max_len=6)
    assert kind == "sequence"
    return DataFeedSchema(
        [Slot("label", SlotType.FLOAT, max_len=1),
         Slot("tokens", SlotType.UINT64, max_len=16, sequence=True),
         Slot("side", SlotType.UINT64, max_len=2)], batch_size=4)


def _lines(schema, n, seed, vocab=40, key=lambda rng, vocab: int(
        rng.integers(0, vocab))):
    """`n` slot-text lines; a small vocabulary, so files share keys."""
    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(n):
        parts = []
        for slot in schema.slots:
            if slot.type == SlotType.FLOAT:
                vals = [f"{rng.random():.3f}"] * slot.max_len
            elif slot.sequence:
                vals = [str(key(rng, vocab)) for _ in range(slot.max_len)]
            else:
                vals = [str(key(rng, vocab))
                        for _ in range(rng.integers(1, slot.max_len + 1))]
            parts += [str(len(vals))] + vals
        lines.append(" ".join(parts))
    return lines


def _files(tmp_path, schema, counts, seed=0, **kw):
    """One file a count; a count of 0 is an empty file."""
    paths = []
    for i, n in enumerate(counts):
        p = tmp_path / f"part-{seed}-{i}"
        p.write_text("".join(line + "\n"
                             for line in _lines(schema, n, seed + i, **kw)))
        paths.append(str(p))
    return paths


def _loaded(tmp_path, kind="multihot", counts=(9, 7, 11), **load_kw):
    schema = _schema(kind)
    ds = SlotDataset(schema)
    ds.set_filelist(_files(tmp_path, schema, counts))
    ds.load_into_memory(**{"global_shuffle": False, **load_kw})
    return ds


def _plain(ds) -> np.ndarray:
    """The definition, from the records as they are now."""
    plain = ds.records.unique_keys()
    if ds.records.sparse_values:
        assert np.array_equal(
            plain, np.unique(np.concatenate(ds.records.sparse_values)))
    return plain


def _counted(fn):
    """fn()'s result and what it added to the three counters."""
    s0 = monitor.STATS.snapshot()
    out = fn()
    s1 = monitor.STATS.snapshot()
    return out, {k.split(".")[1]: int(s1.get(k, 0) - s0.get(k, 0))
                 for k in COUNTERS}


def _same(got: np.ndarray, want: np.ndarray) -> None:
    assert got.dtype == want.dtype == np.int64
    assert got.ndim == 1 and np.array_equal(got, want)


# ---------------------------------------------------------------------------
# (a) a load leaves the set: 1, 3 and 16 files, one of them empty
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["onehot", "multihot", "sequence"])
@pytest.mark.parametrize("counts", [(12,), (9, 0, 11), (5,) * 15 + (0,)],
                         ids=["1file", "3files", "16files"])
def test_load_leaves_the_key_set(tmp_path, kind, counts):
    ds, at_load = _counted(lambda: _loaded(tmp_path, kind, counts))
    assert at_load == {"key_runs": len(counts), "key_set_reused": 0,
                       "key_set_rebuilt": 0}
    keys, at_call = _counted(ds.unique_keys)
    _same(keys, _plain(ds))
    assert at_call == {"key_runs": 0, "key_set_reused": 1,
                       "key_set_rebuilt": 0}
    # a second caller (eval after train, a checkpoint's key list) gets the
    # same array, which no caller can write through
    assert ds.unique_keys() is keys and not keys.flags.writeable


def test_load_of_nothing_but_empty_files(tmp_path):
    ds = _loaded(tmp_path, "onehot", (0, 0))
    assert ds.num_examples == 0
    _same(ds.unique_keys(), np.zeros(0, np.int64))


# ---------------------------------------------------------------------------
# (b) the local shuffle permutes rows: the load's set still answers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["load", "after"])
def test_local_shuffle_keeps_the_set(tmp_path, how):
    if how == "load":       # no shuffle service: the load's own shuffle
        ds = _loaded(tmp_path, global_shuffle=True)
    else:
        ds = _loaded(tmp_path)
        before = ds.records
        ds.local_shuffle()
        assert ds.records is not before
    keys, delta = _counted(ds.unique_keys)
    _same(keys, _plain(ds))
    assert delta == {"key_runs": 0, "key_set_reused": 1,
                     "key_set_rebuilt": 0}


def test_local_shuffle_of_stale_records_does_not_revive_the_set(tmp_path):
    ds = _loaded(tmp_path)
    ds.slots_shuffle(["slot_0"], seed=3)       # the set is stale now
    ds.local_shuffle()
    _, delta = _counted(ds.unique_keys)
    assert delta["key_set_rebuilt"] == 1 and delta["key_set_reused"] == 0


# ---------------------------------------------------------------------------
# (c) whatever changes the records falls to the rebuild, never to the
# stale set
# ---------------------------------------------------------------------------

def _slots_shuffle(ds, tmp_path):
    ds.slots_shuffle(["slot_0", "slot_2"], seed=5)


def _merge_by_ins_id(ds, tmp_path):
    n = ds.records.num
    ds.records.ins_id[:] = np.arange(n, dtype=np.uint64) // 2 + 1
    ds.merge_by_ins_id(merge_size=2)        # an odd one out is dropped


def _merge_by_search_id(ds, tmp_path):
    n = ds.records.num
    ds.records.search_id[:] = np.arange(n, dtype=np.uint64)[::-1] % 5
    ds.merge_by_search_id()


def _rebind_resampled(ds, tmp_path):
    """What metrics/auc_runner.py does to a copy, done to the dataset: one
    column redrawn from a pool, the records rebound."""
    rec = copy.copy(ds.records)
    rec.sparse_values = list(rec.sparse_values)
    rec.sparse_values[1] = np.random.default_rng(7).integers(
        1000, 1040, size=len(rec.sparse_values[1]))
    ds.records = rec


def _rebind_fewer_rows(ds, tmp_path):
    ds.records = ds.records.select(np.arange(0, ds.records.num, 3))


def _reload_other_files(ds, tmp_path):
    ds.set_filelist(_files(tmp_path, ds.schema, (6, 4), seed=100,
                           vocab=4000))
    ds.load_into_memory(global_shuffle=False)


MUTATIONS = [_slots_shuffle, _merge_by_ins_id, _merge_by_search_id,
             _rebind_resampled, _rebind_fewer_rows]


@pytest.mark.parametrize("mutate", MUTATIONS,
                         ids=[m.__name__.strip("_") for m in MUTATIONS])
def test_a_change_to_the_records_rebuilds(tmp_path, mutate):
    ds = _loaded(tmp_path)
    stale = ds.unique_keys()
    mutate(ds, tmp_path)
    keys, delta = _counted(ds.unique_keys)
    _same(keys, _plain(ds))
    assert keys is not stale
    n_cols = len(ds.records.sparse_values)
    assert delta == {"key_runs": n_cols, "key_set_reused": 0,
                     "key_set_rebuilt": 1}
    # the rebuilt set is kept for the records it was built for
    again, delta = _counted(ds.unique_keys)
    assert again is keys and delta["key_set_reused"] == 1


def test_a_shallow_copy_that_rebinds_rebuilds_and_spares_the_original(
        tmp_path):
    """auc_runner's ablation: `copy.copy(dataset)` carries the original's
    set and version; the rebind must strand it on the copy alone."""
    ds = _loaded(tmp_path)
    keys = ds.unique_keys()
    ablated = copy.copy(ds)
    _rebind_resampled(ablated, tmp_path)
    got, delta = _counted(ablated.unique_keys)
    _same(got, _plain(ablated))
    assert not np.array_equal(got, keys)
    assert delta["key_set_rebuilt"] == 1 and delta["key_set_reused"] == 0
    mine, delta = _counted(ds.unique_keys)
    assert mine is keys and delta["key_set_reused"] == 1


def test_unroll_plugin_rebuilds(tmp_path):
    schema = _schema("multihot")

    def plugin(lns, sch):
        return parse_multislot_lines(list(lns), sch)

    def unroll(batch):      # keeps every other instance, twice
        return batch.select(np.repeat(np.arange(0, batch.num, 2), 2))

    plugin.unroll = unroll
    ds = SlotDataset(schema)
    ds.set_filelist(_files(tmp_path, schema, (8, 5), vocab=4000))
    ds.set_parser_plugin(plugin)
    _, at_load = _counted(
        lambda: ds.load_into_memory(global_shuffle=False))
    assert at_load["key_runs"] == 0     # no runs of rows that will not stay
    keys, delta = _counted(ds.unique_keys)
    _same(keys, _plain(ds))
    assert delta["key_set_rebuilt"] == 1 and delta["key_set_reused"] == 0


def test_release_and_reload_with_other_files(tmp_path):
    ds = _loaded(tmp_path)
    first = ds.unique_keys()
    ds.release_memory()
    with pytest.raises(AssertionError):
        ds.unique_keys()
    _reload_other_files(ds, tmp_path)
    keys, delta = _counted(ds.unique_keys)
    _same(keys, _plain(ds))
    assert not np.array_equal(keys, first)
    assert delta["key_set_reused"] == 1     # the new load's own set


def test_exchange_through_a_shuffle_service_rebuilds(tmp_path):
    """Records that came from other ranks are not the files' rows."""
    class OneRankService:
        world = 1

        def exchange(self, routed, schema):
            # another rank's share in place of half of ours
            got = [b for b in routed if b is not None and b.num]
            return [got[0].select(np.arange(0, got[0].num, 2))]

    schema = _schema("multihot")
    ds = SlotDataset(schema, shuffle_service=OneRankService())
    ds.set_filelist(_files(tmp_path, schema, (9, 7), vocab=4000))
    _, at_load = _counted(lambda: ds.load_into_memory(global_shuffle=True))
    assert at_load["key_runs"] == 0
    keys, delta = _counted(ds.unique_keys)
    _same(keys, _plain(ds))
    assert delta["key_set_rebuilt"] == 1


# ---------------------------------------------------------------------------
# (d) preload: the runs and the merge happen on the preload thread
# ---------------------------------------------------------------------------

def test_preload_leaves_the_key_set(tmp_path):
    schema = _schema("onehot")
    ds = SlotDataset(schema)
    ds.set_filelist(_files(tmp_path, schema, (6, 6, 6, 6)))
    s0 = monitor.STATS.snapshot()
    ds.preload_into_memory(global_shuffle=False)
    ds.wait_preload_done()
    assert monitor.STATS.snapshot().get("dataset.key_runs", 0) \
        - s0.get("dataset.key_runs", 0) == 4
    keys, delta = _counted(ds.unique_keys)
    _same(keys, _plain(ds))
    assert delta == {"key_runs": 0, "key_set_reused": 1,
                     "key_set_rebuilt": 0}


# ---------------------------------------------------------------------------
# (e) keys at and above 2^63 arrive as negative int64: signed order kept
# ---------------------------------------------------------------------------

WIDE_KEYS = (0, 1, (1 << 63) - 1, 1 << 63, (1 << 63) + 1, (1 << 64) - 1)


def _wide_key(rng, vocab):
    i = int(rng.integers(len(WIDE_KEYS) + 1))
    return WIDE_KEYS[i] if i < len(WIDE_KEYS) else int(rng.integers(1 << 62))


@pytest.mark.parametrize("rebuilt", [False, True], ids=["load", "rebuild"])
def test_keys_at_and_above_2_63_keep_signed_order(tmp_path, rebuilt):
    schema = _schema("multihot")
    ds = SlotDataset(schema)
    ds.set_filelist(_files(tmp_path, schema, (20, 20, 20), key=_wide_key))
    ds.load_into_memory(global_shuffle=False)
    if rebuilt:
        ds.records = ds.records.select(np.arange(ds.records.num)[::-1])
    keys = ds.unique_keys()
    _same(keys, _plain(ds))
    assert keys[0] == np.iinfo(np.int64).min and keys[0] < 0 < keys[-1]
    assert np.all(np.diff(keys.astype(object)) > 0)
    assert set(WIDE_KEYS) <= set(keys.view(np.uint64).tolist())


# ---------------------------------------------------------------------------
# (f) without the native library numpy answers with the same array
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rebuilt", [False, True], ids=["load", "rebuild"])
def test_numpy_fallback_gives_the_same_array(tmp_path, monkeypatch, rebuilt):
    assert key_index.native_available()
    native = _loaded(tmp_path, counts=(9, 0, 11, 4))
    monkeypatch.setattr(key_index, "get_lib", lambda: None)
    fallback = _loaded(tmp_path, counts=(9, 0, 11, 4))
    for ds in (native, fallback) if rebuilt else ():
        ds.slots_shuffle(["slot_1"], seed=1)
    _same(fallback.unique_keys(), _plain(fallback))
    # `native` merged (or merges) with the library, `fallback` without
    monkeypatch.undo()
    _same(native.unique_keys(), fallback.unique_keys())


# ---------------------------------------------------------------------------
# (g) the merge alone against np.unique
# ---------------------------------------------------------------------------

def _random_runs(k, seed, hi=5000, longest=400):
    rng = np.random.default_rng(seed)
    return [np.unique(rng.integers(-hi, hi, size=rng.integers(0, longest)))
            for _ in range(k)]


MERGE_CASES = {
    "none": [],
    "one": _random_runs(1, 1),
    "one_empty": [np.zeros(0, np.int64)],
    "all_empty": [np.zeros(0, np.int64)] * 4,
    "two": _random_runs(2, 2),
    "three": _random_runs(3, 3),
    "five_dense": _random_runs(5, 5, hi=60),
    "sixteen": _random_runs(16, 16),
    "seventeen": _random_runs(17, 17),
    "thirtythree": _random_runs(33, 33, hi=10**6),
    "empties_between": [r for run in _random_runs(5, 6)
                        for r in (np.zeros(0, np.int64), run)],
    "all_the_same_run": [_random_runs(1, 7)[0]] * 7,
    "disjoint_ranges": [np.arange(i * 100, i * 100 + 50, dtype=np.int64)
                        for i in (3, 0, 2, 1)],
    "int64_edges": [np.array([np.iinfo(np.int64).min, -1, 0], np.int64),
                    np.array([-1, np.iinfo(np.int64).max], np.int64),
                    np.array([np.iinfo(np.int64).min,
                              np.iinfo(np.int64).max], np.int64)],
}


@pytest.mark.parametrize("how", ["native", "native_on_a_pool", "numpy"])
@pytest.mark.parametrize("case", MERGE_CASES)
def test_merge_sorted_runs_is_np_unique(case, how, monkeypatch):
    if how == "numpy":
        monkeypatch.setattr(key_index, "get_lib", lambda: None)
    else:
        assert key_index.native_available()
    runs = MERGE_CASES[case]
    kept = [r.copy() for r in runs]
    want = np.unique(np.concatenate(runs)) if runs else np.zeros(0, np.int64)
    with concurrent.futures.ThreadPoolExecutor(3) as pool:
        got = key_index.merge_sorted_runs(
            runs, *([pool.map] if how == "native_on_a_pool" else []))
    _same(got, want.astype(np.int64))
    assert got.base is None or len(runs) == 1   # no longer buffer kept alive
    for r, k in zip(runs, kept):        # the runs are read, not written
        assert np.array_equal(r, k)


def test_merges_side_by_side_on_more_threads_than_cores():
    """A round's pairs run on the pool with no GIL held; what one merge
    writes no other may touch. More workers than cores and a short switch
    interval, bounded in time."""
    rng = np.random.default_rng(31)
    runs = [np.unique(rng.integers(-10**7, 10**7, size=20_000))
            for _ in range(64)]
    want = np.unique(np.concatenate(runs))
    workers = 4 * (os.cpu_count() or 2)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with concurrent.futures.ThreadPoolExecutor(workers) as pool:
            # whole merges side by side, and three whose rounds go through
            # the same pool (fewer than its workers, so none waits on itself)
            jobs = [pool.submit(key_index.merge_sorted_runs, runs)
                    for _ in range(workers)]
            jobs += [pool.submit(key_index.merge_sorted_runs, runs, pool.map)
                     for _ in range(3)]
            _, late = concurrent.futures.wait(jobs, timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not late
    for job in jobs:
        _same(job.result(), want)


# ---------------------------------------------------------------------------
# the names are a closed list, and the pass's flight record carries them
# ---------------------------------------------------------------------------

def test_the_counters_and_the_span_are_registered():
    assert names.KEY_SET_COUNTER_NAMES == COUNTERS
    assert "ingest/key_merge" in names.SPAN_NAMES
    assert names.is_registered("ingest/key_merge")


def test_the_merge_runs_under_its_span(tmp_path):
    sink = monitor.MemorySink()
    hub = monitor.hub()
    hub.enable(sink)
    try:
        _loaded(tmp_path)
    finally:
        hub.disable()
    spans = [r["name"] for r in sink.records if r["type"] == "span"]
    assert spans.count("ingest/key_merge") == 1
    # inside the load, so it closes first
    assert spans.index("ingest/key_merge") < spans.index("ingest")


def test_plain_definition_is_untouched():
    """SlotRecordBatch.unique_keys stays the definition tests compare
    against: no memo, a fresh writable array a call."""
    schema = _schema("multihot")
    rec = parse_multislot_lines(_lines(schema, 12, 0), schema)
    a, b = rec.unique_keys(), rec.unique_keys()
    assert a is not b and a.flags.writeable
    _same(a, np.unique(np.concatenate(rec.sparse_values)))
    assert SlotRecordBatch.empty(schema).unique_keys().dtype == np.int64
