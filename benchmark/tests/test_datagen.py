"""The generator's files parse back to what it generated, and a pass holds
the keys the recipe reckons: uniform draws from each field's values."""

import numpy as np
import pytest

from benchmark import datagen, sut

CARDS = [40_000_000, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63,
         38_000_000, 2_953_546, 403_346, 10, 2208, 11938, 155, 4, 976, 14,
         39_000_000, 25_000_000, 39_500_000, 585_935, 12972, 108, 36]
MIXES = {
    "onehot": dict(steps_per_pass=8, field_cardinalities=CARDS,
                   max_ind_range=3000, files_per_pass=3, hotness=1,
                   len="hotness"),
    "uncapped": dict(steps_per_pass=8, field_cardinalities=CARDS,
                     files_per_pass=3, hotness=1, len="hotness"),
    "multihot": dict(steps_per_pass=8, field_cardinalities=CARDS,
                     max_ind_range=6000, files_per_pass=3, hotness=4,
                     len="uniform_1_to_hotness"),
    "per_slot": dict(steps_per_pass=4, field_cardinalities=[50, 7, 9000, 3],
                     files_per_pass=2, hotness=[3, 1, 12, 2], len="hotness"),
}
BATCH, DENSE = 512, 13


def _cfg(n_slots):
    slots = [{"name": "label", "kind": "float"}]
    slots += [{"name": f"d{i}", "kind": "float"} for i in range(DENSE)]
    slots += [{"name": f"s{i}", "kind": "sparse"} for i in range(n_slots)]
    return {"slots": slots, "trainer": {"global_batch_size": BATCH}}


@pytest.mark.parametrize("name", sorted(MIXES))
def test_files_parse_back_to_what_was_generated(tmp_path, name):
    from paddlebox_tpu.data import SlotDataset
    mix = MIXES[name]
    n_slots = 4 if name == "per_slot" else 26
    hot = datagen.slot_hotness(mix, n_slots)
    for p, tag in zip(datagen.make_passes(mix, n_slots, DENSE, BATCH,
                                          2 ** 31 + 11), "AB"):
        files = datagen.write_pass(str(tmp_path), tag, p,
                                   mix["files_per_pass"])
        ds = SlotDataset(sut.build_schema(_cfg(n_slots), hot))
        ds.set_filelist(files)
        ds.load_into_memory(global_shuffle=False)
        assert ds.num_examples == p.num
        got = list(ds.batches(BATCH))
        assert np.array_equal(np.concatenate([b.ids for b in got]), p.ids)
        assert np.array_equal(np.concatenate([b.mask for b in got]), p.mask)
        floats = np.concatenate([b.floats for b in got])
        assert np.array_equal(floats[:, 0], p.labels.astype(np.float32))
        assert np.array_equal(floats[:, 1:], p.dense)
        assert np.array_equal(np.sort(ds.unique_keys()), p.unique_keys())


@pytest.mark.parametrize("name", ["onehot", "uncapped", "multihot"])
def test_unique_keys_land_on_the_reckoning(name):
    mix = MIXES[name]
    a, b = datagen.make_passes(mix, 26, DENSE, BATCH, 5)
    want = datagen.expected_unique_keys(mix, 26, BATCH)
    ka, kb = a.unique_keys(), b.unique_keys()
    assert abs(len(ka) - want) < 0.02 * want
    assert abs(len(kb) - want) < 0.02 * want
    assert 0 not in ka and 0 not in kb
    # a field never holds more values than its (cut) cardinality, and a
    # small field holds them all; B is another draw of the same fields
    sizes = datagen.field_sizes(mix, 26)
    for k in (ka, kb):
        per_field = np.bincount((k >> datagen.SLOT_SHIFT) - 1, minlength=26)
        assert np.all(per_field <= sizes)
        assert np.all(per_field[sizes <= 200] == sizes[sizes <= 200])
    tokens = BATCH * mix["steps_per_pass"] * (
        2.5 if mix["len"] == "uniform_1_to_hotness" else 1.0)
    present = 1.0 - (1.0 - 1.0 / sizes) ** tokens
    shared = len(np.intersect1d(ka, kb))
    assert abs(shared - np.sum(sizes * present ** 2)) < 0.03 * shared


def test_same_seed_same_traffic_other_seed_other_traffic():
    mix = MIXES["multihot"]
    a1, b1 = datagen.make_passes(mix, 26, DENSE, BATCH, 2 ** 31 + 7)
    a2, b2 = datagen.make_passes(mix, 26, DENSE, BATCH, 2 ** 31 + 7)
    a3, _ = datagen.make_passes(mix, 26, DENSE, BATCH, 2 ** 31 + 8)
    assert np.array_equal(a1.ids, a2.ids) and np.array_equal(b1.ids, b2.ids)
    assert np.array_equal(a1.labels, a2.labels)
    assert np.array_equal(b1.dense_milli, b2.dense_milli)
    assert not np.array_equal(a1.ids, a3.ids)


def test_a_pool_seed_gives_every_seed_the_same_examples_in_another_order():
    mix = {**MIXES["multihot"], "pool_seed": 5}
    runs = [datagen.make_passes(mix, 26, DENSE, BATCH, 2 ** 31 + n)
            for n in (7, 7, 8)]
    drawn = datagen.make_passes(MIXES["multihot"], 26, DENSE, BATCH, 5)

    def rows(p):        # each example whole: ids, lens, dense and label
        return np.concatenate([p.ids, p.lens, p.dense_milli,
                               p.labels[:, None]], axis=1)

    for k in range(2):
        same, again, other = (rows(r[k]) for r in runs)
        assert np.array_equal(same, again)
        assert not np.array_equal(same, other)
        pool = rows(drawn[k])
        assert not np.array_equal(same, pool)
        for got in (same, other):       # the pool's examples, every one
            assert np.array_equal(got[np.lexsort(got.T[::-1])],
                                  pool[np.lexsort(pool.T[::-1])])
    # A and B are ordered apart
    assert not np.array_equal(
        np.argsort(runs[0][0].ids[:, 0], kind="stable"),
        np.argsort(runs[0][1].ids[:, 0], kind="stable"))
