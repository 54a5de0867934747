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
