"""Traffic of a run: two sets of pass files, A and B, made from ``--seed``.

One general generator reads a traffic mix (a data file under
``benchmark/workloads/``) and a configuration's slot list; a new mix is a
new data file, never new code. The recipe is the public one of the mix's
``source`` (facebookresearch/dlrm, ``--data-generation=random``: every
index drawn uniformly from its table's range), with the per-field table
sizes of a public data set and the source's own cut of scale:

* ``field_cardinalities``: the number of distinct values of each
  categorical field, in the fields' order, as the source lists them
  (``--arch-embedding-size``). Key skew is a shape and is not changed.
* ``max_ind_range`` (optional): the source's ``--max-ind-range`` — an
  index is taken modulo it, so a field holds ``min(cardinality,
  max_ind_range)`` values. A cut of scale; a mix that uses it says so.
* Every sparse slot holds ``len`` ids per example (``hotness`` of the
  mix: 1, or 1..hotness drawn uniformly, or always hotness), each drawn
  uniformly from its field's values. A key is ``(slot + 1) << 27 | (index
  + 1)`` so slots never share keys and no key is 0 (0 is the pad id of
  the packed batch).
* Passes A and B are two independent draws of ``steps_per_pass`` batches
  (two stretches of one log): what they share follows from the field
  sizes and the pass length alone.
* ``pool_seed`` (optional): the draw is this seed's in every run — one
  pool of examples a pass — and ``--seed`` orders each pass's examples.
  Every seed then gives the same set of examples in another order: for a
  cell whose speed follows what its examples hold (an expert layer's load
  follows the tokens), so that a run's reading is the tree's and not the
  draw's. Absent, ``--seed`` makes the draw and the order is the draw's.
* Dense values are multiples of 0.001 in [-9.999, 9.999] (a clipped
  normal), so the text holds them exactly as the float32 the reference
  uses; labels are Bernoulli(sigmoid(2 * dense @ w / sqrt(F))).

The writer builds each file as one fixed-width byte matrix from integer
arrays (zero-padded decimal fields, which the slot parser reads as the
same numbers) — no per-row Python.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import json
import os

import numpy as np

SLOT_SHIFT = 27           # key = (slot + 1) << 27 | (index + 1)
ID_DIGITS = 10            # so up to 73 slots: 74 << 27 < 10**10


def local_index(ids: np.ndarray) -> np.ndarray:
    """The key format inverted: each token's 0-based index within its
    field (what the generator drew, less one); 0 where the id is absent
    (the mask says which)."""
    ids = np.asarray(ids, np.int64)
    return np.where(ids != 0, (ids & ((1 << SLOT_SHIFT) - 1)) - 1, 0)


@dataclasses.dataclass
class Pass:
    """One pass of examples, as the generator wrote them."""
    ids: np.ndarray       # (N, T) int64, 0 where a slot holds fewer ids
    lens: np.ndarray      # (N, S) int32 ids present per slot
    dense_milli: np.ndarray   # (N, F) int32, the dense value times 1000
    labels: np.ndarray    # (N,) int8
    hotness: np.ndarray   # (S,) int32 columns per slot (the slots' max_len)

    @property
    def num(self) -> int:
        return len(self.labels)

    @property
    def dense(self) -> np.ndarray:
        return (self.dense_milli / 1000.0).astype(np.float32)

    @property
    def mask(self) -> np.ndarray:
        cols = np.concatenate([np.arange(h) for h in self.hotness])
        return cols[None, :] < np.repeat(self.lens, self.hotness, axis=1)

    def head(self, n: int) -> "Pass":
        return Pass(self.ids[:n], self.lens[:n], self.dense_milli[:n],
                    self.labels[:n], self.hotness)

    def batches(self, batch: int, n: int) -> list[dict]:
        """The first `n` batches as the trainer should see them: ids with
        0 where absent, presence mask, dense floats, labels."""
        head = self.head(n * batch)
        mask, dense = head.mask, head.dense
        return [{"ids": head.ids[k * batch:(k + 1) * batch],
                 "mask": mask[k * batch:(k + 1) * batch],
                 "dense": dense[k * batch:(k + 1) * batch],
                 "labels": head.labels[k * batch:(k + 1) * batch]
                 .astype(np.float32)} for k in range(n)]

    def unique_keys(self) -> np.ndarray:
        flat = self.ids[self.mask]
        return np.unique(flat)


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    for key in ("source", "field_cardinalities", "steps_per_pass",
                "files_per_pass", "hotness", "len"):
        if key not in mix:
            raise ValueError(f"traffic mix {path} lacks {key!r}")
    if mix["len"] not in ("uniform_1_to_hotness", "hotness"):
        raise ValueError(f"traffic mix {path}: unknown len rule "
                         f"{mix['len']!r}")
    return mix


def slot_counts(cfg: dict) -> tuple[int, int]:
    """(sparse slots, dense values) of a configuration's slot list; the
    first float slot is the label."""
    n_sparse = sum(1 for s in cfg["slots"] if s["kind"] == "sparse")
    return n_sparse, len(cfg["slots"]) - n_sparse - 1


def slot_hotness(mix: dict, n_slots: int) -> np.ndarray:
    h = mix["hotness"]
    hot = np.full(n_slots, h, np.int32) if np.isscalar(h) \
        else np.asarray(h, np.int32)
    if hot.shape != (n_slots,) or hot.min() < 1:
        raise ValueError(f"hotness {h!r} does not fit {n_slots} slots")
    return hot


def field_sizes(mix: dict, n_slots: int) -> np.ndarray:
    """Values each field holds: its cardinality, cut by ``max_ind_range``."""
    sizes = np.asarray(mix["field_cardinalities"], np.int64)
    if sizes.shape != (n_slots,) or sizes.min() < 1:
        raise ValueError(f"{len(sizes)} field cardinalities do not fit "
                         f"{n_slots} slots")
    if mix.get("max_ind_range"):
        sizes = np.minimum(sizes, int(mix["max_ind_range"]))
    if sizes.max() + 1 >= 1 << SLOT_SHIFT:
        raise ValueError(f"a field of {sizes.max()} values does not fit "
                         f"the key format's {1 << SLOT_SHIFT} local ids")
    return sizes


def _mean_len(mix: dict, hot: np.ndarray) -> np.ndarray:
    return (hot + 1) / 2.0 if mix["len"] == "uniform_1_to_hotness" \
        else hot.astype(np.float64)


def expected_unique_keys(mix: dict, n_slots: int, batch: int) -> float:
    """Unique keys of one pass as the recipe reckons them: a value is
    present unless every token of its field missed it."""
    sizes = field_sizes(mix, n_slots).astype(np.float64)
    tokens = mix["steps_per_pass"] * batch \
        * _mean_len(mix, slot_hotness(mix, n_slots))
    return float(np.sum(sizes * (1.0 - (1.0 - 1.0 / sizes) ** tokens)))


def _dense_and_labels(rng, n: int, dense_dim: int, w: np.ndarray):
    milli = np.clip(np.rint(rng.standard_normal((n, dense_dim),
                                                dtype=np.float32) * 1000.0),
                    -9999, 9999).astype(np.int32)
    logit = 2.0 * (milli / 1000.0) @ w / np.sqrt(max(dense_dim, 1))
    labels = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.int8)
    return milli, labels


def _slot_columns(mix: dict, tag: int, s: int, h: int, n: int, seed: int,
                  size: int):
    """Slot `s` of one pass: ids (n, h) and lens (n,)."""
    rng = np.random.default_rng([int(seed), tag, s])
    if mix["len"] == "uniform_1_to_hotness" and h > 1:
        ln = rng.integers(1, h + 1, size=n, dtype=np.int32)
    else:
        ln = np.full(n, h, np.int32)
    present = np.arange(h)[None, :] < ln[:, None]
    local = rng.integers(1, size + 1, size=(n, h), dtype=np.int64)
    return np.where(present, ((s + 1) << SLOT_SHIFT) | local, 0), ln


def make_passes(mix: dict, n_slots: int, dense_dim: int, batch: int,
                seed: int, threads: int = 8) -> tuple[Pass, Pass]:
    """Passes A and B of one run. The same seed gives the same passes; with
    the mix's ``pool_seed``, every seed gives that seed's draw, each pass's
    examples in an order of its own."""
    order_seed, seed = seed, int(mix.get("pool_seed", seed))
    hot = slot_hotness(mix, n_slots)
    sizes = field_sizes(mix, n_slots)
    if (n_slots + 1) << SLOT_SHIFT >= 10 ** ID_DIGITS:
        raise ValueError(f"{n_slots} slots do not fit the key format")
    n = int(mix["steps_per_pass"]) * batch
    starts = np.concatenate([[0], np.cumsum(hot)])
    ids = [np.zeros((n, int(starts[-1])), np.int64) for _ in range(2)]
    lens = [np.empty((n, n_slots), np.int32) for _ in range(2)]

    def one(job):
        k, s = job
        ids[k][:, starts[s]:starts[s + 1]], lens[k][:, s] = _slot_columns(
            mix, 0xA + k, s, int(hot[s]), n, seed, int(sizes[s]))

    with concurrent.futures.ThreadPoolExecutor(max(1, threads)) as pool:
        list(pool.map(one, [(k, s) for k in range(2)
                            for s in range(n_slots)]))
    w = np.random.default_rng([int(seed), 0xD]).normal(size=dense_dim)
    out = []
    for k in range(2):
        milli, labels = _dense_and_labels(
            np.random.default_rng([int(seed), 0xE, k]), n, dense_dim, w)
        if "pool_seed" in mix:
            order = np.random.default_rng(
                [int(order_seed), 0xF, k]).permutation(n)
            ids[k], lens[k] = ids[k][order], lens[k][order]
            milli, labels = milli[order], labels[order]
        out.append(Pass(ids[k], lens[k], milli, labels, hot))
    return out[0], out[1]


# --------------------------------------------------------------------------
# the slot-text writer
# --------------------------------------------------------------------------

def _digits(out: np.ndarray, v: np.ndarray, width: int) -> None:
    """Zero-padded decimal digits of the non-negative `v` (n,) into the
    byte columns `out` (n, width)."""
    v = v.astype(np.int64, copy=True)
    for k in range(width - 1, -1, -1):
        v, r = np.divmod(v, 10)
        out[:, k] = r + 48


def _file_bytes(p: Pass, a: int, b: int) -> bytes:
    """Rows [a, b) as slot text: ``1 <label>``, then ``1 <dense>`` per
    dense slot, then ``<len> <id> ...`` per sparse slot, one example a
    line, in the schema's order."""
    n = b - a
    F = p.dense_milli.shape[1]
    S = len(p.hotness)
    len_w = [len(str(int(h))) for h in p.hotness]
    width = 4 + 9 * F + sum(lw + 1 + int(h) * (ID_DIGITS + 1)
                            for lw, h in zip(len_w, p.hotness)) + 1
    mat = np.full((n, width), 32, np.uint8)          # spaces
    keep = np.ones((n, width), bool)
    mat[:, 0] = 49                                   # "1 <label> "
    mat[:, 2] = p.labels[a:b] + 48
    col = 4
    dm = p.dense_milli[a:b]
    for j in range(F):                               # "1 s#.### "
        v = dm[:, j]
        mat[:, col] = 49
        mat[:, col + 2] = np.where(v < 0, 45, 48)    # '-' or a leading 0
        av = np.abs(v)
        mat[:, col + 3] = av // 1000 + 48
        mat[:, col + 4] = 46
        _digits(mat[:, col + 5:col + 8], av % 1000, 3)
        col += 9
    tcol = 0
    for s in range(S):
        h, lw = int(p.hotness[s]), len_w[s]
        ln = p.lens[a:b, s]
        _digits(mat[:, col:col + lw], ln, lw)
        col += lw + 1
        for j in range(h):
            _digits(mat[:, col:col + ID_DIGITS], p.ids[a:b, tcol + j],
                    ID_DIGITS)
            if j > 0:
                keep[:, col:col + ID_DIGITS + 1] = (ln > j)[:, None]
            col += ID_DIGITS + 1
        tcol += h
    mat[:, col] = 10                                 # newline
    return (mat.tobytes() if keep.all() else mat[keep].tobytes())


def write_pass(root: str, tag: str, p: Pass, n_files: int,
               threads: int = 8) -> list[str]:
    """Write `p` as `n_files` slot-text files under `root`; file k holds
    the k-th contiguous run of rows, so the file list in order is the pass
    in order."""
    per = -(-p.num // n_files)
    jobs = [(os.path.join(root, f"{tag}-part-{k:03d}.txt"),
             k * per, min((k + 1) * per, p.num)) for k in range(n_files)]
    jobs = [j for j in jobs if j[1] < j[2]]

    def one(job):
        path, a, b = job
        with open(path, "wb") as f:
            f.write(_file_bytes(p, a, b))
        return path

    with concurrent.futures.ThreadPoolExecutor(
            max(1, min(threads, len(jobs)))) as pool:
        return list(pool.map(one, jobs))
