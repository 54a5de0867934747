"""KeyIndex — batch uint64→int64 index with native backend + dict fallback.

The embedding store's key→row index (the BoxPS key-agent role). The native
backend (key_index.cc) does linear-probing batch ops; the fallback keeps
the exact dict semantics the store always had. Both assign ids to new keys
in first-occurrence order, so row-append order is identical whichever
backend loads.
"""

from __future__ import annotations

import ctypes
from typing import Callable, Sequence

import numpy as np

from paddlebox_tpu.native.loader import load_native


def _configure(lib: ctypes.CDLL) -> None:
    c = ctypes
    u64p = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    lib.ki_create.restype = c.c_void_p
    lib.ki_create.argtypes = [c.c_int64]
    lib.ki_free.restype = None
    lib.ki_free.argtypes = [c.c_void_p]
    lib.ki_size.restype = c.c_int64
    lib.ki_size.argtypes = [c.c_void_p]
    lib.ki_lookup.restype = None
    lib.ki_lookup.argtypes = [c.c_void_p, u64p, c.c_int64, i64p]
    lib.ki_lookup_or_insert.restype = c.c_int64
    lib.ki_lookup_or_insert.argtypes = [c.c_void_p, u64p, c.c_int64, i64p]
    lib.ki_rebuild.restype = None
    lib.ki_rebuild.argtypes = [c.c_void_p, u64p, c.c_int64]
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    lib.pbtpu_block_plan.restype = None
    lib.pbtpu_block_plan.argtypes = [i32p, c.c_int64, c.c_int32, c.c_int64,
                                     i32p, i32p, i32p]
    lib.pbtpu_dedup_plan.restype = c.c_int64
    lib.pbtpu_dedup_plan.argtypes = [i32p, c.c_int64, c.c_int64, c.c_int32,
                                     c.c_int64, i32p, i32p, i32p, i32p,
                                     i32p]
    lib.pbtpu_merge2.restype = c.c_int64
    lib.pbtpu_merge2.argtypes = [i64p, c.c_int64, i64p, c.c_int64, i64p]


def get_lib() -> ctypes.CDLL | None:
    return load_native("libkeyindex.so", _configure)


def block_plan(idx: np.ndarray, super_block: int, n_blocks: int
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Group token row-ids by table super-block (binned-push host plan).

    Returns (order (n,) int32, rstart (n_blocks,) int32, end (n_blocks,)
    int32). Native counting sort when the lib is available (~1ms at 213k
    tokens on one core); numpy stable argsort (radix on the small block
    keys) otherwise.
    """
    idx = np.ascontiguousarray(idx, dtype=np.int32)
    n = len(idx)
    lib = get_lib()
    if lib is not None:
        order = np.empty(n, np.int32)
        rstart = np.empty(n_blocks, np.int32)
        end = np.empty(n_blocks, np.int32)
        lib.pbtpu_block_plan(idx, n, super_block, n_blocks, order, rstart,
                             end)
        return order, rstart, end
    bk = np.clip(idx // super_block, 0, n_blocks - 1)
    order = np.argsort(bk, kind="stable").astype(np.int32)
    counts = np.bincount(bk, minlength=n_blocks)
    ends = np.cumsum(counts)
    starts = ends - counts
    return (order, ((starts // 8) * 8).astype(np.int32),
            ends.astype(np.int32))


def dedup_plan_counted(idx: np.ndarray, n_rows: int, super_block: int,
                       n_blocks: int) -> tuple[tuple[np.ndarray, ...], int]:
    """Full-row counting sort + unique-row segment bounds (the host half
    of the reference's DedupKeysAndFillIdx/PushMergeCopy pairing; see
    key_index.cc pbtpu_dedup_plan for the array contracts), and the
    number of distinct valid rows the batch has.

    Returns ((order (n,), uniq (n,), segend (n,), rstart (n_blocks,),
    end (n_blocks,)) int32, n_unique). `uniq` holds the n_unique rows
    ascending, then pads with ascending out-of-range ids; `segend` pads
    with zero-width segments — so the device pre-merge needs no dynamic
    shapes, and any prefix `uniq[:L]`, `segend[:L]` with L >= n_unique
    is a whole plan of L lanes (Trainer._host_plan trims to one).
    Native when available; numpy otherwise.
    """
    idx = np.ascontiguousarray(idx, dtype=np.int32)
    n = len(idx)
    assert n_blocks >= 1 and super_block >= 1
    lib = get_lib()
    if lib is not None:
        order = np.empty(n, np.int32)
        uniq = np.empty(n, np.int32)
        segend = np.empty(n, np.int32)
        rstart = np.empty(n_blocks, np.int32)
        end = np.empty(n_blocks, np.int32)
        u = lib.pbtpu_dedup_plan(idx, n, n_rows, super_block, n_blocks,
                                 order, uniq, segend, rstart, end)
        return (order, uniq, segend, rstart, end), int(u)
    r = np.where((idx < 0) | (idx >= n_rows), n_rows, idx)
    order = np.argsort(r, kind="stable").astype(np.int32)
    sr = r[order]
    n_valid = int(np.searchsorted(sr, n_rows))
    uniq_rows, first = np.unique(sr[:n_valid], return_index=True)
    u = len(uniq_rows)
    uniq = np.empty(n, np.int32)
    uniq[:u] = uniq_rows
    uniq[u:] = n_rows + np.arange(n - u, dtype=np.int32)
    segend = np.full(n, n_valid, np.int32)
    segend[:max(0, u - 1)] = first[1:]
    # unique-lane windows per super-block (8-aligned starts, like
    # block_plan; stale lanes below the aligned start are masked by the
    # kernel's local-range check)
    b = np.minimum(uniq_rows // super_block, n_blocks - 1)
    counts = np.bincount(b, minlength=n_blocks)
    ends = np.cumsum(counts)
    return ((order, uniq, segend,
             (((ends - counts) // 8) * 8).astype(np.int32),
             ends.astype(np.int32)), u)


def dedup_plan(idx: np.ndarray, n_rows: int, super_block: int,
               n_blocks: int) -> tuple[np.ndarray, ...]:
    """dedup_plan_counted's five arrays, every one at its full length
    (`uniq` and `segend` n lanes: one a token, the most a batch can
    need)."""
    return dedup_plan_counted(idx, n_rows, super_block, n_blocks)[0]


def merge_sorted_runs(runs: Sequence[np.ndarray],
                      pmap: Callable = map) -> np.ndarray:
    """The ascending, duplicate-free union of ascending, duplicate-free
    int64 runs (signed order) — ``np.unique(np.concatenate(runs))`` to the
    element, by linear merges where that would sort again: a pairwise
    tree, each pair one native call that holds no GIL (key_index.cc
    pbtpu_merge2), a round's pairs through `pmap` (a thread pool's ``map``
    merges them side by side). One run alone comes back as it is. Without
    the native library numpy's sort answers."""
    level = [np.ascontiguousarray(r, dtype=np.int64) for r in runs if len(r)]
    if not level:
        return np.zeros(0, dtype=np.int64)
    lib = get_lib()
    if lib is None:
        return np.unique(np.concatenate(level))

    def merged(pair: list[np.ndarray]) -> np.ndarray:
        if len(pair) == 1:          # the odd run of a round moves up
            return pair[0]
        a, b = pair
        out = np.empty(len(a) + len(b), np.int64)
        n = lib.pbtpu_merge2(a, len(a), b, len(b), out)
        out.resize(n, refcheck=False)   # shrinks in place: no view is kept
        return out

    while len(level) > 1:
        level = list(pmap(merged, [level[i:i + 2]
                                   for i in range(0, len(level), 2)]))
    return level[0]


def native_available() -> bool:
    return get_lib() is not None


class KeyIndex:
    """Batch key index; picks the native backend when available.

    force_python=True pins the dict fallback (used by the parity tests)."""

    def __init__(self, capacity_hint: int = 1024,
                 force_python: bool = False):
        self._lib = None if force_python else get_lib()
        if self._lib is not None:
            self._h = self._lib.ki_create(int(capacity_hint))
            if not self._h:  # native allocation failed → dict fallback
                self._lib = None
        if self._lib is None:
            self._d: dict[int, int] = {}

    @property
    def is_native(self) -> bool:
        return self._lib is not None

    def __del__(self):
        lib = getattr(self, "_lib", None)
        if lib is not None and getattr(self, "_h", None):
            lib.ki_free(self._h)
            self._h = None

    def __len__(self) -> int:
        if self._lib is not None:
            return int(self._lib.ki_size(self._h))
        return len(self._d)

    # ------------------------------------------------------------------
    def lookup(self, keys: np.ndarray) -> np.ndarray:
        """→ int64 ids, -1 for absent keys."""
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        out = np.empty(len(keys), dtype=np.int64)
        if self._lib is not None:
            self._lib.ki_lookup(self._h, keys, len(keys), out)
        else:
            d = self._d
            for i, k in enumerate(keys.tolist()):
                out[i] = d.get(k, -1)
        return out

    def lookup_or_insert(self, keys: np.ndarray) -> tuple[np.ndarray, int]:
        """→ (int64 ids, n_new); new keys get sequential ids from len(self)
        in first-occurrence order."""
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        out = np.empty(len(keys), dtype=np.int64)
        if self._lib is not None:
            added = int(self._lib.ki_lookup_or_insert(
                self._h, keys, len(keys), out))
            return out, added
        d = self._d
        added = 0
        for i, k in enumerate(keys.tolist()):
            j = d.get(k, -1)
            if j < 0:
                j = len(d)
                d[k] = j
                added += 1
            out[i] = j
        return out, added

    def rebuild(self, keys: np.ndarray) -> None:
        """Reset to exactly `keys` with ids 0..n-1."""
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        if self._lib is not None:
            self._lib.ki_rebuild(self._h, keys, len(keys))
        else:
            self._d = {int(k): i for i, k in enumerate(keys.tolist())}
