"""Spill-tier scale evidence: a 50M-key table
through SpillEmbeddingStore with the RAM row cache capped far below the
key count — the reference's SSD tier affordability story (LoadSSD2Mem,
box_wrapper.h:487-494: 10^10-key tables are disk-bounded, not
DRAM-bounded) at a scale the unit tests don't touch.

Host-only: no device in any timed window. Writes ONE JSON line (and
the file named by --out):
  - build: 50M fresh keys through lookup_or_init (init + row-file write)
  - two working-set passes with churn (pass B re-fetches 80% of pass A's
    keys + 20% fresh), measuring fetch keys/s and spill-file MB/s
  - memory: the HARD resident floor (key index + row cache + metadata)
    vs the row file size, plus measured RSS before/after dropping the
    file's page cache (clean memmap pages are reclaimable OS cache, not
    working memory — the drop shows the floor is real)

``--policy`` selects the RAM-tier admission policy: ``freq`` (the
show-count-weighted tier manager, embedding/tiering.py — the default)
or ``direct`` (the legacy direct-mapped last-wins install, kept as the
measured baseline the gate-held ``spill_10x`` bench point compares
against). ``--assoc N`` sets the cache's set associativity (default:
``flags.spill_cache_assoc``; ``direct`` forces 1-way — it IS the
direct-mapped geometry). Per-pass hit rates, the admission/eviction
counters, and the per-policy conflict-miss counts are recorded either
way; a final section refreshes a host-planed TrainerReplicaCache off
the tier ranking and replays the last pass's keys against it, so one
run carries the replica-hit numbers next to the RAM-tier ones.

Usage: python bench_spill.py [--keys 50000000] [--policy freq|direct]
                             [--assoc 4] [--out SPILL_r05.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from paddlebox_tpu.embedding import EmbeddingConfig
from paddlebox_tpu.embedding.spill_store import SpillEmbeddingStore


def rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1]) / 1024.0
    return float("nan")


def drop_file_cache(store) -> bool:
    """Flush dirty memmap pages, then evict the mapping's resident pages
    (madvise MADV_DONTNEED — fadvise cannot evict pages a live mapping
    references) so RSS shows the HARD resident floor (index + cache),
    not reclaimable file-backed cache.

    Returns whether the madvise eviction succeeded — a failed eviction
    leaves the file's pages resident and would silently report an
    INFLATED "hard floor" RSS as if the drop worked, so callers record
    the outcome next to every RSS-after-drop number."""
    import ctypes
    import errno
    import mmap as mmap_mod
    store._rows.flush()
    mm = store._rows
    libc = ctypes.CDLL(None, use_errno=True)
    addr = mm.ctypes.data
    page = os.sysconf("SC_PAGESIZE")
    base = addr - (addr % page)
    length = mm.nbytes + (addr - base)
    rc = libc.madvise(ctypes.c_void_p(base), ctypes.c_size_t(length),
                      mmap_mod.MADV_DONTNEED)
    ok = rc == 0
    if not ok:
        err = ctypes.get_errno()
        print(f"# madvise(MADV_DONTNEED) failed: "
              f"{errno.errorcode.get(err, err)}", file=sys.stderr,
              flush=True)
    fd = os.open(store._rows_path, os.O_RDONLY)
    try:
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    finally:
        os.close(fd)
    return ok


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=50_000_000)
    ap.add_argument("--pass-keys", type=int, default=4_000_000)
    ap.add_argument("--cache-rows", type=int, default=1 << 21)  # ~109MB
    ap.add_argument("--policy", choices=("freq", "direct"), default="freq")
    ap.add_argument("--assoc", type=int, default=None,
                    help="cache set associativity (default: "
                         "flags.spill_cache_assoc; direct forces 1)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cfg = EmbeddingConfig(dim=8, optimizer="adagrad", learning_rate=0.05)
    store = SpillEmbeddingStore(cfg, cache_rows=args.cache_rows,
                                initial_capacity=args.keys + 1024,
                                tier_policy=args.policy,
                                cache_assoc=args.assoc)
    rng = np.random.default_rng(0)
    out = {
        "metric": "spill_store_50m_key_scale",
        "total_keys": args.keys,
        "row_width": cfg.row_width,
        "tier_policy": args.policy,
        "spill_cache_assoc": int(store._assoc),
        "ram_cache_rows": args.cache_rows,
        "ram_cache_mb": round(args.cache_rows * cfg.row_width * 4 / 1e6,
                              1),
        "rss_start_mb": round(rss_mb(), 1),
    }

    # --- build: all keys exist on the spill tier ----------------------
    chunk = 2_000_000
    t0 = time.perf_counter()
    for lo in range(0, args.keys, chunk):
        n = min(chunk, args.keys - lo)
        # disjoint strided windows: every key unique without a 50M-key
        # np.unique pass
        keys = (np.arange(lo, lo + n, dtype=np.uint64) * np.uint64(2654435761)
                + np.uint64(1)) | np.uint64(1) << np.uint64(50)
        store.lookup_or_init(keys)
    build_s = time.perf_counter() - t0
    out["build_seconds"] = round(build_s, 1)
    out["build_keys_per_s"] = round(args.keys / build_s)
    out["row_file_gb"] = round(store.spill_file_bytes / 1e9, 3)
    out["rss_after_build_mb"] = round(rss_mb(), 1)

    # --- two passes with churn ----------------------------------------
    def key_window(idx_arr):
        return (idx_arr.astype(np.uint64) * np.uint64(2654435761)
                + np.uint64(1)) | np.uint64(1) << np.uint64(50)

    pa = rng.choice(args.keys, args.pass_keys, replace=False)
    passes = []
    for p, sel in enumerate((pa, None)):
        if sel is None:   # pass B: 80% of pass A + 20% fresh rows
            keep = pa[rng.random(args.pass_keys) < 0.8]
            fresh = rng.choice(args.keys, args.pass_keys - len(keep),
                               replace=False)
            sel = np.concatenate([keep, fresh])
        keys = key_window(np.unique(sel))
        drop_ok = drop_file_cache(store)    # cold spill tier per pass
        h0, m0 = store.cache_hits, store.cache_misses
        t0 = time.perf_counter()
        rows = store.lookup_or_init(keys)
        fetch_s = time.perf_counter() - t0
        # train-like write-back of every fetched row
        rows[:, 0] += 1.0
        t1 = time.perf_counter()
        store.write_back(keys, rows)
        wb_s = time.perf_counter() - t1
        # the pass-boundary re-evaluation the training loop would run
        # (decay + cold-slot demotion + counter flush)
        tier_stats = store.tier_end_pass()
        mb = rows.nbytes / 1e6
        hits = int(store.cache_hits - h0)
        misses = int(store.cache_misses - m0)
        passes.append({
            "keys": int(len(keys)),
            "fetch_seconds": round(fetch_s, 2),
            "fetch_keys_per_s": round(len(keys) / fetch_s),
            "fetch_mb_per_s": round(mb / fetch_s, 1),
            "writeback_mb_per_s": round(mb / wb_s, 1),
            "cache_hits": hits,
            "cache_misses": misses,
            "hit_rate": round(hits / max(1, hits + misses), 4),
            "conflict_misses": int(tier_stats["pass_conflicts"]),
            "tier_admitted": int(tier_stats["admitted"]),
            "tier_evicted": int(tier_stats["evicted"]),
            "tier_hot_rows": int(tier_stats["hot_rows"]),
            "pre_pass_cache_drop_ok": bool(drop_ok),
        })
        last_keys = keys
    out["passes"] = passes
    out["conflict_misses_total"] = int(store.conflict_misses)

    # --- HBM replica tier replay (flags.use_replica_cache path) -------
    # refresh harvests the tier ranking the two passes just built, then
    # the last pass's keys replay against the replica — the fraction the
    # staging would have short-circuited past RAM/SSD entirely
    from paddlebox_tpu.embedding.replica_cache import TrainerReplicaCache
    replica = TrainerReplicaCache(store, mesh=None)
    t0 = time.perf_counter()
    replica_rows = replica.refresh()
    served = replica.serve(np.sort(last_keys))
    out["replica"] = {
        "rows": int(replica_rows),
        "capacity_rows": int(replica.capacity_rows),
        "replica_hits": int(served.n if served is not None else 0),
        "replay_keys": int(len(last_keys)),
        "refresh_and_replay_seconds": round(time.perf_counter() - t0, 3),
    }
    out["rss_after_passes_mb"] = round(rss_mb(), 1)
    out["final_cache_drop_ok"] = bool(drop_file_cache(store))
    out["rss_after_cache_drop_mb"] = round(rss_mb(), 1)
    out["hard_floor_note"] = (
        "resident floor = key index (~16B/key) + RAM row cache + numpy "
        "bookkeeping; the row file's pages are reclaimable OS cache "
        "(rss_after_cache_drop shows the floor), so table capacity is "
        "bounded by DISK, matching the reference's SSD tier")
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
