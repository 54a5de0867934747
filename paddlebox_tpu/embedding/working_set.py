"""Per-pass embedding working set.

The load-bearing trick of BoxPS (SURVEY.md §2.3 "Sparse model parallelism"):
HBM never holds the whole 10^10-key table — only the keys seen in the current
pass. ``BeginFeedPass``/``EndFeedPass`` build the pass's working set from SSD
into GPU HBM; ``EndPass`` applies/persists it (box_wrapper.h:419-424).

TPU equivalent:

- ``PassWorkingSet.begin_pass(store, keys, mesh)`` — dedup the pass's keys,
  assign dense indices 1..K (0 = null/padding row), fetch rows from the host
  store, lay them out as one (N_pad, row_width) float32 array sharded
  contiguously over the mesh (row i lives on shard i // rows_per_shard).
- ``translate(ids, mask)`` — vectorized uint64 key → int32 index translation
  (one native KeyIndex batch probe over the pass keys); this runs in the
  host data pipeline so jit only ever sees dense int32 indices.
- ``end_pass(store, table)`` — pull the table back and write rows into the
  host store (the EndPass persist).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from paddlebox_tpu.config import flags
from paddlebox_tpu.embedding import quant
from paddlebox_tpu.embedding.config import EmbeddingConfig
from paddlebox_tpu.embedding.store import HostEmbeddingStore
from paddlebox_tpu.monitor import device_scope, device_scopes
from paddlebox_tpu.native.key_index import KeyIndex
from paddlebox_tpu.parallel import mesh as mesh_lib


# ---------------------------------------------------------------------------
# Pass-boundary transfer compression (Flags.transfer_compress_embedx).
#
# The reference's Quant/ShowClk feature types store embedx quantized inside
# the PS to cut memory and transfer (box_wrapper.cu pull variants). The
# TPU-native analogue compresses the TRANSFER, not the compute: embedx
# columns cross host<->device as bfloat16 (counters/w/optimizer state stay
# f32 — counters above 2^8 would round), and the device table is f32
# everywhere the step touches it. Each pass boundary rounds embedx to 8
# mantissa bits — the same concession the reference's int16 quant makes,
# gentler. Opt-in.
# ---------------------------------------------------------------------------

def _split_cols(cfg: EmbeddingConfig):
    e = cfg.embedx_cols
    return e.start, e.stop


def transfer_bytes(cfg: EmbeddingConfig, n_rows: int) -> int:
    """Host<->device bytes for `n_rows` full rows under the current
    storage/compression settings (quantized embedx crosses as int8/16;
    the bf16 transfer-compression flag halves embedx for f32 tables)."""
    if cfg.storage != "f32":
        qbytes = 1 if cfg.storage == "int8" else 2
        return n_rows * (4 * quant.fp_width(cfg) + qbytes * cfg.total_dim)
    if flags.transfer_compress_embedx and cfg.total_dim:
        lo, hi = _split_cols(cfg)
        return n_rows * (4 * (cfg.row_width - (hi - lo)) + 2 * (hi - lo))
    return n_rows * cfg.row_width * 4


def device_width(cfg: EmbeddingConfig) -> int:
    """Physical column count of the f32 device table (flags.table_pad_width).

    TPU random-row gathers are ~2x faster from 64/128-column sources (see
    the flag's comment for measurements); the pad columns are zeros that
    never cross host<->device — every host-bound path slices to
    cfg.row_width on device first. Quantized tables keep their own plane
    layout."""
    rw = cfg.row_width
    pad = flags.table_pad_width
    if not pad or cfg.storage != "f32":
        return rw
    if pad == "auto":
        # width-aware: only the pathological gather zone pads (v5e
        # 852k-row sweep: 14..63-lane gathers run 3-8x slower per row —
        # 24.0ms at 38 lanes vs 5.1ms gathering 64-wide and slicing;
        # <=13-lane and >=64-lane sources are already on the fast path,
        # and round 2 measured the dim-8 full step SLOWER padded). The
        # zone starts at 14, where the sweep's slowdown begins — not 16
        # (ADVICE r5: widths 14-15, e.g. dim 9-10, were stranded on the
        # slow path).
        return 64 if 14 <= rw < 64 else rw
    return max(rw, int(pad))


def plane_layout(cfg: EmbeddingConfig) -> bool:
    """Whether the device table is two planes (quant.PlaneTable) instead
    of one (rows, device_width) array — THE layout rule, from the
    embedding's shape alone.

    Quantized storage always is (the embedx plane is the quantized part).
    f32 storage is where embedx(+expand) is a whole number of 128-lane
    tiles (128, 256, ... — a CTR embedding's 128 or a token embedding's
    2560 alike): the chip stores such an f32 plane row-major with no
    padding, so its rows can be gathered and written in place, and the
    push touches only the rows a step changed (sharded.push). Every
    other width keeps the one array. The two flags that assume one
    array switch the planes off when set: a table padded by
    flags.table_pad_width is one array by definition, and
    flags.transfer_compress_embedx splits and rejoins that array's
    columns at the boundary."""
    if cfg.storage != "f32":
        return True
    return (cfg.total_dim > 0 and cfg.total_dim % 128 == 0
            and device_width(cfg) == cfg.row_width
            and not flags.transfer_compress_embedx)


@functools.lru_cache(maxsize=8)
def _pad_width_jit(extra: int, sharding):
    @device_scope("boundary")
    def pad(t):
        return jnp.pad(t, ((0, 0), (0, extra)))
    if sharding is not None:
        return jax.jit(pad, out_shardings=sharding)
    return jax.jit(pad)


@functools.lru_cache(maxsize=8)
def _slice_width_jit(rw: int):
    @device_scope("boundary")
    def slice_width(t):
        return t[:, :rw]
    return jax.jit(slice_width)


def bucket_size(x: int) -> int:
    """Round up to ~quarter-power-of-two buckets (4 sizes per octave).

    Pass working sets vary in size every pass; exact sizing would recompile
    the train step (and every pass-boundary kernel) per pass. Bucketing
    bounds the number of distinct compiled shapes to O(log N) while wasting
    at most ~25% rows (zero rows are never indexed — translate only maps to
    1..K — and the per-step table scan cost is bandwidth-linear).

    Counts of 1 to 16 share the bucket 16: a table that every pass nearly
    fills (a vocabulary) retires and admits a handful of rows a pass, and
    each count in 1..16 must not be a compiled shape of its own (callers
    pad by repeating the last row, or by rows nothing indexes)."""
    if x <= 0:
        return 0
    if x <= 16:
        return 16
    p = 1 << (int(x).bit_length() - 1)
    step = p >> 2
    return -(-int(x) // step) * step


def shard_rows(cfg, need: int, n_shards: int, min_rows_per_shard: int,
               bucket_rows: bool = True) -> int:
    """Rows a shard of a pass's table holds for `need` rows in all (the
    null row counted): the one rule of a full build and of an incremental
    boundary, so that a pass set meets the same table shape however its
    table came to be (a first build of another size than the boundaries
    that follow it is a step program and a boundary program compiled
    after the warm-up)."""
    rps = max(min_rows_per_shard, -(-need // n_shards))
    if bucket_rows:
        rps = bucket_size(rps)
    # align shard rows to the super-block the binned-push geometry
    # would target for a table of THIS SHARD's size (the kernel runs
    # per shard on rps rows, so the alignment target is rps, not the
    # global row count) — big tables get big-block divisibility,
    # small ones keep the cheap 4096 alignment; the waste is zero
    # rows that are never indexed. Quantized storage rides the same
    # merge accumulator (binned_merge_acc), so it gets the same
    # alignment — _bp_lanes is the shared source of truth.
    if rps >= 4096:
        from paddlebox_tpu.ops.pallas_kernels import bp_row_alignment
        align = bp_row_alignment(cfg, rps)
        rps = -(-rps // align) * align
    return rps


@functools.lru_cache(maxsize=8)  # bounded: each entry retains its Mesh
def _combine_jit(lo: int, hi: int, sharding):
    @device_scope("boundary")
    def combine(rest, emb):
        return jnp.concatenate(
            [rest[:, :lo], emb.astype(jnp.float32), rest[:, lo:]], axis=1)
    # cached per (cols, sharding) so pass boundaries reuse one executable
    # per table shape instead of recompiling every pass
    if sharding is not None:
        return jax.jit(combine, out_shardings=sharding)
    return jax.jit(combine)


@functools.lru_cache(maxsize=None)
def _split_jit(lo: int, hi: int, rw: int):
    @device_scope("boundary")
    def split(t):
        # t may carry pad columns past rw (device_width) — never ship them
        rest = jnp.concatenate([t[:, :lo], t[:, hi:rw]], axis=1)
        return rest, t[:, lo:hi].astype(jnp.bfloat16)
    return jax.jit(split)


def _put_compressed(host_table: np.ndarray, cfg: EmbeddingConfig, sharding):
    lo, hi = _split_cols(cfg)
    rest = np.concatenate([host_table[:, :lo], host_table[:, hi:]], axis=1)
    emb = host_table[:, lo:hi].astype(jnp.bfloat16.dtype)  # ml_dtypes
    if sharding is not None:
        rest_d = jax.device_put(rest, sharding)
        emb_d = jax.device_put(emb, sharding)
    else:
        rest_d, emb_d = jnp.asarray(rest), jnp.asarray(emb)
    return device_scopes.run(_combine_jit(lo, hi, sharding), rest_d, emb_d)


def _get_compressed(table, cfg: EmbeddingConfig) -> np.ndarray:
    lo, hi = _split_cols(cfg)
    rest_d, emb_d = device_scopes.run(_split_jit(lo, hi, cfg.row_width),
                                      table)
    rest = np.asarray(jax.device_get(rest_d))
    emb = np.asarray(jax.device_get(emb_d)).astype(np.float32)
    out = np.empty((table.shape[0], hi - lo + rest.shape[1]), np.float32)
    out[:, :lo] = rest[:, :lo]
    out[:, lo:hi] = emb
    out[:, hi:] = rest[:, lo:]
    return out


# ---------------------------------------------------------------------------
# Row-subset D2H: ship only a set of rows (the pass delta) instead of the
# whole table — the transfer side of the reference's EndPass-applies-delta
# semantics (box_wrapper.h:423). The gather runs on device; only the
# gathered rows cross device->host.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _gather_rows_jit(compress: bool, lo: int, hi: int, rw: int):
    @device_scope("boundary")
    def gather(table, idx):
        # barrier between gather and slice: the full-row gather is the
        # fast path (see sharded.lookup); the slice drops pad columns so
        # only logical bytes cross D2H
        rows = jax.lax.optimization_barrier(table[idx])[:, :rw]
        if compress:
            rest = jnp.concatenate([rows[:, :lo], rows[:, hi:]], axis=1)
            return rest, rows[:, lo:hi].astype(jnp.bfloat16)
        return rows
    return jax.jit(gather)


@functools.lru_cache(maxsize=2)
def _gather_rows_planes_jit():
    # one dispatch for both planes; paired with a single device_get so a
    # pass-boundary flush pays one D2H round trip, not two serialized ones
    @device_scope("boundary")
    def gather_planes(fp, qx, idx):
        return fp[idx], qx[idx]
    return jax.jit(gather_planes)


def fetch_rows(table: jax.Array, row_idx: np.ndarray,
               cfg: EmbeddingConfig,
               pad_to: int = 0) -> tuple[np.ndarray, int]:
    """Device-side gather of `row_idx` rows, then D2H of just those rows.

    Returns (rows float32 (k, row_width), d2h_bytes). The index vector is
    padded to a size bucket so repeated pass boundaries reuse a handful of
    compiled gathers instead of recompiling per dirty-row count.
    `pad_to`: pad the index vector to at least this many rows, and run
    the gather even for no row (null rows) — for a caller whose count
    differs from one pass boundary to the next by the draw alone (0 rows,
    then a handful; 5,116, then 5,343), so that no boundary meets a
    program the one before it did not compile.
    """
    k = len(row_idx)
    if k == 0 and not pad_to:
        return np.zeros((0, cfg.row_width), np.float32), 0
    k_pad = max(bucket_size(k), int(pad_to))
    idxp = np.zeros(k_pad, np.int32)
    idxp[:k] = row_idx
    if quant.is_planes(table):
        fp_d, qx_d = device_scopes.run(_gather_rows_planes_jit(), table.fp,
                                       table.qx, idxp)
        fp, qx = (np.asarray(a) for a in jax.device_get((fp_d, qx_d)))
        rows = quant.decode_rows_np(fp, qx, cfg)
        return rows[:k], transfer_bytes(cfg, k_pad)
    compress = bool(flags.transfer_compress_embedx and cfg.total_dim)
    lo, hi = _split_cols(cfg)
    out = device_scopes.run(
        _gather_rows_jit(compress, lo, hi, cfg.row_width), table, idxp)
    if compress:
        rest_d, emb_d = out
        rest = np.asarray(jax.device_get(rest_d))
        emb_bf = np.asarray(jax.device_get(emb_d))
        rows = np.empty((k_pad, cfg.row_width), np.float32)
        rows[:, :lo] = rest[:, :lo]
        rows[:, lo:hi] = emb_bf.astype(np.float32)
        rows[:, hi:] = rest[:, lo:]
        return rows[:k], rest.nbytes + emb_bf.nbytes
    rows = np.asarray(jax.device_get(out))
    return rows[:k], rows.nbytes


class PushOperandStager:
    """Double-buffered staging for the deferred sparse-push pipeline
    (flags.push_overlap).

    Two slots rotate: the PENDING slot holds step N's packed push
    operands (staged batch refs + the step's premerged grads/shows/clks)
    until the trainer dispatches the apply program; the RETIRED slot
    keeps step N-1's operands referenced for one more rotation, while
    their apply kernel may still be in flight and step N+1's plan-H2D is
    being dispatched — so the device buffers both overlap windows read
    stay pinned without any per-step host sync.

    The pending slot is also the pipeline's staleness bound: a second
    ``put`` before the pending apply was taken means the table would lag
    by MORE than one unapplied step, and raises instead of queueing —
    the trainer must dispatch the apply for step N before step N+1's
    operands land.
    """

    __slots__ = ("_pending", "_retired", "puts", "applies")

    def __init__(self):
        self._pending = None
        self._retired = None
        self.puts = 0
        self.applies = 0

    def put(self, item) -> None:
        if self._pending is not None:
            raise RuntimeError(
                "deferred push staleness bound exceeded: a second step's "
                "operands were queued while one apply is still pending — "
                "dispatch the pending apply first (one-step bound)")
        self._pending = item
        self.puts += 1

    def take(self):
        """Pop the pending operands (None if none). The popped item moves
        to the retired slot — its buffers stay referenced for one more
        rotation while the apply that consumes them is in flight."""
        item, self._pending = self._pending, None
        if item is not None:
            self._retired = item
            self.applies += 1
        return item

    def pending(self) -> int:
        return int(self._pending is not None)

    def live(self) -> int:
        """Slots currently pinning device buffers (<= 2 by construction
        — the leak check the deferred pipeline's tests assert on)."""
        return (int(self._pending is not None)
                + int(self._retired is not None))

    def clear(self) -> None:
        self._pending = None
        self._retired = None


class PassWorkingSet:
    def __init__(self, cfg: EmbeddingConfig, sorted_keys: np.ndarray,
                 table: jax.Array, rows_per_shard: int, n_shards: int):
        self.cfg = cfg
        self.sorted_keys = sorted_keys      # uint64 (K,), ascending
        self.table = table                  # (N_pad, row_width) sharded:
        #                                     one array or quant.PlaneTable
        self.rows_per_shard = rows_per_shard
        self.n_shards = n_shards
        # hash index over the pass keys: per-batch translate becomes one
        # native batch probe (~6x faster than searchsorted at CTR batch
        # sizes); ids follow sorted order so row mapping is unchanged
        self._tindex = KeyIndex(len(sorted_keys) or 1)
        self._tindex.rebuild(sorted_keys)
        # host-side dirty-row mask: translate() records every row a batch
        # referenced, so end_pass can ship only the pass delta D2H (the
        # device never modifies a row that no batch indexed — push
        # guarantees untouched rows keep their exact bits)
        self.touched = np.zeros(self.padded_rows, dtype=bool)

    @property
    def num_keys(self) -> int:
        return len(self.sorted_keys)

    @property
    def padded_rows(self) -> int:
        return self.rows_per_shard * self.n_shards

    def shard_of(self, idx: np.ndarray) -> np.ndarray:
        """Owner mesh shard per working-set index (the contiguous
        partition the exchange routes by: row i lives on shard
        i // rows_per_shard). Host-side twin of the routing rule inside
        ``sharded._route`` — the capacity preplan histograms off it."""
        return np.asarray(idx) // self.rows_per_shard

    # ---- lifecycle ----

    @classmethod
    def begin_pass(cls, store: HostEmbeddingStore, keys: np.ndarray,
                   mesh: jax.sharding.Mesh | None = None,
                   min_rows_per_shard: int = 8,
                   test_mode: bool = False,
                   bucket_rows: bool = False,
                   timing_out: dict | None = None) -> "PassWorkingSet":
        """Build the pass working set on device (BeginFeedPass/EndFeedPass).

        test_mode=True reads rows without inserting unseen keys into the
        store (eval passes must not grow or dirty it). bucket_rows=True
        rounds the per-shard row count up to a size bucket so consecutive
        passes of similar size share compiled step shapes. ``timing_out``
        (mutated in place) receives the boundary split the flight record
        carries: ``build`` = host-side key dedup + store fetch + table
        assembly seconds, ``h2d`` = device transfer (+ on-device pad)
        seconds — the critical-path attributor needs the two apart.
        """
        import time as _time
        cfg = store.cfg
        t0 = _time.perf_counter()
        keys = np.unique(np.asarray(keys).astype(np.uint64))
        if flags.spill_prefetch:
            # madvise(WILLNEED)-style readahead of the disk-tier rows
            # about to fault in (spill-backed stores only): the kernel
            # pages them in while the fetch below assembles the table,
            # instead of serializing the fault-in inside it
            prefetch = getattr(store, "prefetch_rows", None)
            if prefetch is not None:
                prefetch(keys)
        rows = (store.peek_rows(keys) if test_mode
                else store.lookup_or_init(keys))
        n_shards = mesh_lib.num_shards(mesh) if mesh is not None else 1
        need = len(keys) + 1                       # +1 for the null row
        rps = shard_rows(cfg, need, n_shards, min_rows_per_shard,
                         bucket_rows)
        n_pad = rps * n_shards
        host_table = np.zeros((n_pad, cfg.row_width), dtype=np.float32)
        host_table[1:1 + len(keys)] = rows
        t1 = _time.perf_counter()
        sharding = (mesh_lib.table_sharding(mesh) if mesh is not None
                    else None)
        if cfg.storage != "f32" and flags.transfer_compress_embedx:
            raise ValueError(
                "transfer_compress_embedx is redundant with quantized "
                "storage — the embedx plane already crosses as "
                f"{cfg.storage}")
        if plane_layout(cfg):
            table = quant.device_planes(host_table, cfg, sharding)
        elif flags.transfer_compress_embedx and cfg.total_dim:
            table = _put_compressed(host_table, cfg, sharding)
        elif sharding is not None:
            table = jax.device_put(host_table, sharding)
        else:
            table = jnp.asarray(host_table)
        # pad f32 tables to the fast gather width ON DEVICE — the H2D
        # above carried logical bytes only (see device_width)
        W = device_width(cfg)
        if W > cfg.row_width:
            table = device_scopes.run(
                _pad_width_jit(W - cfg.row_width, sharding), table)
        if timing_out is not None:
            # device_put returns before bytes move; without this barrier
            # the h2d component would read near-zero and the transfer
            # would land silently in the caller's sync (the same trap
            # _account_begin's D2H sync exists for)
            jax.block_until_ready(table)
            t2 = _time.perf_counter()
            timing_out["build"] = timing_out.get("build", 0.0) + (t1 - t0)
            timing_out["h2d"] = timing_out.get("h2d", 0.0) + (t2 - t1)
        return cls(cfg, keys, table, rps, n_shards)

    def translate(self, ids: np.ndarray, mask: np.ndarray | None = None
                  ) -> np.ndarray:
        """uint64 feature signs → dense int32 working-set indices.

        Unknown keys (not in this pass) and masked positions map to the null
        index 0. Vectorized host-side; this is the key→index hop that keeps
        64-bit keys out of jit entirely.
        """
        ids_arr = np.asarray(ids)
        if len(self.sorted_keys) == 0:
            idx = np.zeros(ids_arr.shape, dtype=np.int32)
            return idx
        flat = ids_arr.astype(np.uint64).reshape(-1)
        if self._tindex.is_native:
            pos = self._tindex.lookup(flat)  # -1 = not in this pass
        else:
            # dict-backed KeyIndex would loop per key; the keys are already
            # sorted, so a vectorized searchsorted is the fast host path
            pos = np.searchsorted(self.sorted_keys, flat)
            pos[pos >= len(self.sorted_keys)] = 0
            pos = np.where(self.sorted_keys[pos] == flat, pos, -1)
        idx = (pos + 1).astype(np.int32).reshape(ids_arr.shape)
        if mask is not None:
            idx = np.where(mask, idx, 0).astype(np.int32)
        # record the pass delta: every row this batch will pull/push
        self.touched[idx.reshape(-1)] = True
        self.touched[0] = False          # null row is never persisted
        return idx

    def end_pass(self, store: HostEmbeddingStore,
                 table: jax.Array | None = None,
                 only_touched: bool | None = None) -> int:
        """Persist the (possibly updated) device table back to the host store.

        only_touched=None (default) ships just the rows translate() recorded
        when any were recorded — the incremental EndPass (box_wrapper.h:423:
        only the pass delta moves) — and falls back to a full write-back for
        working sets that never went through translate (direct-table tests).
        Returns the number of bytes moved D2H.
        """
        t = table if table is not None else self.table
        use_touched = (self.touched.any() if only_touched is None
                       else only_touched)
        if use_touched:
            dirty = np.flatnonzero(self.touched[1:1 + self.num_keys]) + 1
            rows, nbytes = fetch_rows(t, dirty, self.cfg)
            store.write_back(self.sorted_keys[dirty - 1], rows)
            return nbytes
        if quant.is_planes(t):
            host = quant.decode_rows_np(
                np.asarray(jax.device_get(t.fp)),
                np.asarray(jax.device_get(t.qx)), self.cfg)
            n_rows = t.fp.shape[0]
        elif flags.transfer_compress_embedx and self.cfg.total_dim:
            host = _get_compressed(t, self.cfg)
            n_rows = t.shape[0]
        else:
            if t.shape[1] > self.cfg.row_width:   # drop pad columns first
                t = device_scopes.run(
                    _slice_width_jit(self.cfg.row_width), t)
            host = np.asarray(jax.device_get(t))
            n_rows = t.shape[0]
        nbytes = transfer_bytes(self.cfg, n_rows)
        store.write_back(self.sorted_keys, host[1:1 + self.num_keys])
        return nbytes

    # convenience for single-host training loops
    def update_table(self, table: jax.Array) -> None:
        self.table = table
