"""Pass-scoped in-memory dataset — the PadBoxSlotDataset equivalent.

Reference (data_set.{h,cc}; class at data_set.h:348-474): a pass's worth of
``SlotRecord``s is downloaded+parsed by a thread pool, globally shuffled
across nodes, merged, key-extracted into the parameter server's feed-pass
agent, then sliced into per-device batch ranges for the trainers
(``PrepareTrain``). ``PreLoadIntoMemory``/``WaitPreLoadDone`` overlap the next
pass's ingest with the current pass's training (data_set.cc:1712-1786).

TPU-native changes: records are columnar (``SlotRecordBatch``), shuffle rides
host TCP over DCN (``shuffle.py``), and "key extraction into the PS agent"
becomes handing the pass's unique keys to the embedding engine's
``begin_pass`` working-set builder (see embedding/store.py).
"""

from __future__ import annotations

import concurrent.futures
import threading
from typing import Iterator, Sequence

import numpy as np

from paddlebox_tpu.config import flags
from paddlebox_tpu.data.reader import ParserPlugin, read_file
from paddlebox_tpu.data.schema import DataFeedSchema
from paddlebox_tpu.data.slot_record import PackedBatch, SlotRecordBatch, batch_iterator
from paddlebox_tpu.data.shuffle import LocalShuffler, RoutingMode, TcpShuffleService, route_records
from paddlebox_tpu.monitor import counter_add as stat_add
from paddlebox_tpu.monitor import span as mon_span
from paddlebox_tpu.native.key_index import merge_sorted_runs


class SlotDataset:
    """One pass of training data, held columnar in host memory."""

    def __init__(self, schema: DataFeedSchema,
                 shuffle_service: TcpShuffleService | None = None,
                 seed: int = 0):
        self.schema = schema
        self.filelist: list[str] = []
        self.pipe_command: str | None = None
        self.parser_plugin: ParserPlugin | None = None
        self.with_ins_id = False
        self.records = None
        self.date: int | None = None
        self._preload: concurrent.futures.Future | None = None
        self._pool = None
        self._shuffler = LocalShuffler(seed)
        self._service = shuffle_service
        self._lock = threading.Lock()
        # the records' key set beside the version of the records it was
        # built for (unique_keys): (version, ascending distinct int64)
        self._key_set: tuple[int, np.ndarray] | None = None
        # per-device slices set by prepare_train
        self._shards: list[SlotRecordBatch] = []

    # every rebind of the record batch bumps a version counter so pass-
    # level caches keyed on dataset content (Trainer._preplan_capacity's
    # capacity memo) invalidate when records are swapped behind an
    # unchanged num_examples (ADVICE r4; auc_runner ablation rebinds); the
    # key set kept by unique_keys is one of them
    @property
    def records(self) -> SlotRecordBatch | None:
        return self._records

    @records.setter
    def records(self, value: SlotRecordBatch | None) -> None:
        self._records = value
        self._records_version = getattr(self, "_records_version", 0) + 1

    # ---- configuration (BoxPSDataset python API, dataset.py:1081-1191) ----

    def set_filelist(self, files: Sequence[str]) -> None:
        self.filelist = list(files)

    def set_pipe_command(self, cmd: str | None) -> None:
        self.pipe_command = cmd

    def set_parser_plugin(self, plugin: ParserPlugin | None) -> None:
        self.parser_plugin = plugin

    def set_date(self, date: int) -> None:
        """Reference BoxPSDataset.set_date (dataset.py:1101)."""
        self.date = date

    # ---- ingest (LoadIntoMemory, data_set.cc:1780) ----

    @mon_span("ingest")
    def load_into_memory(self, global_shuffle: bool = True,
                         routing: RoutingMode = "random") -> None:
        n_threads = min(flags.dataset_load_thread_num, max(1, len(self.filelist)))
        unroll = getattr(self.parser_plugin, "unroll", None)
        # the files' key runs are the records' only while the records are
        # the files' rows: a local shuffle permutes them, an exchange with
        # other ranks or an unroll makes other records (no runs taken, and
        # unique_keys builds its own from what is bound)
        keyed = unroll is None and not (
            global_shuffle and self._service is not None)
        with concurrent.futures.ThreadPoolExecutor(n_threads) as pool:
            loaded = list(pool.map(
                lambda path: self._read_one(path, keyed), self.filelist))
            keys = self._merge_key_runs([run for _, run in loaded], pool) \
                if keyed else None
        parts = [p for p, _ in loaded if p.num > 0]
        del loaded
        batch = (SlotRecordBatch.concat(parts) if parts
                 else SlotRecordBatch.empty(self.schema))
        if global_shuffle and batch.num > 0:
            batch = self._global_shuffle(batch, routing)
        # UnrollInstance hook (data_set.cc:2356, data_feed.cc:3304): like
        # the reference, unrolling is plugin-defined — a parser plugin may
        # carry an `unroll(SlotRecordBatch) -> SlotRecordBatch` attribute
        # (e.g. expanding PV-merged page views back into instances) applied
        # once after load/shuffle.
        if unroll is not None and batch.num > 0:
            batch = unroll(batch)
        # STAT_ADD counters, like data_feed's feasign stats (monitor.h:129)
        stat_add("dataset.records_loaded", batch.num)
        stat_add("dataset.feasigns_loaded",
                 float(sum(len(v) for v in batch.sparse_values)))
        with self._lock:
            self.records = batch
            if keys is not None:
                self._key_set = (self._records_version, keys)

    def preload_into_memory(self, **kw) -> None:
        """Overlap next pass ingest with training (PreLoadIntoMemory,
        data_set.cc:1712)."""
        ex = concurrent.futures.ThreadPoolExecutor(1)
        self._preload = ex.submit(self.load_into_memory, **kw)
        ex.shutdown(wait=False)

    def wait_preload_done(self) -> None:
        if self._preload is not None:
            self._preload.result()
            self._preload = None

    def _read_one(self, path: str, keyed: bool
                  ) -> tuple[SlotRecordBatch, np.ndarray | None]:
        """One file's records and, if `keyed`, its key run (its ascending
        distinct keys: numpy's sort holds no GIL) — both on the loader
        thread that read it."""
        part = read_file(path, self.schema, pipe_command=self.pipe_command,
                         parser_plugin=self.parser_plugin,
                         with_ins_id=self.with_ins_id)
        return part, part.unique_keys() if keyed else None

    @staticmethod
    def _merge_key_runs(runs: Sequence[np.ndarray],
                        pool: concurrent.futures.Executor) -> np.ndarray:
        """The key set of the records the sorted `runs` cover: one merge
        (native/key_index.py: a pairwise tree, a round's pairs side by side
        on the pool that made the runs), whatever they were taken from."""
        with mon_span("ingest/key_merge"):
            keys = merge_sorted_runs(runs, pool.map)
        stat_add("dataset.key_runs", len(runs))
        keys.flags.writeable = False    # one array answers every call
        return keys

    def _global_shuffle(self, batch: SlotRecordBatch,
                        routing: RoutingMode) -> SlotRecordBatch:
        if self._service is None:
            return self._shuffler.shuffle(batch, routing)
        # random-mode routing draws from the PERSISTENT shuffle generator
        # so shuffle_state() checkpoints the routing decisions too (a
        # mid-pass resume replays identical destinations)
        routed = route_records(batch, self._service.world, routing,
                               rng=self._shuffler.rng)
        received = self._service.exchange(routed, self.schema)
        merged = (SlotRecordBatch.concat(received) if received
                  else SlotRecordBatch.empty(self.schema))
        return self._shuffler.shuffle(merged) if merged.num else merged

    # ---- in-memory transforms ----

    def local_shuffle(self) -> None:
        if self.records is not None and self.records.num:
            held = self._held_key_set()
            self.records = self._shuffler.shuffle(self.records)
            if held is not None:    # the same rows in another order
                self._key_set = (self._records_version, held)

    # ---- crash-recovery shuffle cursor (distributed/resilience.py) ----

    def shuffle_state(self) -> dict:
        """The shuffle RNG cursor: JSON-serializable bit-generator state.
        Recorded into pass snapshots (PassCheckpointer cursor) so a
        resumed rank replays the identical per-pass permutations — the
        state BEFORE a pass's draw reproduces that pass's order, the
        state after it produces the next pass's."""
        return self._shuffler.state_dict()

    def set_shuffle_state(self, state: dict) -> None:
        self._shuffler.load_state_dict(state)

    # ---- elastic world shrink (distributed/resilience.py, ISSUE 6) ----

    def member_shards(self, world_size: int) -> list[SlotRecordBatch]:
        """Deterministic per-member slices of the current records — the
        same round-robin split :meth:`prepare_train` uses, returned
        instead of stored. Every rank computes the identical partition
        from the identically-shuffled records, so after a rank loss the
        survivors know exactly which records the departed rank owned
        without ever having talked to it."""
        assert self.records is not None
        n = self.records.num
        return [self.records.select(np.arange(d, n, world_size))
                for d in range(world_size)]

    def reroute_records(self, batch: SlotRecordBatch, world_size: int
                        ) -> list[SlotRecordBatch | None]:
        """Cursor-preserving re-route of ``batch`` across ``world_size``
        survivors, drawing destinations from THE persistent shuffle
        generator (:meth:`shuffle_state`'s cursor). See
        :func:`paddlebox_tpu.data.shuffle.elastic_reroute` for the
        lockstep contract."""
        from paddlebox_tpu.data.shuffle import elastic_reroute
        return elastic_reroute(batch, world_size, self._shuffler.rng)

    def slots_shuffle(self, slot_names: Sequence[str], seed: int = 0) -> None:
        """Shuffle the values of the given sparse slots *across examples*
        (reference BoxPSDataset.slots_shuffle, dataset.py:1191 — used for
        feature-ablation evaluation)."""
        if self.records is None or self.records.num == 0:
            return
        rng = np.random.default_rng(seed)
        rec = self.records
        sparse_names = [s.name for s in self.schema.sparse_slots]
        # resolve every name BEFORE mutating: an unknown slot must not
        # leave records half-shuffled with no version bump below
        slot_idx = [sparse_names.index(name) for name in slot_names]
        for s in slot_idx:
            vals, offs = rec.sparse_values[s], rec.sparse_offsets[s]
            lens = offs[1:] - offs[:-1]
            # permute whole per-example value LISTS across examples (the
            # reference swaps slot value vectors between instances,
            # data_set.cc slots_shuffle) — example i receives example
            # perm[i]'s entire list, keeping multi-value lists intact
            perm = rng.permutation(rec.num)
            new_lens = lens[perm]
            new_offs = np.zeros(rec.num + 1, dtype=np.int64)
            np.cumsum(new_lens, out=new_offs[1:])
            total = int(new_offs[-1])
            # vectorized ragged gather: output position t inside example j
            # reads vals[offs[perm[j]] + (t - new_offs[j])]
            src_start = np.repeat(offs[:-1][perm], new_lens)
            local = np.arange(total, dtype=np.int64) - \
                np.repeat(new_offs[:-1], new_lens)
            rec.sparse_values[s] = vals[src_start + local]
            rec.sparse_offsets[s] = new_offs
        # in-place mutation changes per-example routing: pass-level caches
        # keyed on content (capacity-preplan memo) must invalidate
        self._records_version = getattr(self, "_records_version", 0) + 1

    def merge_by_ins_id(self, merge_size: int = 0) -> int:
        """Merge examples sharing an ins_id into one (MergeByInsId,
        reference data_set.cc:1012): sort by ins_id, group, and concatenate
        each group's sparse slot values member-by-member. With
        ``merge_size > 0``, groups whose size differs are DROPPED (the
        reference's strict mode — e.g. exactly one click log + one show
        log per instance). Float slots and metadata come from the group's
        first member. Returns the number of dropped examples."""
        assert self.records is not None
        r = self.records
        if r.num == 0:
            return 0
        if not r.ins_id.any():
            raise ValueError(
                "merge_by_ins_id needs real instance ids; load with "
                "with_ins_id=True (all ins_id are 0 — merging would "
                "collapse the whole dataset into one group)")
        order = np.argsort(r.ins_id, kind="stable")
        ids = r.ins_id[order]
        starts = np.flatnonzero(
            np.concatenate([[True], ids[1:] != ids[:-1]]))
        sizes = np.diff(np.append(starts, len(ids)))
        keep = (sizes == merge_size) if merge_size > 0 \
            else np.ones(len(starts), bool)
        dropped = int(sizes[~keep].sum())
        kept_groups = [(starts[g], sizes[g]) for g in np.flatnonzero(keep)]
        if not kept_groups:
            self.records = SlotRecordBatch.empty(self.schema)
            stat_add("dataset.merge_by_ins_id_dropped", dropped)
            return dropped
        # one ragged gather via select(), then collapse offsets at group
        # boundaries (offsets are cumulative, so the group's span is just
        # the offsets sampled at member boundaries)
        member_rows = np.concatenate(
            [order[st:st + sz] for st, sz in kept_groups])
        picked = r.select(member_rows)
        bounds = np.cumsum([0] + [sz for _, sz in kept_groups])
        firsts = r.select(np.asarray([order[st] for st, _ in kept_groups]))
        self.records = SlotRecordBatch(
            schema=r.schema, num=len(kept_groups),
            sparse_values=picked.sparse_values,
            sparse_offsets=[off[bounds] for off in picked.sparse_offsets],
            float_values=firsts.float_values,
            ins_id=firsts.ins_id, search_id=firsts.search_id,
            rank=firsts.rank, cmatch=firsts.cmatch)
        stat_add("dataset.merge_by_ins_id_dropped", dropped)
        return dropped

    def merge_by_search_id(self) -> np.ndarray:
        """Group examples into page views (PV merge, reference MergePvInstance):
        returns group ids per example ordered so same-search_id examples are
        adjacent; used to build rank_offset for rank_attention."""
        assert self.records is not None
        order = np.argsort(self.records.search_id, kind="stable")
        self.records = self.records.select(order)
        _, group = np.unique(self.records.search_id, return_inverse=True)
        return group

    # ---- hand-off to embedding engine + trainers ----

    def unique_keys(self) -> np.ndarray:
        """The pass's feature-sign working set (MergeInsKeys → PSAgent,
        data_set.cc:1786)."""
        assert self.records is not None
        keys = self._held_key_set()
        if keys is not None:
            stat_add("dataset.key_set_reused")
            return keys
        # the records are not what a load left (rebound, or changed in
        # place): the same routine, one run a sparse column
        stat_add("dataset.key_set_rebuilt")
        version, columns = self._records_version, self.records.sparse_values
        n_threads = min(flags.dataset_load_thread_num, max(1, len(columns)))
        with concurrent.futures.ThreadPoolExecutor(n_threads) as pool:
            runs = list(pool.map(np.unique, columns))
            keys = self._merge_key_runs(runs, pool)
        self._key_set = (version, keys)
        return keys

    def _held_key_set(self) -> np.ndarray | None:
        """The key set, if it was built for the records as they are."""
        held = self._key_set
        if held is not None and held[0] == self._records_version:
            return held[1]
        return None

    def prepare_train(self, num_shards: int) -> None:
        """Slice records round-robin into per-device shards
        (PadBoxSlotDataset::PrepareTrain, data_set.h:376)."""
        assert self.records is not None
        n = self.records.num
        self._shards = [
            self.records.select(np.arange(d, n, num_shards))
            for d in range(num_shards)
        ]

    def shard_batches(self, shard: int, batch_size: int | None = None,
                      drop_last: bool = True) -> Iterator[PackedBatch]:
        bs = batch_size or self.schema.batch_size
        return batch_iterator(self._shards[shard], bs, drop_last=drop_last)

    def batches(self, batch_size: int | None = None,
                drop_last: bool = True) -> Iterator[PackedBatch]:
        assert self.records is not None
        bs = batch_size or self.schema.batch_size
        return batch_iterator(self.records, bs, drop_last=drop_last)

    @property
    def num_examples(self) -> int:
        return 0 if self.records is None else self.records.num

    def release_memory(self) -> None:
        self.records = None
        self._key_set = None
        self._shards = []
