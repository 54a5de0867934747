"""The system under test: built from a configuration file, driven as the
user's day loop.

Everything the benchmark touches of ``paddlebox_tpu`` is in this file:
the public entry points of the loop (``SlotDataset``, ``BoxPS``,
``Trainer.train_pass``), the counters the per-layer metrics read
(``Trainer.timers``, ``feed_mgr.last_*``), ``Trainer.engines()`` and
``Trainer.block_until_ready()``, the expert layer's ``route_rungs`` (the
shapes a trace shows its route by), and — for the comparison that decides
``correct`` — the trainer's own mid-pass snapshot hook, in whose place
``StepProbe`` stands.
"""

from __future__ import annotations

import time

import numpy as np


def span(name: str):
    """A harness span, as an annotation on the profiler's clock so that a
    trace can label the device's idle gaps by what the host was doing."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench/{name}")


class StepProbe:
    """Reads the trainer's state after chosen steps of a pass.

    It stands where a pass checkpointer stands in
    ``Trainer.enable_midpass_snapshots``: the trainer calls ``save`` at a
    step boundary with its live dense state, after landing the pending
    push, and the probe reads the rows of `keys` through the store's own
    ``get_rows``. After the last wanted step it switches the hook off, so
    the rest of the pass and every later pass run undisturbed."""

    def __init__(self, keys: np.ndarray, steps: tuple[int, ...]):
        self.keys = keys
        self.steps = tuple(steps)
        self.after: dict[int, dict] = {}

    def attach(self, trainer, box) -> None:
        trainer.enable_midpass_snapshots(self, 1, box=box)

    def save(self, trainer, *, mid_steps: int, dense_override, **_kw) -> str:
        import jax
        if mid_steps in self.steps:
            params, opt_state = jax.device_get(dense_override)
            self.after[mid_steps] = {
                "params": params, "m": adam_first_moment(opt_state),
                "rows": trainer.store.get_rows(self.keys)}
        if mid_steps >= max(self.steps):
            trainer.enable_midpass_snapshots(None, 0)
        return ""


def adam_first_moment(opt_state):
    """Adam's first moment out of the dense optimizer's state (optax:
    the state that has ``mu``)."""
    for part in opt_state:
        if hasattr(part, "mu"):
            return part.mu
    raise ValueError("the dense optimizer's state holds no first moment")


def route_rungs(rows: int, held: int, n_experts: int) -> tuple[int, ...]:
    """The static row bounds the program may give the sorted copy of a
    chunk of `rows` (token, choice) assignments where `held` of
    `n_experts` experts are this chip's; the whole chunk alone in a
    program whose expert layer has no such ladder."""
    from paddlebox_tpu.parallel import expert
    rungs = getattr(expert, "route_rungs", None)
    return tuple(rungs(rows, held, n_experts)) if rungs else (rows,)


def build_schema(cfg: dict, hotness: np.ndarray):
    """The configuration's slot list as the program's schema. What a
    slot's entry holds under ``args`` reaches ``Slot`` as keywords, so an
    attribute the program's slots gain needs no edit here."""
    from paddlebox_tpu.data import DataFeedSchema
    from paddlebox_tpu.data.schema import Slot, SlotType
    slots, s = [], 0
    for spec in cfg["slots"]:
        more = spec.get("args", {})
        if spec["kind"] == "sparse":
            slots.append(Slot(spec["name"], SlotType.UINT64,
                              max_len=int(hotness[s]), **more))
            s += 1
        else:
            slots.append(Slot(spec["name"], SlotType.FLOAT,
                              max_len=int(spec.get("max_len", 1)), **more))
    return DataFeedSchema(slots,
                          batch_size=cfg["trainer"]["global_batch_size"])


class System:
    """Trainer, host store and BoxPS, built once and living across passes
    (so the incremental boundary reuses resident rows, as in production)."""

    def __init__(self, cfg: dict, hotness: np.ndarray, seed: int,
                 dense_params=None, n_devices: int = 1):
        from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
        from paddlebox_tpu.fleet import BoxPS
        from paddlebox_tpu.models import MODEL_REGISTRY
        from paddlebox_tpu.parallel import make_mesh
        from paddlebox_tpu.train import Trainer, TrainerConfig

        self.cfg = cfg
        self.schema = build_schema(cfg, hotness)
        self.store = HostEmbeddingStore(
            EmbeddingConfig(**cfg["embedding"], seed=int(seed)))
        args = {k: tuple(v) if isinstance(v, list) else v
                for k, v in cfg["model_args"].items()}
        model = MODEL_REGISTRY[cfg["model"]](**args)
        self.trainer = Trainer(model, self.store, self.schema,
                               make_mesh(n_devices),
                               TrainerConfig(**cfg["trainer"]),
                               seed=int(seed) % (1 << 31))
        if dense_params is not None:
            # the benchmark's own weights, the same the reference starts from
            self.trainer.restore_dense(dense_params)
        self.box = BoxPS(self.store)
        self.box.set_date(20260929)
        self.passes: list[dict] = []
        self._preloaded = None

    # -- the day loop ------------------------------------------------------

    def _dataset(self, files):
        from paddlebox_tpu.data import SlotDataset
        ds = SlotDataset(self.schema)
        ds.set_filelist(files)
        return ds

    def run_pass(self, files, next_files=None, keep_batches: int = 0) -> dict:
        """One pass cycle: load -> begin_pass -> train_pass -> end_pass.
        With `next_files` the next pass's files load in the background
        while this pass trains (``overlap_load``). `keep_batches` keeps
        what the parser and packer delivered for the first batches."""
        tr = self.trainer
        t0 = time.perf_counter()
        timers0 = dict(tr.timers.total)
        with span("load"):
            if self._preloaded is not None and \
                    self._preloaded.filelist == list(files):
                ds = self._preloaded
                ds.wait_preload_done()
            else:
                ds = self._dataset(files)
                ds.load_into_memory(global_shuffle=False)
            self._preloaded = None
        t_loaded = time.perf_counter()
        kept = []
        for pb in ds.batches(self.schema.batch_size) if keep_batches else ():
            if len(kept) == keep_batches:
                break
            kept.append({"ids": pb.ids.copy(), "mask": pb.mask.copy(),
                         "floats": pb.floats.copy()})
        if next_files is not None:
            self._preloaded = self._dataset(next_files)
            self._preloaded.preload_into_memory(global_shuffle=False)
        with span("begin_pass"):
            self.box.begin_pass()
        with span("train_pass"):
            stats = tr.train_pass(ds, metrics=self.box.metrics)
        with span("end_pass"):
            self.box.end_pass(trainer=tr)
        t1 = time.perf_counter()
        fm = tr.feed_mgr
        rec = {
            "t0": t0, "t1": t1, "seconds": t1 - t0,
            "ingest_s": t_loaded - t0,
            "examples": int(ds.num_examples), "steps": int(stats["steps"]),
            "losses": stats["losses"], "first_batches": kept,
            "routed_dropped": int(stats["routed_dropped"]),
            "boundary_s": float(fm.last_boundary_seconds),
            "boundary_split": {k: float(v) for k, v
                               in fm.last_boundary_split.items()},
            "boundary_h2d_bytes": int(fm.last_h2d_bytes),
            "boundary_d2h_bytes": int(fm.last_d2h_bytes),
            "fresh_rows": int(fm.last_fresh_rows),
            "reused_rows": int(fm.last_reused_rows),
            "timers": {k: tr.timers.total.get(k, 0.0) - timers0.get(k, 0.0)
                       for k in tr.timers.total},
        }
        ds.release_memory()
        self.passes.append(rec)
        return rec

    def block(self) -> None:
        """Wait for everything the loop has dispatched: the table (the
        last deferred apply lands after the last loss is read) and the
        dense state."""
        self.trainer.block_until_ready()

    def read_rows(self, keys: np.ndarray) -> np.ndarray:
        """The rows of `keys` as a user reads them between passes: the
        store's ``get_rows``, which first lands what the device still
        holds unsynced."""
        return self.store.get_rows(keys)

    def engines(self) -> dict:
        """What the resolvers picked for the pass that just ran, and the
        table's shape: the logical one and each array the device holds."""
        return {**self.trainer.engines(), "store_keys": len(self.store)}

    def free(self) -> None:
        """Let go of the program's state, the device table with it (before
        the reference runs). Nothing is written back: the run is over."""
        self.trainer = self.box = self.store = self._preloaded = None
