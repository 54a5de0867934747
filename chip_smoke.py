"""The quickest proof that the training main path still starts on the chip.

Drives slot files -> ``SlotDataset`` -> ``BoxPS.begin_pass`` ->
``Trainer.train_pass`` -> ``end_pass`` -> ``eval_pass`` once, in one
process, at the full width of a Criteo-shaped DeepFM (26 sparse slots +
13 dense, emb dim 16, hidden (400, 400, 400), batch 8192, adagrad,
allreduce dense sync; ``FULL`` below), with default flags — the point
is to see what ``auto`` picks on a chip — and checks what comes out.

    python chip_smoke.py              one chip: phases train, multihot, kernels
    python chip_smoke.py --chips 4    four chips: phase mesh4 and its one-chip
                                      comparison, no other phase
    python chip_smoke.py --rehearse   tiny sizes on whatever backend JAX has
                                      (CPU rehearsal of the control flow);
                                      never prints the ok line

Every line on stdout is one JSON object. Wall and compile seconds are
smoke facts, not metrics. Any failed check or exception exits non-zero
before the last line, which on success is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The script fails at once when JAX finds no TPU and never chooses a
platform itself. Data is generated from ``--seed`` into a directory that is
removed on the way out.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

NUM_SLOTS, DENSE_DIM = 26, 13
# the full sizes: the DeepFM's batch, ids a slot and tower, and the
# multi-hot phase's layout (2^19-key pool, 4-hot, dim 32)
FULL = dict(batch=8192, steps=8, slot_space=650_000, hidden=(400, 400, 400),
            mh_steps=3, mh_keys=1 << 19, min_keys=1_000_000, files=4)
TINY = dict(batch=512, steps=6, slot_space=4000, hidden=(32, 32),
            mh_steps=3, mh_keys=1 << 12, min_keys=0, files=2)
# per-step loss agreement of the four-chip run with the one-chip run on
# the same batches: the bf16 push wire rounds each routed grad to 8
# mantissa bits (relative 2^-9) before the owner shard sums it, the f32
# wire only reorders f32 sums. Seen on four v5e chips (PR 21): 3.2e-5
# and 3.1e-5 — both sit on the floor the engines share (per-device
# means, the 2-plane binned push), so the f32 bound is the tighter one
# by decision, not by a margin the run can show.
TOL_BF16_WIRE = 1e-3
TOL_F32_WIRE = 2e-4


class SmokeFailure(Exception):
    pass


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# --------------------------------------------------------------------------
# seeded MultiSlot text files
# --------------------------------------------------------------------------

def _label_weights(seed: int) -> np.ndarray:
    return np.random.default_rng(seed + 7919).normal(size=DENSE_DIM)


def _write_files(root: str, tag: str, labels, dense, slot_cols,
                 n_files: int) -> list[str]:
    """slot_cols: per slot, an (n_ex,) array of ready "<len> id ..."
    strings. One line per example, slots in schema order (label, dense
    floats, sparse ids)."""
    n = len(labels)
    cols = [np.char.add("1 ", labels.astype(np.int64).astype(str))]
    cols += [np.char.add("1 ", np.char.mod("%.5f", dense[:, j]))
             for j in range(dense.shape[1])]
    cols += list(slot_cols)
    lines = [" ".join(r) for r in zip(*cols)]
    files = []
    per = -(-n // n_files)
    for f in range(n_files):
        path = os.path.join(root, f"{tag}-part-{f:03d}.txt")
        with open(path, "w") as fh:
            fh.write("\n".join(lines[f * per:(f + 1) * per]) + "\n")
        files.append(path)
    return files


def _dense_and_labels(rng, n_ex: int, seed: int):
    dense = rng.normal(size=(n_ex, DENSE_DIM)).astype(np.float32)
    logit = 2.0 * dense @ _label_weights(seed) / np.sqrt(DENSE_DIM)
    labels = rng.random(n_ex) < 1.0 / (1.0 + np.exp(-logit))
    return dense, labels


def write_onehot_pass(root: str, tag: str, n_ex: int, slot_space: int,
                      seed: int, n_files: int, prev_ids=None,
                      overlap: float = 0.9):
    """One pass of one-hot CTR lines. With `prev_ids` (the previous
    pass's (n_ex, slots) ids) ~`overlap` of the tokens resample its keys
    and the rest come from a disjoint window — consecutive passes share
    most of their working set, so the incremental boundary has rows to
    reuse."""
    rng = np.random.default_rng(seed)
    salt = (np.arange(NUM_SLOTS, dtype=np.int64) + 1) << 40
    if prev_ids is None:
        ids = rng.integers(0, slot_space, size=(n_ex, NUM_SLOTS)) | salt
    else:
        old = prev_ids[rng.integers(0, len(prev_ids),
                                    size=(n_ex, NUM_SLOTS)),
                       np.arange(NUM_SLOTS)[None, :]]
        fresh = rng.integers(slot_space, 2 * slot_space,
                             size=(n_ex, NUM_SLOTS)) | salt
        ids = np.where(rng.random((n_ex, NUM_SLOTS)) < overlap, old, fresh)
    dense, labels = _dense_and_labels(rng, n_ex, seed)
    slot_cols = [np.char.add("1 ", ids[:, s].astype(str))
                 for s in range(NUM_SLOTS)]
    return _write_files(root, tag, labels, dense, slot_cols, n_files), ids


def write_multihot_pass(root: str, tag: str, n_ex: int, n_keys: int,
                        max_len: int, seed: int, n_files: int):
    """1..max_len ids per slot from one n_keys pool (variable lengths,
    so the pad mask is real)."""
    rng = np.random.default_rng(seed)
    pool = rng.choice(1 << 50, n_keys, replace=False).astype(np.int64)
    dense, labels = _dense_and_labels(rng, n_ex, seed)
    slot_cols = []
    for _ in range(NUM_SLOTS):
        lens = rng.integers(1, max_len + 1, size=n_ex)
        ids = pool[rng.integers(0, n_keys, size=(n_ex, max_len))].astype(str)
        col = np.char.add(lens.astype(str), "")
        for j in range(max_len):
            col = np.where(lens > j,
                           np.char.add(np.char.add(col, " "), ids[:, j]),
                           col)
        slot_cols.append(col)
    return _write_files(root, tag, labels, dense, slot_cols, n_files)


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------

def build_trainer(size: dict, emb_dim: int, max_len: int, mesh, seed: int):
    from paddlebox_tpu.data import DataFeedSchema
    from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
    from paddlebox_tpu.fleet import BoxPS
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.train import Trainer, TrainerConfig

    batch = size["batch"]
    schema = DataFeedSchema.ctr(num_sparse=NUM_SLOTS, num_float=DENSE_DIM,
                                batch_size=batch, max_len=max_len)
    store = HostEmbeddingStore(EmbeddingConfig(
        dim=emb_dim, optimizer="adagrad", learning_rate=0.05))
    model = DeepFMModel(num_slots=NUM_SLOTS, emb_dim=emb_dim,
                        dense_dim=DENSE_DIM, hidden=size["hidden"])
    tr = Trainer(model, store, schema, mesh,
                 TrainerConfig(global_batch_size=batch, auc_buckets=1 << 16,
                               dense_lr=3e-3), seed=seed)
    box = BoxPS(store)
    box.set_date(20260926)
    return schema, store, tr, box


def load_dataset(schema, files):
    from paddlebox_tpu.data import SlotDataset
    ds = SlotDataset(schema)
    ds.set_filelist(files)
    ds.load_into_memory(global_shuffle=False)
    return ds


def engines(tr) -> dict:
    """What the resolvers picked for the pass that just ran."""
    from paddlebox_tpu.ops import pallas_kernels
    ws = tr._last_ws
    push = tr.resolved_push_engine(ws)
    return {
        "table_layout": tr.table_layout,
        "exchange_wire": tr.exchange_wire,
        "pull_engine": tr.pull_engine,
        "push_engine": push,
        # the routed apply hands the binned kernel raw received lanes
        # (device argsort + reorder); only a host dedup plan on a single
        # shard feeds it premerged, already-sorted lanes
        "pack_engine": (pallas_kernels.pack_engine(
            tr.store.cfg, ws.rows_per_shard,
            premerged=(tr.table_layout != "sharded" and tr._use_plan
                       and tr._dedup_premerge(ws)))
            if push == "binned_kernel" else None),
        "push_overlap": bool(tr.push_overlap),
        "host_plan": bool(tr._use_plan),
        "table_shape": list(ws.table.shape),
        "rows_per_shard": int(ws.rows_per_shard),
    }


# Pallas kernels carry these names into the compiled program's text
KERNEL_OF_ENGINE = {"binned_kernel": "pbtpu_binned_merge_acc",
                    "scatter_accumulate": "pbtpu_scatter_accumulate",
                    "fused_gather_pool": "pbtpu_gather_pool"}


def compiled_step_facts(tr, ds) -> dict:
    """Compile the programs the pass ran, from abstract arguments shaped
    like its own, and read their text: which kernels and collectives the
    compiler really put in. (With the persistent cache on this is a cache
    read of what ``train_pass`` compiled.)"""
    import jax
    from paddlebox_tpu.parallel import mesh as mesh_lib
    from paddlebox_tpu.train.trainer import PLAN_ARITY

    ws = tr._last_ws
    bat_sh = mesh_lib.batch_sharding(tr.mesh)

    def like(a, sharding=None):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=sharding or a.sharding)

    pb = next(iter(ds.batches(tr.cfg.global_batch_size)))
    host = tr._pack_host(ws, pb)
    table = like(ws.table)
    dstate = [like(x) for x in tr.pack_dense()]
    batch = [like(a, bat_sh) for a in host]
    texts = {}
    if tr.push_overlap:
        args = (table, *dstate, *batch)
        step = tr._defer_step_fn.lower(*args).compile()
        ops = tr.split_defer_out(
            jax.eval_shape(tr._defer_step_fn, *args))[1]
        apply = tr._apply_fn.lower(
            table, batch[0], batch[1], batch[3],
            *batch[4:4 + PLAN_ARITY],
            *[like(o, bat_sh) for o in ops]).compile()
        texts["apply"] = apply.as_text()
    else:
        step = tr._step_fn.lower(table, *dstate, *batch).compile()
    texts["step"] = step.as_text()
    whole = "\n".join(texts.values())
    mem = step.memory_analysis()
    return {
        "programs": sorted(texts),
        "tpu_custom_calls": whole.count('custom_call_target="tpu_custom_call"'),
        "kernels": sorted(k for k in set(KERNEL_OF_ENGINE.values())
                          | {"pbtpu_merge_update"} if k in whole),
        "all_to_all": whole.count(" all-to-all("),
        "step_temp_bytes": int(mem.temp_size_in_bytes),
        "step_argument_bytes": int(mem.argument_size_in_bytes),
    }


def check_kernels_present(eng: dict, facts: dict, on_tpu: bool) -> None:
    """A kernel engine named in the record must be in the program."""
    if not on_tpu:
        return
    for named in (eng["push_engine"], eng["pull_engine"]):
        kernel = KERNEL_OF_ENGINE.get(named)
        if kernel is not None:
            check(kernel in facts["kernels"] and facts["tpu_custom_calls"] > 0,
                  f"engine {named!r} is recorded but {kernel} is not in "
                  f"the compiled step ({facts})")


def check_pass(stats: dict, what: str) -> None:
    check(stats["steps"] > 0, f"{what}: no step ran")
    check(all(np.isfinite(stats["losses"])), f"{what}: non-finite loss")
    check(int(stats["routed_dropped"]) == 0,
          f"{what}: {stats['routed_dropped']} tokens dropped")


def table_on(ws, platform: str) -> list:
    devs = sorted(str(s.device) for s in ws.table.addressable_shards)
    check(all(s.device.platform == platform
              for s in ws.table.addressable_shards),
          f"table shards sit on {devs}, not on the {platform}")
    return devs


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


@contextlib.contextmanager
def flags_set(**kw):
    from paddlebox_tpu.config import flags
    old = {k: flags.get(k) for k in kw}
    for k, v in kw.items():
        setattr(flags, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(flags, k, v)


def one_pass_run(name: str, size: dict, emb_dim: int, max_len: int,
                 n_dev: int, files, seed: int, device: dict, meter,
                 flags_kw: dict) -> dict:
    """A fresh trainer on an `n_dev` mesh trains one pass over `files`
    under the BoxPS lifecycle with `flags_kw` set; the common checks run
    and the common facts come back (and go out as the run's JSON line
    once the caller has added its own)."""
    from paddlebox_tpu.parallel import make_mesh

    with flags_set(**flags_kw):
        t0 = time.perf_counter()
        c0 = meter.snapshot()
        schema, store, tr, box = build_trainer(size, emb_dim, max_len,
                                               make_mesh(n_dev), seed)
        ds = load_dataset(schema, files)
        box.begin_pass()
        stats = tr.train_pass(ds, metrics=box.metrics)
        box.end_pass(trainer=tr)
        check_pass(stats, name)
        eng = engines(tr)
        facts = compiled_step_facts(tr, ds)
        check_kernels_present(eng, facts, device["platform"] == "tpu")
        line = dict(phase=name, flags=flags_kw, devices=n_dev,
                    steps=stats["steps"],
                    working_set_keys=int(len(ds.unique_keys())),
                    losses=stats["losses"], train_auc=stats["auc"],
                    table_devices=table_on(tr._last_ws, device["platform"]),
                    compiled=facts, **eng)
        return dict(tr=tr, ds=ds, stats=stats, eng=eng, facts=facts,
                    line=line, t0=t0, c0=c0)


def emit_run(run: dict, meter, **more) -> None:
    emit(**run["line"], **more, **meter.since(run["c0"]),
         peak_bytes_in_use=peak_bytes(),
         wall_seconds=round(time.perf_counter() - run["t0"], 3))


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def sync_check(device: dict) -> None:
    """Does ``block_until_ready`` wait for the device? A measuring
    program that ends its windows with it needs to know. A chain of matmuls is dispatched, waited on, and then a scalar is
    read back: if the wait had returned early the read would take the
    chain's time."""
    import jax
    import jax.numpy as jnp
    n, reps = (4096, 64) if device["platform"] == "tpu" else (256, 4)

    @jax.jit
    def chain(x):
        def body(_, a):
            return jnp.tanh(a @ a) * 0.5
        y = jax.lax.fori_loop(0, reps, body, x)
        return y, jnp.sum(y[:8, :128].astype(jnp.float32))

    x = jnp.full((n, n), 0.01, jnp.bfloat16)
    jax.block_until_ready(chain(x))                 # compile + warm
    t0 = time.perf_counter()
    y, s = chain(x)
    t1 = time.perf_counter()
    jax.block_until_ready(y)
    t2 = time.perf_counter()
    float(np.asarray(s))                            # 4-byte read
    t3 = time.perf_counter()
    flops = 2.0 * n ** 3 * reps
    emit(phase="sync_check", matmul_chain_flops=flops,
         dispatch_seconds=t1 - t0, block_until_ready_seconds=t2 - t1,
         read_after_seconds=t3 - t2,
         implied_flops_per_second=flops / max(t2 - t0, 1e-9),
         block_until_ready_waits=bool((t3 - t2) < 0.25 * (t2 - t0)))


def phase_train(size: dict, seed: int, root: str, device: dict,
                meter) -> None:
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.utils.pass_ckpt import PassCheckpointer

    on_tpu = device["platform"] == "tpu"
    t_phase = time.perf_counter()
    n_ex = size["batch"] * size["steps"]
    files0, ids0 = write_onehot_pass(root, "train-p0", n_ex,
                                     size["slot_space"], seed,
                                     size["files"])
    files1, _ = write_onehot_pass(root, "train-p1", n_ex,
                                  size["slot_space"], seed + 1,
                                  size["files"], prev_ids=ids0)
    schema, store, tr, box = build_trainer(size, 16, 1, make_mesh(1), seed)
    ckpt = PassCheckpointer(os.path.join(root, "snapshots"))
    passes = []
    ds = None
    for p, files in enumerate((files0, files1)):
        ds = load_dataset(schema, files)
        keys = int(len(ds.unique_keys()))
        check(keys >= size["min_keys"],
              f"pass {p} working set has {keys} keys, wants "
              f">= {size['min_keys']}")
        c0 = meter.snapshot()
        t0 = time.perf_counter()
        box.begin_pass()
        stats = tr.train_pass(ds, metrics=box.metrics)
        box.end_pass(trainer=tr, dataset=ds,
                     checkpointer=ckpt if p == 1 else None)
        wall = time.perf_counter() - t0
        check_pass(stats, f"train pass {p}")
        fm = tr.feed_mgr
        passes.append(stats)
        emit(phase="train", **{"pass": p}, steps=stats["steps"],
             working_set_keys=keys, loss_first=stats["loss_first"],
             loss_last=stats["loss_last"], train_auc=stats["auc"],
             wall_seconds=round(wall, 3), **meter.since(c0),
             fresh_rows=int(fm.last_fresh_rows),
             reused_rows=int(fm.last_reused_rows),
             table_devices=table_on(tr._last_ws, device["platform"]),
             **engines(tr))
    check(passes[1]["loss_last"] < passes[0]["loss_first"],
          f"loss did not fall: {passes[0]['loss_first']} at the start of "
          f"pass 0, {passes[1]['loss_last']} at the end of pass 1")
    check(tr.feed_mgr.last_reused_rows > 0,
          "pass 1 reused no resident row of pass 0")
    ev = tr.eval_pass(ds)
    check(np.isfinite(ev["auc"]) and abs(ev["auc"] - 0.5) > 0.02,
          f"eval AUC {ev['auc']} is not off 0.5")
    check(int(ev["routed_dropped"]) == 0, "eval dropped tokens")
    eng = engines(tr)
    facts = compiled_step_facts(tr, ds)
    check_kernels_present(eng, facts, on_tpu)

    # one save/load round trip of the pass checkpoint, into a new trainer
    _, store2, tr2, box2 = build_trainer(size, 16, 1, make_mesh(1),
                                         seed + 100)
    cursor = tr2.resume(ckpt, box=box2)
    check(cursor is not None and cursor["pass_id"] == box.pass_id,
          f"resume cursor {cursor} is not pass {box.pass_id}")
    check(len(store2) == len(store),
          f"resumed store has {len(store2)} keys, saved {len(store)}")
    sample = np.sort(ds.unique_keys())[:: max(1, len(store) // 4096)]
    check(np.array_equal(store2.peek_rows(sample), store.peek_rows(sample)),
          "resumed sparse rows differ from the saved ones")
    import jax
    same = jax.tree.map(lambda a, b: bool(np.array_equal(a, b)),
                        tr2.eval_params(), tr.eval_params())
    check(all(jax.tree.leaves(same)),
          "resumed dense params differ from the saved ones")
    emit(phase="train", eval_auc=ev["auc"], checkpoint_round_trip="ok",
         store_keys=len(store), compiled=facts,
         peak_bytes_in_use=peak_bytes(),
         wall_seconds=round(time.perf_counter() - t_phase, 3))


def phase_multihot(size: dict, seed: int, root: str, device: dict,
                   meter) -> None:
    """The 4-hot dim-32 layout, twice on the same data: with default
    flags, and with the device table padded to whole 128-lane tiles —
    the table form the fused gather_pool / scatter_accumulate kernels'
    geometry accepts, so `auto` selects them. The second run's losses
    are held to the first's."""
    on_tpu = device["platform"] == "tpu"
    n_ex = size["batch"] * size["mh_steps"]
    files = write_multihot_pass(root, "multihot", n_ex, size["mh_keys"], 4,
                                seed + 2, size["files"])
    losses = {}
    for name, kw in (("multihot", {}),
                     ("multihot_lane_tiles", {"table_pad_width": 128})):
        run = one_pass_run(name, size, 32, 4, 1, files, seed, device,
                           meter, kw)
        stats, eng = run["stats"], run["eng"]
        with flags_set(**kw):
            ev = run["tr"].eval_pass(run["ds"])
        emit_run(run, meter, eval_auc=ev["auc"])
        check(stats["loss_last"] < stats["loss_first"],
              f"{name}: loss did not fall ({stats['losses']})")
        check(np.isfinite(ev["auc"]) and abs(ev["auc"] - 0.5) > 0.02,
              f"{name}: eval AUC {ev['auc']} is not off 0.5")
        if on_tpu and kw:
            check(eng["pull_engine"] == "fused_gather_pool"
                  and eng["push_engine"] == "scatter_accumulate",
                  f"{name}: auto did not pick the fused kernels on a "
                  f"lane-tile table ({eng})")
        losses[name] = np.asarray(stats["losses"])
    diff = float(np.max(np.abs(losses["multihot"]
                               - losses["multihot_lane_tiles"])))
    check(diff < 2e-3, f"lane-tile run's losses differ from the default "
                       f"run's by {diff}")
    emit(phase="multihot", max_loss_diff_between_runs=diff)


def phase_kernels(device: dict) -> None:
    """Each Pallas kernel a resolver can select, against its jnp
    reference on a small input (compiled for the device when it is a TPU,
    interpreted otherwise)."""
    import jax.numpy as jnp
    from paddlebox_tpu.embedding import EmbeddingConfig, sharded
    from paddlebox_tpu.ops import pallas_kernels as pk

    interpret = device["platform"] != "tpu"
    rng = np.random.default_rng(3)
    cfg = EmbeddingConfig(dim=16, optimizer="adagrad", learning_rate=0.05)
    n_rows, W, B, S, L = 8192, 128, 128, 4, 2
    table = np.zeros((n_rows, W), np.float32)
    table[:, :cfg.row_width] = np.abs(rng.normal(
        size=(n_rows, cfg.row_width)))
    table[0] = 0.0
    table = jnp.asarray(table)
    idx = rng.integers(0, n_rows, size=(B, S * L)).astype(np.int32)
    out = {}

    pooled = pk.gather_pool(table, jnp.asarray(idx), cfg, S, L,
                            interpret=interpret)
    ref = sharded.lookup(table, jnp.asarray(idx).reshape(-1), cfg).reshape(
        B, S, L, cfg.pull_width).sum(axis=2)
    out["gather_pool"] = float(jnp.max(jnp.abs(pooled - ref)))

    uniq = np.unique(idx)
    lanes = np.concatenate([uniq, np.full(37, n_rows)]).astype(np.int32)
    grads = rng.normal(size=(len(lanes), cfg.grad_width)).astype(np.float32)
    shows = np.ones(len(lanes), np.float32)
    clks = (rng.random(len(lanes)) < 0.3).astype(np.float32)
    args = tuple(map(jnp.asarray, (lanes, grads, shows, clks)))
    got = pk.scatter_accumulate(table, *args, cfg, interpret=interpret)
    with flags_set(push_engine="xla_scatter"):
        want = sharded.push(table, *args, cfg)
    out["scatter_accumulate"] = float(jnp.max(jnp.abs(got - want)))

    narrow = table[:, :cfg.row_width]
    tok = idx.reshape(-1)
    tg = rng.normal(size=(len(tok), cfg.grad_width)).astype(np.float32)
    ts = np.ones(len(tok), np.float32)
    tc = (rng.random(len(tok)) < 0.3).astype(np.float32)
    targs = tuple(map(jnp.asarray, (tok, tg, ts, tc)))
    got = pk.binned_push(narrow, *targs, cfg, n_split=3,
                         interpret=interpret)
    with flags_set(push_engine="xla_scatter"):
        want = sharded.push(narrow, *targs, cfg)
    out["binned_push"] = float(jnp.max(jnp.abs(got - want)))
    for name, err in out.items():
        check(np.isfinite(err) and err < 1e-4,
              f"kernel {name} differs from its reference by {err}")
    emit(phase="kernels", interpret=interpret, max_abs_error=out)


def phase_mesh4(size: dict, seed: int, root: str, device: dict, meter,
                rehearse: bool) -> None:
    """The train phase's first pass on a four-device mesh (sharded
    exchange, bf16 push wire by default; once more with the f32 wire),
    held to the same batches on a one-device mesh in this process."""
    on_tpu = device["platform"] == "tpu"
    n_ex = size["batch"] * size["steps"]
    files, _ = write_onehot_pass(root, "mesh4", n_ex, size["slot_space"],
                                 seed, size["files"])
    # off-TPU `auto` keeps the legacy layout on any mesh; the rehearsal
    # forces what a TPU resolves to, so it walks the same exchange
    forced = {"table_layout": "sharded"} if rehearse and not on_tpu else {}
    losses = {}
    for name, n_dev, kw in (("mesh1", 1, {}),
                            ("mesh4_bf16_wire", 4, forced),
                            ("mesh4_f32_wire", 4,
                             {**forced, "exchange_wire": "f32"})):
        run = one_pass_run(name, size, 16, 1, n_dev, files, seed, device,
                           meter, kw)
        eng, facts, ws = run["eng"], run["facts"], run["tr"]._last_ws
        shards = ws.table.addressable_shards
        shard_rows = [int(sh.data.shape[0]) for sh in shards]
        emit_run(run, meter, shard_rows=shard_rows)
        if n_dev > 1:
            check(eng["table_layout"] == "sharded",
                  f"{name}: table_layout resolved to "
                  f"{eng['table_layout']!r}")
            check(len(set(run["line"]["table_devices"])) == n_dev,
                  f"{name}: table shards sit on "
                  f"{run['line']['table_devices']}")
            check(shard_rows == [ws.rows_per_shard] * n_dev,
                  f"{name}: shard rows {shard_rows} are not "
                  f"{ws.rows_per_shard} each")
            check(facts["all_to_all"] > 0,
                  f"{name}: no all-to-all in the compiled step")
        losses[name] = np.asarray(run["stats"]["losses"])
    d_bf16 = float(np.max(np.abs(losses["mesh4_bf16_wire"]
                                 - losses["mesh1"])))
    d_f32 = float(np.max(np.abs(losses["mesh4_f32_wire"]
                                - losses["mesh1"])))
    emit(phase="mesh4", max_loss_diff_bf16_wire=d_bf16,
         tolerance_bf16_wire=TOL_BF16_WIRE, max_loss_diff_f32_wire=d_f32,
         tolerance_f32_wire=TOL_F32_WIRE, f32_wire_tighter=d_f32 <= d_bf16)
    check(d_bf16 < TOL_BF16_WIRE,
          f"bf16-wire losses differ from one chip by {d_bf16}")
    check(d_f32 < TOL_F32_WIRE,
          f"f32-wire losses differ from one chip by {d_f32}")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; never prints ok")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU — jax.devices()[0].platform is "
              f"{device['platform']!r}; this script checks the program on "
              f"the chip and does not fall back (--rehearse walks the "
              f"control flow at tiny sizes instead)", file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2

    from paddlebox_tpu.utils.compile_cache import (CompileMeter,
                                                   enable_compile_cache)
    cache = enable_compile_cache()
    meter = CompileMeter()
    import jaxlib
    from paddlebox_tpu.native import key_index, slot_parser_binding
    native = {"slot_parser": slot_parser_binding.available(),
              "key_index": key_index.native_available()}
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:            # noqa: BLE001 — absent off-TPU installs
        libtpu = None
    emit(phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu, device=device, chips=args.chips, seed=args.seed,
         rehearse=args.rehearse, compile_cache=cache, native=native)

    size = TINY if args.rehearse else FULL
    root = tempfile.mkdtemp(prefix="pbtpu_smoke_")
    t0 = time.perf_counter()
    try:
        check(all(native.values()),
              f"native helpers missing ({native}): the smoke checks the "
              f"production ingest and pack path")
        if args.chips == 4:
            phase_mesh4(size, args.seed, root, device, meter, args.rehearse)
        else:
            sync_check(device)
            phase_train(size, args.seed, root, device, meter)
            phase_multihot(size, args.seed, root, device, meter)
            phase_kernels(device)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(root, ignore_errors=True)
    emit(phase="end", wall_seconds=round(time.perf_counter() - t0, 3),
         **meter.snapshot(), compile_cache=cache)
    if args.rehearse:
        emit(rehearsal="passed", device=device)
        return 0
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
