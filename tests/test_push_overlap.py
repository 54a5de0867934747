"""Deferred sparse-push pipeline (flags.push_overlap).

The jitted step returns the packed push operands instead of applying them
inline; the trainer dispatches the table apply for step N as its own
program while step N+1's pack/plan-H2D runs. The contract under test:

- **Bit-for-bit parity**: overlap-on (after the pass-boundary flush) must
  equal overlap-off on the persisted table rows, the dense params, and
  the whole loss trajectory — the apply is always data-sequenced before
  the next step consumes the table, so deferral is a program-boundary
  choice with no numeric consequence.
- **Loss path**: the deferred step program must not contain the table
  apply (no scatter in the lowered text, no table output) — the
  acceptance criterion verified via jaxpr/HLO inspection.
- **Flush ordering**: pass end, eval, and store save/export must all see
  the applied table (pending applies land first).
- **Bounded staleness**: at most ONE unapplied step, enforced by the
  operand stager; and no thread or staged-buffer leaks after a pass.
"""

import threading

import numpy as np
import pytest

import jax

from paddlebox_tpu.config import set_flags
from paddlebox_tpu.data import DataFeedSchema
from paddlebox_tpu.data.dataset import SlotDataset
from paddlebox_tpu.data.slot_record import SlotRecordBatch
from paddlebox_tpu.embedding import EmbeddingConfig, HostEmbeddingStore
from paddlebox_tpu.embedding.working_set import PushOperandStager
from paddlebox_tpu.models import DeepFMModel
from paddlebox_tpu.parallel import make_mesh
from paddlebox_tpu.train import Trainer, TrainerConfig

NUM_SLOTS, EMB_DIM, BATCH = 4, 4, 16


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags(push_overlap="auto", push_dedup_premerge="auto")


def _dataset(n_ex, seed=0):
    schema = DataFeedSchema.ctr(num_sparse=NUM_SLOTS, num_float=1,
                                batch_size=BATCH, max_len=1)
    rng = np.random.default_rng(seed)
    offs = np.arange(n_ex + 1, dtype=np.int64)
    ds = SlotDataset(schema)
    ds.records = SlotRecordBatch(
        schema=schema, num=n_ex,
        sparse_values=[(rng.integers(1, 400, size=n_ex).astype(np.int64)
                        | (np.int64(s + 1) << np.int64(40)))
                       for s in range(NUM_SLOTS)],
        sparse_offsets=[offs.copy() for _ in range(NUM_SLOTS)],
        float_values=[(rng.random(n_ex) < 0.3).astype(np.float32),
                      rng.normal(size=n_ex).astype(np.float32)],
        ins_id=np.zeros(n_ex, dtype=np.uint64),
        search_id=np.zeros(n_ex, dtype=np.uint64),
        rank=np.zeros(n_ex, dtype=np.int32),
        cmatch=np.zeros(n_ex, dtype=np.int32))
    return ds, schema


def _build(overlap, n_dev=8, use_plan=False, n_batches=6):
    set_flags(push_overlap=overlap)
    ds, schema = _dataset(n_batches * BATCH)
    store = HostEmbeddingStore(EmbeddingConfig(dim=EMB_DIM,
                                               learning_rate=0.05))
    tr = Trainer(DeepFMModel(num_slots=NUM_SLOTS, emb_dim=EMB_DIM,
                             dense_dim=1, hidden=(8,)),
                 store, schema, make_mesh(n_dev),
                 TrainerConfig(global_batch_size=BATCH))
    if use_plan:
        # the host binned/dedup plan is TPU-gated in production; force it
        # so the CPU suite exercises the premerged deferred variant
        tr._use_plan = True
    return tr, ds, store


def _run(overlap, n_dev=8, use_plan=False):
    tr, ds, store = _build(overlap, n_dev, use_plan)
    out = tr.train_pass(ds)
    tr.flush_sparse()
    keys = np.sort(np.unique(np.concatenate(
        [np.asarray(v) for v in ds.records.sparse_values]))).astype(
        np.uint64)
    rows = store.peek_rows(keys)
    params = jax.tree.map(np.asarray, tr.params)
    return out, rows, params, tr


def _assert_bitwise(a, b):
    assert np.array_equal(a, b), (
        f"maxdiff {np.abs(np.asarray(a) - np.asarray(b)).max()}")


def test_overlap_parity_bitwise_mesh8():
    """Flushed overlap-on == overlap-off bit-for-bit: table rows, dense
    params, loss trajectory (the acceptance criterion)."""
    out_on, rows_on, p_on, tr_on = _run("on")
    out_off, rows_off, p_off, tr_off = _run("off")
    assert tr_on.push_overlap and not tr_off.push_overlap
    assert out_on["steps"] == out_off["steps"] == 6
    for k in ("loss_first", "loss_last", "loss_mean", "auc"):
        assert out_on[k] == out_off[k], k
    _assert_bitwise(rows_on, rows_off)
    for a, b in zip(jax.tree.leaves(p_on), jax.tree.leaves(p_off)):
        _assert_bitwise(a, b)
    # one apply dispatched per step, all drained at the boundary
    assert tr_on.push_applies == out_on["steps"]
    assert tr_on._push_stager.pending() == 0
    assert tr_off.push_applies == 0


def test_overlap_parity_premerged_plan_single_shard():
    """The dedup-plan variant: the step premerges grads/shows/clks onto
    unique lanes in-step and the apply replays only the engine — still
    bit-for-bit against the inline path with the same plan."""
    set_flags(push_dedup_premerge="on")
    out_on, rows_on, p_on, tr_on = _run("on", n_dev=1, use_plan=True)
    set_flags(push_dedup_premerge="on")
    out_off, rows_off, p_off, tr_off = _run("off", n_dev=1, use_plan=True)
    # prove the plan actually carried dedup bounds (the premerged path)
    ws = tr_on.feed_mgr._current
    plan = tr_on._host_plan(ws, ws.translate(
        np.asarray(ws.sorted_keys[:BATCH * NUM_SLOTS]).reshape(
            BATCH, NUM_SLOTS)))
    assert plan[3].shape[0] > 0, "dedup premerge plan did not engage"
    for k in ("loss_first", "loss_last", "loss_mean"):
        assert out_on[k] == out_off[k], k
    _assert_bitwise(rows_on, rows_off)
    for a, b in zip(jax.tree.leaves(p_on), jax.tree.leaves(p_off)):
        _assert_bitwise(a, b)


def test_step_program_excludes_table_apply():
    """jaxpr/HLO inspection (the acceptance criterion): with overlap on,
    the loss-producing step program contains no table scatter-update and
    returns no table; the inline program contains both."""
    tr, ds, store = _build("on", n_dev=1)
    ws = tr.feed_mgr.begin_pass(ds.unique_keys())
    pb = next(iter(ds.batches(BATCH)))
    staged = tr._put_batch(ws, pb)
    dstate = tr.pack_dense()

    defer_txt = tr._defer_step_fn.lower(
        ws.table, *dstate, *staged).as_text()
    inline_txt = tr._step_fn.lower(ws.table, *dstate, *staged).as_text()
    assert "scatter" in inline_txt, \
        "inline step lost its table apply — test premise broken"
    assert "scatter" not in defer_txt, \
        "deferred step still contains the table apply on the loss path"
    # the apply program is where the scatter moved. Both the inline step
    # and the apply DONATE the table, so each consumer gets its own copy
    from paddlebox_tpu.parallel import mesh as mesh_lib
    tbl_sh = mesh_lib.table_sharding(tr.mesh)
    table_np = np.asarray(ws.table)
    # both execs below donate their dense state and must see the SAME
    # pre-step state — snapshot it to host first
    dstate_np = tuple(np.asarray(a) for a in dstate)
    ops = tr._defer_step_fn(jax.device_put(table_np, tbl_sh), *dstate,
                            *staged)
    dst, push_ops, loss, preds, dropped = tr.split_defer_out(ops)
    apply_txt = tr._apply_fn.lower(
        jax.device_put(table_np, tbl_sh), staged[0], staged[1],
        staged[3], *staged[4:9], *push_ops).as_text()
    assert "scatter" in apply_txt
    # and the deferred step's output carries no table: applying the ops
    # through the apply program reproduces the inline step's table
    inline_out = tr._step_fn(
        jax.device_put(table_np, tbl_sh),
        *(jax.device_put(a) for a in dstate_np), *staged)
    inline_table = np.asarray(inline_out[0])
    applied = tr._apply_fn(jax.device_put(table_np, tbl_sh), staged[0],
                           staged[1], staged[3], *staged[4:9], *push_ops)
    _assert_bitwise(np.asarray(applied), inline_table)


def test_flush_on_eval_and_save_ordering():
    """eval_pass and store save (via flush hooks) must observe the fully
    applied table: predictions and persisted rows equal the overlap-off
    run's after identical training."""
    out_on, rows_on, p_on, tr_on = _run("on")
    tr2, ds2, store2 = _build("off")
    tr2.train_pass(ds2)
    ev_off = tr2.eval_pass(ds2)

    tr3, ds3, store3 = _build("on")
    tr3.train_pass(ds3)
    ev_on = tr3.eval_pass(ds3)     # flush_push runs at eval entry
    assert ev_on["auc"] == ev_off["auc"]
    # store-initiated flush (save path) reaches the trainer through the
    # feed manager's pre-flush hook; rows must be final
    assert tr3._push_stager.pending() == 0
    assert tr3._push_stager.live() == 0


def test_staleness_bound_enforced():
    st = PushOperandStager()
    st.put("step0")
    with pytest.raises(RuntimeError, match="staleness"):
        st.put("step1")
    assert st.take() == "step0"
    assert st.live() == 1          # retired slot pins the in-flight refs
    st.put("step1")
    st.take()
    st.clear()
    assert st.live() == 0


def test_no_thread_or_slot_leaks():
    """The deferred pipeline is async-dispatch only: no helper threads,
    and the stager holds no buffers between passes (conftest's autouse
    thread-leak fixture double-checks the thread half)."""
    before = threading.active_count()
    out, rows, params, tr = _run("on")
    assert tr._push_stager.live() == 0
    assert tr._push_stager.pending() == 0
    assert threading.active_count() <= before + 1  # pack thread may lag


def test_auto_selection_rules():
    """auto = on for allreduce; off for kstep/async; 'on' raises where
    the pipeline cannot hold its bound."""
    ds, schema = _dataset(2 * BATCH)
    mesh = make_mesh(8)

    def make(**kw):
        return Trainer(DeepFMModel(num_slots=NUM_SLOTS, emb_dim=EMB_DIM,
                                   dense_dim=1, hidden=(8,)),
                       HostEmbeddingStore(EmbeddingConfig(dim=EMB_DIM)),
                       schema, mesh,
                       TrainerConfig(global_batch_size=BATCH, **kw))

    set_flags(push_overlap="auto")
    assert make().push_overlap
    assert not make(dense_sync_mode="kstep").push_overlap
    assert not make(dense_sync_mode="async").push_overlap
    set_flags(push_overlap="on")
    with pytest.raises(ValueError, match="push_overlap"):
        make(dense_sync_mode="kstep")
    set_flags(push_overlap="off")
    assert not make().push_overlap
