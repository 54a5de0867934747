"""Lane-tile f32 planes: a wide-row device table whose rows can be addressed.

Where embedx(+expand) is whole 128-lane tiles the device table is two
planes (quant.PlaneTable: the f32 embedx plane, and the narrow rest) and
the push of premerged lanes touches only the rows a step changed. These
tests hold the planes to the one-array table of the same configuration:
the layout rule and who is NOT moved by it, lookup and push bit for bit
(eagerly: one primitive at a time, so no compile fusion reorders a
rounding), the working set's whole boundary cycle, one trainer pass, and
the routed apply on a two-device mesh.
"""

import contextlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddlebox_tpu.config import flags
from paddlebox_tpu.embedding import (EmbeddingConfig, HostEmbeddingStore,
                                     PassWorkingSet, exchange, feed_pass,
                                     quant, sharded, working_set)
from paddlebox_tpu.embedding.feed_pass import FeedPassManager
from paddlebox_tpu.native.key_index import dedup_plan
from paddlebox_tpu.ops import pallas_kernels as pk
from paddlebox_tpu.parallel import make_mesh

OPTIMIZERS = ("sgd", "adagrad", "adam", "ftrl")


@pytest.fixture()
def restore_flags():
    old = (flags.push_engine, flags.table_pad_width,
           flags.transfer_compress_embedx)
    yield
    (flags.push_engine, flags.table_pad_width,
     flags.transfer_compress_embedx) = old


@contextlib.contextmanager
def one_array(monkeypatch):
    """The table this configuration had before the planes: one array."""
    with monkeypatch.context() as m:
        for mod in (working_set, feed_pass):
            m.setattr(mod, "plane_layout", lambda cfg: cfg.storage != "f32")
        yield


def _cfg(optimizer="adagrad", dim=128, **kw):
    return EmbeddingConfig(dim=dim, optimizer=optimizer, learning_rate=0.05,
                           **kw)


def _host_rows(cfg, n_rows, seed=0):
    rng = np.random.default_rng(seed)
    rows = (rng.standard_normal((n_rows, cfg.row_width)) * 0.1
            ).astype(np.float32)
    rows[:, 0] = rng.integers(0, 20, n_rows)            # show
    rows[:, 1] = rng.integers(0, 5, n_rows)             # clk
    rows[:, cfg.opt_cols] = np.abs(rows[:, cfg.opt_cols])   # g2sum, v >= 0
    rows[0] = 0.0                                       # the null row
    return rows


def _tokens(cfg, n_rows, n_tok, seed):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n_rows, n_tok).astype(np.int32)
    grads = (rng.standard_normal((n_tok, cfg.grad_width)) * 0.01
             ).astype(np.float32)
    shows = (idx > 0).astype(np.float32)
    clks = (rng.integers(0, 2, n_tok) * shows).astype(np.float32)
    grads[idx == 0] = 0.0
    return idx, grads, shows, clks


def _premerge(idx, grads, shows, clks, n_rows):
    """The lanes the engine sees in production: the host dedup plan, then
    plan_premerge — unique, ascending, pads out of range."""
    o, u, s, _, _ = dedup_plan(idx, n_rows, n_rows, 1)
    Z = np.zeros(0, np.int32)
    uniq, mg, ms, mc, _ = sharded.plan_premerge(
        *map(jnp.asarray, (idx, grads, shows, clks)),
        tuple(map(jnp.asarray, (o, Z, Z, u, s))))
    return uniq, mg, ms, mc


def _as_rows(table, cfg):
    if quant.is_planes(table):
        return quant.decode_rows_np(np.asarray(table.fp),
                                    np.asarray(table.qx), cfg)
    return np.asarray(table)


# ---------------------------------------------------------------------------
# (a) the layout rule, and who it leaves alone
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("storage,dim,expand,planes", [
    ("f32", 8, 0, False), ("f32", 10, 0, False), ("f32", 64, 0, False),
    ("f32", 120, 0, False), ("f32", 128, 0, True), ("f32", 64, 64, True),
    ("f32", 256, 0, True), ("f32", 512, 0, True), ("f32", 640, 0, True),
    ("f32", 2560, 0, True), ("f32", 600, 0, False),
    ("f32", 0, 0, False), ("int8", 8, 0, True), ("int16", 128, 0, True)])
def test_layout_rule(storage, dim, expand, planes):
    cfg = EmbeddingConfig(dim=dim, expand_dim=expand, storage=storage)
    assert working_set.plane_layout(cfg) is planes
    store = HostEmbeddingStore(cfg)
    ws = PassWorkingSet.begin_pass(
        store, np.arange(1, 30, dtype=np.uint64), make_mesh(1))
    assert quant.is_planes(ws.table) is planes
    assert quant.is_quant(ws.table) is (storage != "f32")
    assert tuple(ws.table.shape) == (ws.padded_rows, cfg.row_width)
    if planes:
        assert ws.table.qx.shape == (ws.padded_rows, cfg.total_dim)
        assert ws.table.fp.shape == (ws.padded_rows, quant.fp_width(cfg))
        assert quant.fp_width(cfg) == (cfg.row_width - cfg.total_dim
                                       + (storage != "f32"))
    else:
        assert isinstance(ws.table, jax.Array)


def test_flags_that_assume_one_array_switch_the_planes_off(restore_flags):
    cfg = _cfg()
    assert working_set.plane_layout(cfg)
    flags.table_pad_width = "auto"          # pads nothing at 133 columns
    assert working_set.plane_layout(cfg)
    flags.table_pad_width = 256
    assert not working_set.plane_layout(cfg)
    store = HostEmbeddingStore(cfg)
    ws = PassWorkingSet.begin_pass(
        store, np.arange(1, 30, dtype=np.uint64), make_mesh(1))
    assert ws.table.shape == (ws.padded_rows, 256)
    flags.table_pad_width = 0
    flags.transfer_compress_embedx = True
    assert not working_set.plane_layout(cfg)
    assert working_set.plane_layout(_cfg(storage="int8"))   # as before


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_engines_by_class(monkeypatch, restore_flags, backend):
    """Narrow rows, half tiles and quantized storage resolve as they did;
    the plane class takes the touched-rows engine where its lanes are
    premerged — on a TPU by `auto`, elsewhere when forced."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    rows = 1 << 16
    tpu = backend == "tpu"

    def engine(cfg, premerged, width):
        return pk.resolve_push_engine(
            cfg, rows, premerged=premerged,
            storage_f32=cfg.storage == "f32", table_width=width)

    for dim in (8, 10):
        c = _cfg(dim=dim)
        for pm in (False, True):
            assert engine(c, pm, c.row_width) == (
                "binned_kernel" if tpu else "xla_scatter")
    c64 = _cfg(dim=64)
    assert engine(c64, True, c64.row_width) == "xla_scatter"
    for st in ("int8", "int16"):
        assert engine(_cfg(dim=128, storage=st), True, None) == "xla_scatter"
    c = _cfg()
    planes = quant.device_planes(_host_rows(c, 64), c, None)
    assert quant.row_engine_width(planes) == 128
    assert quant.row_engine_width(jnp.zeros((4, 133))) == 133
    assert quant.row_engine_width(quant.device_planes(
        _host_rows(c, 8), _cfg(storage="int8"), None)) is None
    assert engine(c, True, 128) == (
        "scatter_accumulate" if tpu else "xla_scatter")
    assert engine(c, False, 128) == "xla_scatter"
    assert engine(c, True, c.row_width) == "xla_scatter"     # one array
    flags.push_engine = "scatter_accumulate"
    assert engine(c, True, 128) == "scatter_accumulate"
    assert engine(c, False, 128) == "xla_scatter"


# ---------------------------------------------------------------------------
# (b) lookup and push, bit for bit against the one-array dense path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("optimizer", OPTIMIZERS)
def test_lookup_and_push_bit_identical(optimizer, restore_flags):
    cfg = _cfg(optimizer)
    n_rows = 96
    host = _host_rows(cfg, n_rows, seed=1)
    one = jnp.asarray(host)
    planes = quant.device_planes(host, cfg, None)
    assert planes.shape == one.shape
    probe = jnp.asarray(
        np.random.default_rng(2).integers(0, n_rows, (5, 7)), jnp.int32)
    for step in range(4):
        np.testing.assert_array_equal(
            np.asarray(sharded.lookup(planes, probe, cfg)),
            np.asarray(sharded.lookup(one, probe, cfg)))
        idx, grads, shows, clks = _tokens(cfg, n_rows, 120, seed=10 + step)
        if step == 3:                       # an all-pad batch
            idx[:] = 0
            grads[:], shows[:], clks[:] = 0.0, 0.0, 0.0
        uniq, mg, ms, mc = _premerge(idx, grads, shows, clks, n_rows)
        assert int(uniq[-1]) >= n_rows      # pads out of range
        assert len(np.unique(np.asarray(uniq))) == len(uniq)
        before = _as_rows(planes, cfg)
        flags.push_engine = "xla_scatter"   # the dense accumulator path
        one = sharded.push(one, uniq, mg, ms, mc, cfg, premerged=True)
        flags.push_engine = "scatter_accumulate"
        planes = sharded.push(planes, uniq, mg, ms, mc, cfg,
                              premerged=True)
        got = _as_rows(planes, cfg)
        np.testing.assert_array_equal(got, np.asarray(one))
        untouched = np.setdiff1d(np.arange(n_rows), idx)
        np.testing.assert_array_equal(got[untouched], before[untouched])
        assert (got[np.unique(idx[idx > 0]), 0]
                > before[np.unique(idx[idx > 0]), 0]).all()
        assert not got[0].any()             # the null row stays zero


@pytest.mark.parametrize("optimizer", ["adagrad", "adam"])
def test_unmerged_tokens_take_the_dense_plane_pass(optimizer,
                                                   restore_flags):
    """Raw token streams (no host plan: every CPU run under `auto`) keep
    the accumulator engines, plane by plane — the same update."""
    cfg = _cfg(optimizer)
    host = _host_rows(cfg, 64, seed=3)
    args = tuple(map(jnp.asarray, _tokens(cfg, 64, 90, seed=4)))
    want = np.asarray(sharded.push(jnp.asarray(host), *args, cfg))
    got = sharded.push(quant.device_planes(host, cfg, None), *args, cfg)
    assert quant.is_planes(got) and not quant.is_quant(got)
    np.testing.assert_array_equal(_as_rows(got, cfg), want)


# ---------------------------------------------------------------------------
# (c) the working set's cycle: begin -> pushes -> incremental boundary
# ---------------------------------------------------------------------------

def _foreign_delta(store, keys, tmp):
    """A delta replay from another trainer: the store's rows of `keys`
    change under the resident working set, and the stale log names them."""
    donor = HostEmbeddingStore(store.cfg)
    rows = donor.lookup_or_init(keys)
    rows[:, 2] = 7.0
    donor.write_back(keys, rows)
    store.apply_delta_file(donor.save_delta(os.path.join(tmp, "delta")))


def _boundary_cycle(cfg, tmp):
    """Two passes through a FeedPassManager with churn between them, a
    store mutation that patches a staged feed, a flush and an end."""
    flags.push_engine = "scatter_accumulate"
    store = HostEmbeddingStore(cfg)
    mgr = FeedPassManager(store, make_mesh(1))
    rng = np.random.default_rng(5)
    keys_a = rng.choice(1 << 40, 70, replace=False).astype(np.uint64)
    keys_b = np.concatenate([keys_a[:40], rng.choice(
        1 << 40, 25, replace=False).astype(np.uint64)])
    seen = []
    for p, keys in enumerate((keys_a, keys_b)):
        if p == 1:
            mgr.begin_feed_pass(keys)               # staged in background
            mgr.wait_feed_pass_done()
            # a mutation after the staging: _patch_jit lands its rows
            _foreign_delta(store, np.sort(keys)[:3], tmp)
        ws = mgr.begin_pass(keys)
        seen.append(quant.is_planes(ws.table))
        mgr.pass_opened()
        for step in range(3):
            ids = rng.choice(keys, (8, 6))
            idx = ws.translate(ids).reshape(-1)
            _, grads, shows, clks = _tokens(cfg, 2, idx.size,
                                            seed=20 + 10 * p + step)
            uniq, mg, ms, mc = _premerge(idx, grads * 0 + grads[:1], shows
                                         * 0 + 1.0, clks * 0, ws.padded_rows)
            ws.table = sharded.push(ws.table, uniq, mg, ms, mc, cfg,
                                    premerged=True)
        mgr.pass_closed()
        mgr.end_pass(ws)
    assert mgr.last_patched_rows == 3 and mgr.last_reused_rows == 40
    rows, _ = working_set.fetch_rows(ws.table, np.arange(1, 9), cfg)
    mgr.flush()
    every = np.unique(np.concatenate([keys_a, keys_b]))
    return seen, rows, store.peek_rows(every)


def test_working_set_round_trip_matches_one_array(monkeypatch, tmp_path,
                                                  restore_flags):
    cfg = _cfg("adagrad")
    seen_p, rows_p, store_p = _boundary_cycle(cfg, str(tmp_path / "p"))
    with one_array(monkeypatch):
        seen_1, rows_1, store_1 = _boundary_cycle(cfg, str(tmp_path / "1"))
    assert seen_p == [True, True] and seen_1 == [False, False]
    np.testing.assert_array_equal(rows_p, rows_1)
    np.testing.assert_array_equal(store_p, store_1)


def test_end_pass_write_back_full_and_touched(restore_flags):
    """PassWorkingSet.end_pass on planes: the touched-rows write-back
    and the whole-table one both hand the store full f32 rows."""
    cfg = _cfg("adam")
    store = HostEmbeddingStore(cfg)
    keys = np.arange(100, 140, dtype=np.uint64)
    ws = PassWorkingSet.begin_pass(store, keys, make_mesh(1))
    first = store.peek_rows(keys).copy()
    idx = ws.translate(keys[:10].reshape(2, 5)).reshape(-1)
    _, grads, shows, clks = _tokens(cfg, 2, idx.size, seed=6)
    flags.push_engine = "scatter_accumulate"
    uniq, mg, ms, mc = _premerge(idx, grads + 0.01, shows + 1.0, clks,
                                 ws.padded_rows)
    ws.table = sharded.push(ws.table, uniq, mg, ms, mc, cfg, premerged=True)
    want = _as_rows(ws.table, cfg)[1:1 + len(keys)]
    ws.end_pass(store)                              # only the touched rows
    np.testing.assert_array_equal(store.peek_rows(keys), want)
    assert not np.array_equal(want[:10], first[:10])
    np.testing.assert_array_equal(want[10:], first[10:])
    store.write_back(keys, first)
    ws.end_pass(store, only_touched=False)          # the whole table
    np.testing.assert_array_equal(store.peek_rows(keys), want)


# ---------------------------------------------------------------------------
# (d) one trainer pass
# ---------------------------------------------------------------------------

def _train_one_pass(dim=128):
    from paddlebox_tpu.data import DataFeedSchema, SlotDataset
    from paddlebox_tpu.data.parser import parse_multislot_lines
    from paddlebox_tpu.models import DLRMModel
    from paddlebox_tpu.train import Trainer, TrainerConfig

    num_slots, vocab = 3, 40
    rng = np.random.default_rng(21)
    schema = DataFeedSchema.ctr(num_sparse=num_slots, num_float=2,
                                batch_size=16, max_len=1)
    lines = []
    for _ in range(80):
        parts = [f"1 {int(rng.random() < 0.3)}", f"1 {rng.normal():.4f}",
                 f"1 {rng.normal():.4f}"]
        for s in range(num_slots):
            parts.append(f"1 {rng.integers(0, vocab) + s * 1000003}")
        lines.append(" ".join(parts))
    ds = SlotDataset(schema)
    ds.records = parse_multislot_lines(lines, schema)
    store = HostEmbeddingStore(_cfg("adagrad", dim=dim))
    model = DLRMModel(num_slots=num_slots, emb_dim=dim, dense_dim=2,
                      bottom_hidden=(16,), top_hidden=(16, 8),
                      use_cvm=False)
    tr = Trainer(model, store, schema, make_mesh(1),
                 TrainerConfig(global_batch_size=16), seed=3)
    out = tr.train_pass(ds)
    engines = tr.engines()
    tr.flush_sparse()
    keys = np.unique(ds.unique_keys())
    return out, store.peek_rows(keys), engines, tr


def test_trainer_pass_matches_one_array_trajectory(monkeypatch,
                                                   restore_flags):
    flags.push_engine = "scatter_accumulate"    # the chip's `auto`, here
    out_p, rows_p, eng_p, tr = _train_one_pass()
    with one_array(monkeypatch):
        out_1, rows_1, eng_1, _ = _train_one_pass()
    assert tr.push_overlap and tr._use_plan     # the deferred, planned push
    rows = eng_p["table_shape"][0]
    assert eng_p["push_engine"] == "scatter_accumulate"
    assert eng_p["table_shape"] == [rows, 133] == eng_1["table_shape"]
    assert eng_p["plane_shapes"] == [[rows, 5], [rows, 128]]
    assert eng_1["plane_shapes"] == [[rows, 133]]
    assert out_p["steps"] == out_1["steps"] == 5
    np.testing.assert_allclose(out_p["losses"], out_1["losses"], rtol=1e-6)
    np.testing.assert_allclose(rows_p, rows_1, rtol=1e-5, atol=1e-7)
    np.testing.assert_array_equal(rows_p[:, :2], rows_1[:, :2])
    assert rows_p[:, 0].sum() == 80 * 3         # every token counted once


def test_trainer_narrow_rows_keep_the_one_array(restore_flags):
    _, _, eng, tr = _train_one_pass(dim=8)
    assert eng["plane_shapes"] == [eng["table_shape"]]
    assert eng["table_shape"][1] == 13
    assert eng["push_engine"] == "xla_scatter"


# ---------------------------------------------------------------------------
# the routed apply: one rule on every mesh
# ---------------------------------------------------------------------------

def test_routed_apply_on_planes_two_shards(restore_flags):
    """On a two-device mesh a plane table takes the same touched-rows
    apply at the tail of the sharded exchange (premerged per source,
    merged across devices, then scatter_accumulate on each shard's
    planes) — equal to the one-shard push on one array."""
    mesh2 = make_mesh(2)
    cfg = _cfg("adagrad")
    store = HostEmbeddingStore(cfg)
    keys = np.random.default_rng(7).choice(
        1 << 40, 60, replace=False).astype(np.uint64)
    ws = PassWorkingSet.begin_pass(store, keys, mesh2)
    assert quant.is_planes(ws.table) and ws.n_shards == 2
    idx, grads, shows, clks = _tokens(cfg, ws.num_keys + 1, 64, seed=8)
    args = tuple(map(jnp.asarray, (idx, grads, shows, clks)))
    want = np.asarray(sharded.push(jnp.asarray(_as_rows(ws.table, cfg)),
                                   *args, cfg))
    parts = [dedup_plan(a, ws.padded_rows, ws.padded_rows, 1)
             for a in idx.reshape(2, -1)]
    Z = jnp.zeros(0, jnp.int32)
    plan = (jnp.asarray(np.concatenate([p[0] for p in parts])), Z, Z,
            jnp.asarray(np.concatenate([p[1] for p in parts])),
            jnp.asarray(np.concatenate([p[2] for p in parts])))
    flags.push_engine = "scatter_accumulate"
    assert exchange._scatter_engine(ws.table, cfg, ws.rows_per_shard)

    def body(tshard, i, g, sh, ck, *p):
        return exchange.routed_push(tshard, i, g, sh, ck, cfg, ("dp",),
                                    2.0, wire="f32", plan=p)

    out = jax.jit(jax.shard_map(
        body, mesh=mesh2, in_specs=(P("dp"),) * 10,
        out_specs=P("dp")))(ws.table, *args, *plan)
    got = _as_rows(out, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    np.testing.assert_array_equal(got[:, :2], want[:, :2])
    pulled = jax.jit(jax.shard_map(
        lambda t, i: sharded.routed_lookup(t, i, cfg, ("dp",)),
        mesh=mesh2, in_specs=(P("dp"), P("dp")), out_specs=P("dp")))(
        out, jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(pulled),
                                  got[idx][:, :cfg.pull_width])


# ---------------------------------------------------------------------------
# (g) a boundary that moves a handful of rows meets one compiled shape
# ---------------------------------------------------------------------------

def test_bucket_size_small_counts_share_one_bucket():
    assert working_set.bucket_size(0) == 0
    assert {working_set.bucket_size(x) for x in range(1, 17)} == {16}
    assert working_set.bucket_size(17) == 20
    # the counts of a large pass keep their buckets
    assert working_set.bucket_size(5285) == 6144
    assert working_set.bucket_size(5000) == 5120


def test_boundary_pad_is_one_bucket_for_counts_of_a_size():
    pad = feed_pass.boundary_pad
    # two key sets in turn swap the counts: either order, one bucket —
    # also where the draw puts them either side of a bucket's edge
    assert pad(5116, 5343) == pad(5343, 5116) == 6144
    assert pad(0, 3) == pad(3, 0) == pad(0, 0) == pad(16, 1) == 16
    assert pad(5285, 5290) == 6144
    # lopsided churn pays for no padding: the fresh rows' own bucket
    assert pad(10, 100_000) == 16 and pad(100_000, 10) == 114688


@pytest.mark.parametrize("dim", [128, 8])
def test_boundary_of_a_few_rows_meets_one_compiled_shape(dim, restore_flags):
    """A table that every pass nearly fills (a vocabulary): passes that
    admit and retire 0, 1, 3 or 16 rows run the fresh-row staging, the
    write-back of the retiring rows and jit_combine without compiling
    anything the first such boundary did not."""
    from paddlebox_tpu.utils.compile_cache import CompileMeter
    cfg = _cfg("adagrad", dim=dim)
    store = HostEmbeddingStore(cfg)
    mgr = FeedPassManager(store, make_mesh(1))
    rng = np.random.default_rng(11)
    pool = rng.choice(1 << 40, 400, replace=False).astype(np.uint64)
    # 196..212 keys through the test: one bucket (224) of table rows
    keys, spare = pool[:208], list(pool[208:])
    meter = CompileMeter()

    def one_pass(keys):
        ws = mgr.begin_pass(keys)
        mgr.pass_opened()
        ws.translate(keys.reshape(1, -1))        # every row touched
        mgr.pass_closed()
        mgr.end_pass(ws)
        return mgr.last_fresh_rows

    def churn(keys, fresh, retire):
        keep = keys[retire:] if retire else keys
        new = np.asarray([spare.pop() for _ in range(fresh)], np.uint64)
        return np.concatenate([keep, new])

    one_pass(keys)
    keys = churn(keys, 2, 0)     # the first such boundary retires no row:
    one_pass(keys)               # its write-back program is met all the same
    warm = meter.snapshot()
    for fresh, retire in ((1, 1), (3, 3), (16, 16), (0, 0), (3, 1),
                          (0, 16), (16, 0), (1, 3)):
        keys = churn(keys, fresh, retire)
        assert one_pass(keys) == fresh
        assert meter.since(warm)["compilations"] == 0, (fresh, retire)
