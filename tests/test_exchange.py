"""Sharded embedding exchange (embedding/exchange.py + the trainer's
table_layout=sharded engine + ShardedEmbeddingStore).

Bitwise-parity discipline: gathers move exact bits, so PULL parity is
asserted bit-for-bit on arbitrary rows. PUSH parity is asserted
bit-for-bit under EXACT arithmetic — lattice grads (multiples of 2^-10,
bounded) and a power-of-two SGD learning rate keep every sum and update
exactly representable, so ANY merge order yields identical bits and the
comparison pins routing/dedup/premerge/wire delivery exactly: a
misrouted, duplicated, or dropped lane shows as a large error, not a
rounding one. With adagrad the optimizer's sqrt/divide compiles to
different fusions under shard_map vs plain jit (1-ulp variance, present
in the LEGACY routed path too — verified while building this suite), so
the adagrad companion bounds at allclose.
"""

import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from paddlebox_tpu import monitor
from paddlebox_tpu.config import flags, set_flags
from paddlebox_tpu.data import DataFeedSchema, SlotDataset
from paddlebox_tpu.data.slot_record import SlotRecordBatch
from paddlebox_tpu.embedding import (EmbeddingConfig, HostEmbeddingStore,
                                     PassWorkingSet, ShardedEmbeddingStore,
                                     exchange, sharded)
from paddlebox_tpu.embedding.feed_pass import FeedPassManager
from paddlebox_tpu.models import DeepFMModel
from paddlebox_tpu.native.key_index import dedup_plan
from paddlebox_tpu.parallel import make_mesh
from paddlebox_tpu.train import Trainer, TrainerConfig
from paddlebox_tpu.utils import faultpoint


@pytest.fixture(scope="module")
def mesh2():
    return make_mesh(2)


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(4)


def _cfg(**kw):
    kw.setdefault("dim", 4)
    kw.setdefault("optimizer", "sgd")
    kw.setdefault("learning_rate", 0.0625)   # power of two: exact step
    return EmbeddingConfig(**kw)


def _ws(cfg, n_keys, mesh):
    store = HostEmbeddingStore(cfg)
    keys = np.random.default_rng(7).choice(
        1 << 40, size=n_keys, replace=False).astype(np.uint64)
    return store, PassWorkingSet.begin_pass(store, keys, mesh)


def _device_plans(idx_flat: np.ndarray, n_rows: int, n_dev: int):
    """Per-device dedup plans concatenated along dim 0 — exactly what
    Trainer._host_plan stages for the sharded engine (shard_map splits
    every plan array into contiguous per-device slices)."""
    parts = [dedup_plan(a, n_rows, n_rows, 1)
             for a in idx_flat.reshape(n_dev, -1)]
    Z = jnp.zeros(0, jnp.int32)
    return (jnp.asarray(np.concatenate([p[0] for p in parts])), Z, Z,
            jnp.asarray(np.concatenate([p[1] for p in parts])),
            jnp.asarray(np.concatenate([p[2] for p in parts])))


def _lattice_grads(rng, n, width):
    """Exact-arithmetic payloads: multiples of 2^-10 bounded by 0.5 —
    every sum of a few hundred stays exactly representable in f32, so
    summation order cannot change bits."""
    return (rng.integers(-512, 512, size=(n, width)) / 1024.0
            ).astype(np.float32)


# ---------------------------------------------------------------------------
# op-level parity (the acceptance bar: 2-shard routed exchange
# bit-identical to the single-shard path on identical data)
# ---------------------------------------------------------------------------

def test_pull_bit_identical_2shard(mesh2):
    c = _cfg()
    store, ws = _ws(c, 100, mesh2)
    rng = np.random.default_rng(3)
    idx = rng.integers(0, ws.num_keys + 1, size=64).astype(np.int32)
    plan = _device_plans(idx, ws.padded_rows, 2)

    def body(tshard, i, *p):
        # the per-shard drop count is psummed to the global one, as the
        # trainer's step does — that is what makes it replicated (P())
        out, dropped = exchange.routed_pull(tshard, i, c, ("dp",), 2.0,
                                            plan=p, return_dropped=True)
        return out, lax.psum(dropped, "dp")

    out, dropped = jax.jit(jax.shard_map(
        body, mesh=mesh2, in_specs=(P("dp"),) * 7,
        out_specs=(P("dp"), P())))(ws.table, jnp.asarray(idx), *plan)
    want = np.asarray(sharded.lookup(ws.table, jnp.asarray(idx), c))
    assert int(dropped) == 0
    np.testing.assert_array_equal(np.asarray(out), want)


def test_plan_dedup_indices_reconstructs():
    idx = np.array([5, 3, 5, 0, 9, 3, 3, 12], np.int32)
    o, u, s, _r, _e = dedup_plan(idx, 16, 16, 1)
    Z = jnp.zeros(0, jnp.int32)
    dplan = tuple(jnp.asarray(a) for a in (o, np.zeros(0, np.int32),
                                           np.zeros(0, np.int32), u, s))
    uniq, inverse = exchange.plan_dedup_indices(
        (dplan[0], dplan[1], dplan[2], dplan[3], dplan[4]))
    np.testing.assert_array_equal(
        np.asarray(uniq)[np.asarray(inverse)], idx)


def test_pull_pooled_bit_identical_2shard(mesh2):
    """The fused gather-pool pull per shard after routing: the pooled
    sums over the received lanes match the single-shard fused path
    bit-for-bit (same gathered values summed in the same slot order)."""
    c = _cfg()
    store, ws = _ws(c, 80, mesh2)
    rng = np.random.default_rng(5)
    B, S, L = 8, 4, 2
    idx = rng.integers(0, ws.num_keys + 1, size=(B, S * L)).astype(np.int32)
    idx[rng.random(idx.shape) < 0.3] = 0        # mask-nulled padding
    plan = _device_plans(idx.reshape(-1), ws.padded_rows, 2)

    def body(tshard, i, *p):
        pooled, dropped = exchange.routed_pull_pooled(
            tshard, i, c, ("dp",), S, L, 2.0, plan=p, return_dropped=True)
        return pooled, lax.psum(dropped, "dp")

    pooled, dropped = jax.jit(jax.shard_map(
        body, mesh=mesh2, in_specs=(P("dp"),) * 7,
        out_specs=(P("dp"), P())))(ws.table, jnp.asarray(idx), *plan)
    want = np.asarray(sharded.fused_pull_pool(ws.table, jnp.asarray(idx),
                                              c, S, L))
    assert int(dropped) == 0
    np.testing.assert_array_equal(np.asarray(pooled), want)


def _push_operands(c, ws, n_tok=64, seed=4):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, ws.num_keys + 1, size=n_tok).astype(np.int32)
    grads = _lattice_grads(rng, n_tok, c.grad_width)
    shows = (idx > 0).astype(np.float32)
    clks = (rng.integers(0, 2, n_tok) * shows).astype(np.float32)
    grads[idx == 0] = 0.0                       # null rows carry zeros
    return idx, grads, shows, clks


def test_push_bit_identical_2shard_exact(mesh2):
    """Plan-keyed, premerged-before-route push over the f32 wire equals
    the single-shard push bit-for-bit under exact arithmetic."""
    c = _cfg()
    store, ws = _ws(c, 60, mesh2)
    idx, grads, shows, clks = _push_operands(c, ws)
    plan = _device_plans(idx, ws.padded_rows, 2)
    args = tuple(map(jnp.asarray, (idx, grads, shows, clks)))

    def body(tshard, i, g, sh, ck, *p):
        return exchange.routed_push(tshard, i, g, sh, ck, c, ("dp",),
                                    2.0, wire="f32", plan=p)

    out = jax.jit(jax.shard_map(
        body, mesh=mesh2, in_specs=(P("dp"),) * 10,
        out_specs=P("dp")))(ws.table, *args, *plan)
    want = np.asarray(sharded.push(ws.table, *args, c))
    np.testing.assert_array_equal(np.asarray(out), want)


def test_push_premerged_deferred_bit_identical(mesh2):
    """The deferred-apply form: the step premerges onto unique lanes
    (deferred_push_operands) and the apply routes the premerged lanes —
    bit-identical to the inline exchange under exact arithmetic."""
    c = _cfg()
    store, ws = _ws(c, 60, mesh2)
    idx, grads, shows, clks = _push_operands(c, ws, seed=9)
    plan = _device_plans(idx, ws.padded_rows, 2)
    args = tuple(map(jnp.asarray, (idx, grads, shows, clks)))

    def inline(tshard, i, g, sh, ck, *p):
        return exchange.routed_push(tshard, i, g, sh, ck, c, ("dp",),
                                    2.0, wire="f32", plan=p)

    def deferred(tshard, i, g, sh, ck, *p):
        mg, ms, mc = sharded.deferred_push_operands(i, g, sh, ck, p)
        return exchange.routed_push(tshard, p[3], mg, ms, mc, c, ("dp",),
                                    2.0, wire="f32", premerged=True)

    a = jax.jit(jax.shard_map(inline, mesh=mesh2,
                              in_specs=(P("dp"),) * 10,
                              out_specs=P("dp")))(ws.table, *args, *plan)
    b = jax.jit(jax.shard_map(deferred, mesh=mesh2,
                              in_specs=(P("dp"),) * 10,
                              out_specs=P("dp")))(ws.table, *args, *plan)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_push_adagrad_close(mesh2):
    """Adagrad companion: the optimizer's sqrt/divide fuses differently
    under shard_map vs plain jit (1-ulp program variance, present in the
    legacy routed path too) — the exchange stays within float noise."""
    c = _cfg(optimizer="adagrad", learning_rate=0.05)
    store, ws = _ws(c, 60, mesh2)
    idx, grads, shows, clks = _push_operands(c, ws, seed=11)
    plan = _device_plans(idx, ws.padded_rows, 2)
    args = tuple(map(jnp.asarray, (idx, grads, shows, clks)))
    out = jax.jit(jax.shard_map(
        lambda t, i, g, sh, ck, *p: exchange.routed_push(
            t, i, g, sh, ck, c, ("dp",), 2.0, wire="f32", plan=p),
        mesh=mesh2, in_specs=(P("dp"),) * 10,
        out_specs=P("dp")))(ws.table, *args, *plan)
    want = np.asarray(sharded.push(ws.table, *args, c))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("wire,rtol", [("bf16", 2e-2), ("int8", 2e-2)])
def test_push_wire_compression_bounded(mesh2, wire, rtol):
    """Compressed wires: grads round (bf16 mantissa / int8 per-lane
    scale) but show/clk counter increments stay EXACT — counters must
    never round."""
    c = _cfg()
    store, ws = _ws(c, 60, mesh2)
    idx, grads, shows, clks = _push_operands(c, ws, seed=13)
    plan = _device_plans(idx, ws.padded_rows, 2)
    args = tuple(map(jnp.asarray, (idx, grads, shows, clks)))
    out = np.asarray(jax.jit(jax.shard_map(
        lambda t, i, g, sh, ck, *p: exchange.routed_push(
            t, i, g, sh, ck, c, ("dp",), 2.0, wire=wire, plan=p),
        mesh=mesh2, in_specs=(P("dp"),) * 10,
        out_specs=P("dp")))(ws.table, *args, *plan))
    want = np.asarray(sharded.push(ws.table, *args, c))
    np.testing.assert_allclose(out, want, rtol=rtol, atol=rtol)
    # counters crossed the f32 side plane: bit-exact show/clk columns
    np.testing.assert_array_equal(out[:, :2], want[:, :2])


def test_select_wire_and_bytes():
    c = _cfg()
    old = flags.exchange_wire
    try:
        flags.exchange_wire = "auto"
        assert exchange.select_wire(c) == "bf16"
        assert exchange.select_wire(_cfg(storage="int8")) == "int8"
        flags.exchange_wire = "f32"
        assert exchange.select_wire(c) == "f32"
        flags.exchange_wire = "nope"
        with pytest.raises(ValueError, match="exchange_wire"):
            exchange.select_wire(c)
    finally:
        flags.exchange_wire = old
    # wire accounting: bf16 halves the grad plane, int8 quarters it
    f32b = exchange.push_wire_bytes(c, 100, "f32")
    bfb = exchange.push_wire_bytes(c, 100, "bf16")
    i8b = exchange.push_wire_bytes(c, 100, "int8")
    gw = c.grad_width
    assert f32b - bfb == 100 * 2 * gw
    assert f32b - i8b == 100 * (3 * gw - 4)     # minus the scale column
    assert exchange.pull_wire_bytes(c, 100) == 100 * (4 + 4 * c.pull_width)


# ---------------------------------------------------------------------------
# trainer engine
# ---------------------------------------------------------------------------

def _dataset(n_ex, num_slots=4, batch=32, seed=0, key_space=400,
             skew=False):
    schema = DataFeedSchema.ctr(num_sparse=num_slots, num_float=1,
                                batch_size=batch, max_len=1)
    rng = np.random.default_rng(seed)
    offs = np.arange(n_ex + 1, dtype=np.int64)
    if skew:
        # DISTINCT contiguous keys per batch: lands on 1-2 shards and
        # dedup cannot shrink it — the capacity worst case
        e = np.arange(n_ex, dtype=np.int64)
        sv = [(e // batch) * 100_000 + (e % batch) * num_slots + s
              for s in range(num_slots)]
    else:
        sv = [(rng.integers(0, key_space, size=n_ex)
               | (np.int64(s + 1) << 40)).astype(np.int64)
              for s in range(num_slots)]
    ds = SlotDataset(schema)
    ds.records = SlotRecordBatch(
        schema=schema, num=n_ex, sparse_values=sv,
        sparse_offsets=[offs.copy() for _ in range(num_slots)],
        float_values=[(rng.random(n_ex) < 0.3).astype(np.float32),
                      rng.normal(size=n_ex).astype(np.float32)],
        ins_id=np.zeros(n_ex, np.uint64),
        search_id=np.zeros(n_ex, np.uint64),
        rank=np.zeros(n_ex, np.int32), cmatch=np.zeros(n_ex, np.int32))
    return ds, schema


def _trainer(schema, mesh, **cfg_kw):
    store = HostEmbeddingStore(EmbeddingConfig(dim=4, learning_rate=0.05))
    cfg_kw.setdefault("global_batch_size", 32)
    return Trainer(DeepFMModel(num_slots=4, emb_dim=4, dense_dim=1,
                               hidden=(8,)),
                   store, schema, mesh, TrainerConfig(**cfg_kw))


@pytest.fixture
def sharded_flags():
    set_flags(table_layout="sharded", exchange_wire="f32")
    try:
        yield
    finally:
        set_flags(table_layout="auto", exchange_wire="auto")


def test_trainer_sharded_engine_end_to_end(mesh2, sharded_flags):
    """The sharded engine trains and evals on a 2-shard mesh: plan-keyed
    exchange engaged, traffic counters populated (dedup ratio < 1),
    flight record carrying the engine identity, zero drops."""
    ds, schema = _dataset(4 * 32)
    tr = _trainer(schema, mesh2)
    assert tr.table_layout == "sharded"
    assert tr.exchange_wire == "f32"
    assert tr._use_plan                      # plan-keyed a2a engaged
    h = monitor.hub()
    h.disable()
    ms = monitor.MemorySink()
    h.enable(ms)
    try:
        snap0 = monitor.STATS.snapshot()
        out = tr.train_pass(ds)
        flights = [r for r in ms.records
                   if r.get("type") == "flight_record"]
    finally:
        h.disable()
    assert out["routed_dropped"] == 0
    assert out["steps"] == 4
    snap = monitor.STATS.snapshot()
    toks = snap["exchange.tokens"] - snap0.get("exchange.tokens", 0)
    uniq = snap["exchange.unique_lanes"] - snap0.get(
        "exchange.unique_lanes", 0)
    assert toks == 4 * 32 * 4
    assert 0 < uniq <= toks
    assert snap["exchange.pull_bytes"] > snap0.get(
        "exchange.pull_bytes", 0)
    assert snap["exchange.push_bytes"] > snap0.get(
        "exchange.push_bytes", 0)
    # the engine identity + the exchange counters ride the flight record
    assert flights
    assert flights[-1]["extra"]["table_layout"] == "sharded"
    assert flights[-1]["extra"]["exchange_wire"] == "f32"
    assert flights[-1]["stats_delta"].get("exchange.tokens") == toks
    ev = tr.eval_pass(ds)
    assert ev["routed_dropped"] == 0
    assert np.isfinite(ev["auc"])


def test_trainer_sharded_emits_exchange_flow_points(mesh2, sharded_flags):
    """World trace (ISSUE 15): a traced pass on the sharded engine
    stamps one deterministic exchange flow point per step — the
    cross-rank edge anchor — with the wire identity riding along."""
    from paddlebox_tpu.config import flags
    from paddlebox_tpu.monitor import flight
    ds, schema = _dataset(4 * 32)
    tr = _trainer(schema, mesh2)
    h = monitor.hub()
    h.disable()
    ms = monitor.MemorySink()
    prev = flags.trace
    flags.trace = True
    h.enable(ms)
    try:
        out = tr.train_pass(ds)
    finally:
        h.disable()
        flags.trace = prev
    flows = [r for r in ms.records if r.get("name") == "trace.flow"]
    ex = [r for r in flows
          if (r.get("fields") or {}).get("kind") == "exchange"]
    assert len(ex) == out["steps"]
    keys = [(r["fields"]["key"]) for r in ex]
    assert len(set(keys)) == len(keys)        # one per step, distinct
    assert all(k.startswith("p") and ".s" in k for k in keys)
    for r in ex:
        assert r["fields"]["wire"] == "f32"
        assert r["fields"]["tokens"] == 32 * 4
        assert r["fields"]["bytes_bound"] > 0
        assert r["trace_id"]                  # stamped, mergeable
        assert flight.validate_event(r) == []


def test_trainer_sharded_matches_single_shard_loss(mesh2, sharded_flags):
    """Same data through the 2-shard exchange engine and a single-shard
    trainer: losses agree to float tolerance (dense pmean over 2 devices
    reassociates the batch mean, so bitwise equality is not defined at
    trainer level — the op-level tests above carry the bitwise bar)."""
    ds, schema = _dataset(4 * 32, seed=2)
    tr2 = _trainer(schema, mesh2)
    out2 = tr2.train_pass(ds)
    set_flags(table_layout="auto")
    tr1 = _trainer(schema, make_mesh(1))
    assert tr1.table_layout == "single"
    out1 = tr1.train_pass(ds)
    assert out2["routed_dropped"] == 0
    np.testing.assert_allclose(out2["loss_mean"], out1["loss_mean"],
                               rtol=1e-4)
    np.testing.assert_allclose(out2["auc"], out1["auc"], atol=5e-3)


def test_overflow_never_silent_and_retry(mesh4, sharded_flags):
    """Capacity overflow accounting end to end: with the preplan off and
    a skewed pass, drops are counted (exchange.overflow_dropped), the
    capacity factor doubles, and the NEXT pass trains losslessly (the
    trainer-level retry at a larger factor). The eval pass retries IN
    PLACE: its returned numbers are from the lossless re-run."""
    old = flags.routed_capacity_preplan
    flags.routed_capacity_preplan = False
    try:
        ds, schema = _dataset(4 * 32, skew=True)
        tr = _trainer(schema, mesh4)
        snap0 = monitor.STATS.snapshot()
        with pytest.warns(UserWarning, match="exceeded all_to_all"):
            out = tr.train_pass(ds)
        assert out["routed_dropped"] > 0
        snap = monitor.STATS.snapshot()
        assert (snap.get("exchange.overflow_dropped", 0)
                - snap0.get("exchange.overflow_dropped", 0)) \
            == out["routed_dropped"]
        assert tr.cfg.capacity_factor == 4.0     # doubled
        with warnings.catch_warnings():
            warnings.simplefilter("error")       # retry pass: no drops
            out2 = tr.train_pass(ds)
        assert out2["routed_dropped"] == 0
        # eval overflow: fresh trainer at the small factor; the eval
        # pass must re-run itself and return LOSSLESS numbers
        tr_e = _trainer(schema, mesh4)
        r0 = monitor.STATS.snapshot().get("exchange.overflow_retries", 0)
        with pytest.warns(UserWarning, match="exceeded all_to_all"):
            ev = tr_e.eval_pass(ds)
        assert ev["routed_dropped"] == 0         # the RETURNED run is clean
        assert monitor.STATS.snapshot()["exchange.overflow_retries"] > r0
        # the retry window is a registered fault point
        tr_f = _trainer(schema, mesh4)
        faultpoint.arm("exchange.eval.pre_retry", "ioerror")
        try:
            with pytest.raises(faultpoint.FaultInjected):
                with pytest.warns(UserWarning):
                    tr_f.eval_pass(ds)
        finally:
            faultpoint.disarm()
    finally:
        flags.routed_capacity_preplan = old


def test_sharded_layout_forced_on_single_shard_raises():
    ds, schema = _dataset(32)
    set_flags(table_layout="sharded")
    try:
        with pytest.raises(ValueError, match="multi-device"):
            _trainer(schema, make_mesh(1))
    finally:
        set_flags(table_layout="auto")


# ---------------------------------------------------------------------------
# ShardedEmbeddingStore (the host plane of the partitioned table)
# ---------------------------------------------------------------------------

def test_sharded_store_protocol_parity():
    c = _cfg()
    ss = ShardedEmbeddingStore(c, 4)
    href = HostEmbeddingStore(c)
    keys = np.random.default_rng(1).choice(
        1 << 60, size=200, replace=False).astype(np.uint64)
    # deterministic per-key init: identical rows regardless of partition
    np.testing.assert_array_equal(ss.lookup_or_init(keys),
                                  href.lookup_or_init(keys))
    assert len(ss) == len(href) == 200
    owner = ss.shard_of(keys)
    assert owner.min() >= 0 and owner.max() < 4
    assert len(set(owner.tolist())) > 1          # really partitioned
    rows = ss.get_rows(keys)
    rows[:, 2] = 7.5
    ss.write_back(keys, rows)
    np.testing.assert_array_equal(ss.get_rows(keys)[:, 2], 7.5)
    # peek never grows
    ss.peek_rows(np.array([123456789], np.uint64))
    assert len(ss) == 200


def test_sharded_store_save_load_roundtrip(tmp_path):
    c = _cfg()
    ss = ShardedEmbeddingStore(c, 3)
    keys = np.arange(1, 101, dtype=np.uint64) * 0x1234567890ab
    ss.lookup_or_init(keys)
    ss.save_base(str(tmp_path))
    rows = ss.get_rows(keys)
    rows[:, 2] = 42.0
    ss.write_back(keys[:50], rows[:50])
    ss.save_delta(str(tmp_path))
    assert ss.save_seq == 1
    s2 = ShardedEmbeddingStore.load(str(tmp_path))
    assert len(s2) == 100 and s2.n_shards == 3
    np.testing.assert_array_equal(s2.get_rows(keys), ss.get_rows(keys))
    assert sorted(n for n in os.listdir(tmp_path)
                  if n.startswith("shard-")) == \
        ["shard-00", "shard-01", "shard-02"]


def test_sharded_store_crash_rolls_whole_save_back(tmp_path):
    """A kill before the top-level manifest commit (or mid shard loop)
    must leave the restore on the LAST COMMITTED save — orphaned newer
    shard files are invisible (the save_delta seq-commit discipline,
    lifted to the shard fan-out)."""
    c = _cfg()
    ss = ShardedEmbeddingStore(c, 2)
    keys = np.arange(1, 41, dtype=np.uint64) * 0x9876543210
    ss.lookup_or_init(keys)
    ss.save_base(str(tmp_path))
    base_rows = ss.get_rows(keys)
    rows = base_rows.copy()
    rows[:, 2] = 9.0
    ss.write_back(keys, rows)
    faultpoint.arm("exchange.store.pre_manifest", "ioerror")
    try:
        with pytest.raises(faultpoint.FaultInjected):
            ss.save_delta(str(tmp_path))
    finally:
        faultpoint.disarm()
    s2 = ShardedEmbeddingStore.load(str(tmp_path))
    np.testing.assert_array_equal(s2.get_rows(keys), base_rows)
    # mid-shard-loop kill: first shard's delta landed, second didn't
    ss2 = ShardedEmbeddingStore(c, 2)
    ss2.lookup_or_init(keys)
    ss2.save_base(str(tmp_path / "b"))
    r2 = ss2.get_rows(keys)
    r2[:, 2] = 11.0
    ss2.write_back(keys, r2)
    faultpoint.arm("exchange.store.pre_shard_save", "ioerror", after=1)
    try:
        with pytest.raises(faultpoint.FaultInjected):
            ss2.save_delta(str(tmp_path / "b"))
    finally:
        faultpoint.disarm()
    s3 = ShardedEmbeddingStore.load(str(tmp_path / "b"))
    # base state == the deterministic init rows the save captured
    np.testing.assert_array_equal(
        s3.get_rows(keys), ShardedEmbeddingStore(c, 2).lookup_or_init(keys))
    # a re-run of the interrupted save commits cleanly over the orphans
    ss2.write_back(keys, r2)
    ss2.save_delta(str(tmp_path / "b"))
    s4 = ShardedEmbeddingStore.load(str(tmp_path / "b"))
    np.testing.assert_array_equal(s4.get_rows(keys)[:, 2], 11.0)


def test_sharded_store_base_resave_crash_detected_loudly(tmp_path):
    """The documented caveat (HostEmbeddingStore.save_base, restated on
    the sharded wrapper): a BASE re-save into a directory already
    holding a chain, killed before the top manifest commit, resets the
    shard chains under a stale top manifest — load must fail LOUDLY
    (CheckpointCorruptError), never silently resurrect mixed state.
    Writers needing fall-back semantics rotate directories per base."""
    from paddlebox_tpu.utils.checkpoint import CheckpointCorruptError
    c = _cfg()
    ss = ShardedEmbeddingStore(c, 2)
    keys = np.arange(1, 31, dtype=np.uint64) * 0xabcdef
    ss.lookup_or_init(keys)
    ss.save_base(str(tmp_path))
    r = ss.get_rows(keys)
    r[:, 2] = 3.0
    ss.write_back(keys, r)
    ss.save_delta(str(tmp_path))
    faultpoint.arm("exchange.store.pre_manifest", "ioerror")
    try:
        with pytest.raises(faultpoint.FaultInjected):
            ss.save_base(str(tmp_path))      # re-save into the SAME dir
    finally:
        faultpoint.disarm()
    with pytest.raises(CheckpointCorruptError):
        ShardedEmbeddingStore.load(str(tmp_path))


def test_sharded_store_wrong_shard_count_rejected(tmp_path):
    c = _cfg()
    ss = ShardedEmbeddingStore(c, 2)
    ss.lookup_or_init(np.array([5, 6], np.uint64))
    ss.save_base(str(tmp_path))
    from paddlebox_tpu.utils.checkpoint import CheckpointCorruptError
    with pytest.raises(CheckpointCorruptError, match="shards"):
        ShardedEmbeddingStore(c, 4).restore(str(tmp_path))


def test_sharded_spill_substores_train_bit_identical(mesh2, sharded_flags,
                                                     tmp_path):
    """ISSUE 11 acceptance: a 2-shard ShardedEmbeddingStore whose
    sub-stores are SPILL-backed (memmap row file + pathologically tiny
    frequency-aware RAM cache) trains bit-identical to host-backed
    sub-stores through the sharded exchange engine — the tier is a
    storage choice, not a math change. Same compiled step, so the bar is
    exact bits on losses AND on every final store row, which pins the
    whole read/install/write-through/fault-in cycle."""
    from paddlebox_tpu.embedding.tiering import shard_store_factory
    ds, schema = _dataset(4 * 32, seed=5)
    results = {}
    for name in ("host", "spill"):
        factory = (None if name == "host" else shard_store_factory(
            tiering="spill", cache_rows=37,
            spill_dir=str(tmp_path / "spill")))
        store = ShardedEmbeddingStore(
            EmbeddingConfig(dim=4, learning_rate=0.05), 2,
            store_factory=factory)
        tr = Trainer(DeepFMModel(num_slots=4, emb_dim=4, dense_dim=1,
                                 hidden=(8,)),
                     store, schema, mesh2,
                     TrainerConfig(global_batch_size=32))
        assert tr.table_layout == "sharded"
        outs = [tr.train_pass(ds) for _ in range(2)]
        tr.flush_sparse()
        keys = np.sort(np.asarray(ds.unique_keys(), np.uint64))
        results[name] = (outs, store.get_rows(keys), tr)
    for p in range(2):
        np.testing.assert_array_equal(
            results["host"][0][p]["loss_mean"],
            results["spill"][0][p]["loss_mean"])
    np.testing.assert_array_equal(results["host"][1], results["spill"][1])
    # the spill tier really engaged: disk traffic + the tier identity
    spill_tr = results["spill"][2]
    assert spill_tr.table_tiering == "sharded+spill"
    subs = results["spill"][2].store._shards
    assert all(s.cache_misses > 0 for s in subs)
    assert all(s.spill_file_bytes > 0 for s in subs)


def test_sharded_store_spill_factory_checkpoint_roundtrip(tmp_path):
    """Spill-backed shards save/load through the per-shard chain dirs
    with the STREAMED payloads, and the loaded store reads back
    bit-identical through a fresh spill factory."""
    from paddlebox_tpu.embedding.tiering import shard_store_factory
    c = _cfg()
    mk = lambda sub: shard_store_factory(      # noqa: E731
        tiering="spill", cache_rows=13, spill_dir=str(tmp_path / sub))
    ss = ShardedEmbeddingStore(c, 2, store_factory=mk("a"))
    keys = np.arange(1, 301, dtype=np.uint64) * np.uint64(0x9E3779B9)
    ss.lookup_or_init(keys)
    ss.save_base(str(tmp_path / "ck"))
    rows = ss.get_rows(keys)
    rows[:, 2] = 6.5
    ss.write_back(keys[:150], rows[:150])
    ss.save_delta(str(tmp_path / "ck"))
    s2 = ShardedEmbeddingStore.load(str(tmp_path / "ck"),
                                    store_factory=mk("b"))
    assert s2.n_shards == 2
    np.testing.assert_array_equal(s2.get_rows(keys), ss.get_rows(keys))
    # really spill-backed on both sides
    assert all(s.spill_file_bytes > 0 for s in s2._shards)


def test_sharded_store_drives_working_set(mesh2):
    """Drop-in for the trainer stack: a pass working set builds from the
    sharded host store, trains nothing, and writes back through it."""
    c = _cfg()
    ss = ShardedEmbeddingStore(c, 2)
    mgr = FeedPassManager(ss, mesh2)
    keys = np.random.default_rng(2).choice(
        1 << 50, size=64, replace=False).astype(np.uint64)
    ws = mgr.begin_pass(keys)
    assert ws.num_keys == 64 and len(ss) == 64
    idx = ws.translate(keys)
    assert (idx > 0).all()
    mgr.end_pass(ws, ws.table)
    mgr.flush()
    np.testing.assert_array_equal(
        ss.get_rows(keys),
        np.asarray(ws.table)[idx.reshape(-1)][:, :c.row_width])
    mgr.close()


# ---------------------------------------------------------------------------
# self-adapting exchange (ISSUE 16): the D-way merge of the routed tail,
# the hierarchical topology, and the per-pass wire controller
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh4h():
    """2 hosts x 2 devices — the (node, dp) mesh the hier topology keys
    off (conftest forces 8 virtual CPU devices, so 4 are available)."""
    return make_mesh(4, num_nodes=2)


def test_merge_sorted_runs_matches_argsort_dedup():
    """The D-way merge is bit-equivalent to the stable-argsort dedup on
    row-wise ascending runs — including overflow-capped runs (ascending
    valid prefix + out-of-range pad tail, exactly what a capacity-capped
    receive buffer holds)."""
    rng = np.random.default_rng(17)
    for trial in range(10):
        D = int(rng.integers(2, 6))
        L = int(rng.integers(3, 40))
        runs = np.sort(rng.integers(0, 50, size=(D, L)), axis=1)
        if trial % 2:
            for r in range(D):          # capped run: pad tail stays sorted
                k = int(rng.integers(0, L + 1))
                runs[r, k:] = 64        # out-of-range, >= any valid row
        runs = jnp.asarray(np.ascontiguousarray(runs).astype(np.int32))
        u_m, inv_m = sharded.merge_sorted_runs(runs)
        u_a, inv_a = sharded.dedup_tokens(runs.reshape(-1))
        np.testing.assert_array_equal(np.asarray(u_m), np.asarray(u_a))
        np.testing.assert_array_equal(np.asarray(inv_m), np.asarray(inv_a))


def test_select_topology_resolution_and_errors():
    old = flags.exchange_topology
    try:
        flags.exchange_topology = "auto"
        assert exchange.select_topology((2,)) == "flat"
        assert exchange.select_topology((2, 2)) == "hier"
        assert exchange.select_topology((1, 4)) == "flat"   # degenerate axis
        flags.exchange_topology = "flat"
        assert exchange.select_topology((2, 2)) == "flat"
        flags.exchange_topology = "hier"
        assert exchange.select_topology((2, 2)) == "hier"
        with pytest.raises(ValueError, match="hier"):
            exchange.select_topology((4,))
        flags.exchange_topology = "ring"
        with pytest.raises(ValueError, match="exchange_topology"):
            exchange.select_topology((2, 2))
    finally:
        flags.exchange_topology = old


def test_hier_push_bit_identical_to_flat_and_single_shard(mesh4h):
    """The two-stage (intra-host shuffle, host-merge, inter-host) push
    over the f32 wire lands the exact bits of both the flat 4-way a2a
    and the single-shard scatter path — for the plan-keyed AND the
    planless (token-order) input."""
    c = _cfg()
    store, ws = _ws(c, 120, mesh4h)
    idx, grads, shows, clks = _push_operands(c, ws, n_tok=128, seed=21)
    plan = _device_plans(idx, ws.padded_rows, 4)
    args = tuple(map(jnp.asarray, (idx, grads, shows, clks)))
    axes = tuple(mesh4h.axis_names)

    def run(topology, use_plan):
        def body(tshard, i, g, sh, ck, *p):
            return exchange.routed_push(
                tshard, i, g, sh, ck, c, axes, 2.0, wire="f32",
                plan=p if use_plan else None, topology=topology)
        return np.asarray(jax.jit(jax.shard_map(
            body, mesh=mesh4h, in_specs=(P(axes),) * 10,
            out_specs=P(axes)))(ws.table, *args, *plan))

    want = np.asarray(sharded.push(ws.table, *args, c))
    for use_plan in (True, False):
        np.testing.assert_array_equal(run("flat", use_plan), want)
        np.testing.assert_array_equal(run("hier", use_plan), want)


@pytest.mark.parametrize("wire,rtol", [("bf16", 2e-2), ("int8", 2e-2)])
def test_hier_push_wire_compression_bounded(mesh4h, wire, rtol):
    """Compressed wires through the hier topology: grads round within
    the wire's tolerance, but the parity guard holds — show/clk counter
    columns cross the f32 side plane on BOTH legs and stay bit-exact."""
    c = _cfg()
    store, ws = _ws(c, 120, mesh4h)
    idx, grads, shows, clks = _push_operands(c, ws, n_tok=128, seed=23)
    plan = _device_plans(idx, ws.padded_rows, 4)
    args = tuple(map(jnp.asarray, (idx, grads, shows, clks)))
    axes = tuple(mesh4h.axis_names)
    out = np.asarray(jax.jit(jax.shard_map(
        lambda t, i, g, sh, ck, *p: exchange.routed_push(
            t, i, g, sh, ck, c, axes, 2.0, wire=wire, plan=p,
            topology="hier"),
        mesh=mesh4h, in_specs=(P(axes),) * 10,
        out_specs=P(axes)))(ws.table, *args, *plan))
    want = np.asarray(sharded.push(ws.table, *args, c))
    np.testing.assert_allclose(out, want, rtol=rtol, atol=rtol)
    np.testing.assert_array_equal(out[:, :2], want[:, :2])


def test_compress_push_side_plane_exact_on_every_wire():
    """The structural parity guard: whatever the wire does to the grad
    plane, the show/clk side plane survives compress->decompress
    bit-for-bit (int8 additionally rides its scale column there)."""
    rng = np.random.default_rng(29)
    gw = 5
    pay = jnp.asarray(rng.normal(size=(2, 16, gw + 2)).astype(np.float32))
    for wire in exchange.WIRES:
        planes = exchange._compress_push(pay, gw, wire)
        back = exchange._decompress_push(planes, wire)
        np.testing.assert_array_equal(np.asarray(back[..., gw:gw + 2]),
                                      np.asarray(pay[..., gw:gw + 2]))
        if wire != "f32":               # the grad plane really compressed
            assert planes[0].dtype != jnp.float32


def test_wire_cost_regimes_and_errors():
    c = _cfg()                          # grad_width 5
    # unique-heavy (depth ~1): bytes-bound, the narrow wire wins
    assert (exchange.wire_cost(c, 100, 100, "bf16")
            < exchange.wire_cost(c, 100, 100, "f32"))
    # duplication-heavy (depth 32): exposure-bound, the exact wire wins
    assert (exchange.wire_cost(c, 3200, 100, "f32")
            < exchange.wire_cost(c, 3200, 100, "bf16"))
    with pytest.raises(ValueError, match="wire"):
        exchange.wire_cost(c, 1, 1, "fp8")


def test_wire_controller_flips_within_hysteresis_no_flap():
    c = _cfg()
    ctl = exchange.WireController(c, "f32", hysteresis=2)
    for _ in range(3):                  # deep-dup regime: f32 optimal
        d = ctl.observe(3200, 100)
        assert d["wire"] == "f32" and d["reason"] == "optimal"
    # a single unique-heavy spike: challenger appears, hysteresis holds
    d = ctl.observe(100, 100)
    assert (d["candidate"] == "bf16" and not d["switched"]
            and d["wire"] == "f32" and d["streak"] == 1)
    # regime snaps back: the streak resets — no flap
    d = ctl.observe(3200, 100)
    assert d["reason"] == "optimal" and ctl.switches == 0
    # sustained drift: the flip lands on EXACTLY the hysteresis'th
    # consecutive challenger win, not earlier
    assert not ctl.observe(100, 100)["switched"]
    d = ctl.observe(100, 100)
    assert d["switched"] and d["wire"] == "bf16" and d["prev_wire"] == "f32"
    assert ctl.switches == 1 and ctl.wire == "bf16"


def test_wire_controller_on_a_drifting_stream_beats_every_pinned_wire():
    """A busy duplication-heavy head (depth 32, 6x the traffic) then a
    unique-heavy tail: the two passes the hysteresis spends on the old
    wire must cost less than any pinned wire loses over the stream."""
    c = _cfg()
    ctl = exchange.WireController(c, "f32", hysteresis=2)
    stream = [(6 * 3200, 6 * 100)] * 4 + [(100, 100)] * 5
    adaptive, pinned, path = 0.0, dict.fromkeys(exchange.WIRES, 0.0), []
    for tokens, unique in stream:
        path.append(ctl.wire)
        adaptive += exchange.wire_cost(c, tokens, unique, ctl.wire)
        for w in pinned:
            pinned[w] += exchange.wire_cost(c, tokens, unique, w)
        ctl.observe(tokens, unique)
    assert path == ["f32"] * 6 + ["bf16"] * 3 and ctl.switches == 1
    assert all(adaptive <= cost for cost in pinned.values()), (adaptive,
                                                               pinned)


def test_wire_controller_holds_on_overflow_flow_and_silence():
    c = _cfg()
    ctl = exchange.WireController(c, "f32", hysteresis=1)
    assert ctl.observe(0, 0)["reason"] == "no-traffic"
    d = ctl.observe(100, 100, overflow_retries=1)
    assert not d["switched"] and d["reason"] == "overflow-hold"
    # flow attribution says the exchange edge is not the limiter: hold
    quiet = {"edges": 4, "by_kind": {"exchange": {"max_latency_s": 0.01}}}
    d = ctl.observe(100, 100, flow=quiet, wall_seconds=10.0)
    assert not d["switched"] and d["reason"] == "not-limiter"
    # no exchange edge at all in the attribution: same hold
    d = ctl.observe(100, 100, flow={"edges": 4, "by_kind": {}},
                    wall_seconds=10.0)
    assert d["reason"] == "not-limiter"
    # the limiter signal present: the switch proceeds (hysteresis=1)
    hot = {"edges": 4, "by_kind": {"exchange": {"max_latency_s": 5.0}}}
    d = ctl.observe(100, 100, flow=hot, wall_seconds=10.0)
    assert d["switched"] and d["wire"] == "bf16"


def test_trainer_adaptive_wire_end_to_end(mesh2):
    """flags.exchange_adaptive on a drifting stream: duplication-heavy
    passes hold f32, then unique-heavy passes flip the wire to bf16 on
    exactly the hysteresis'th pass after the drift; the switch emits the
    registered exchange_wire_adapted event, bumps the switch counter,
    and every pass's flight record carries the exchange_wire /
    exchange_topology / exchange_wire_next extras through the schema."""
    from paddlebox_tpu.monitor import flight
    set_flags(table_layout="sharded", exchange_wire="f32",
              exchange_adaptive=True)
    try:
        dup, schema = _dataset(4 * 32, key_space=1, seed=3)
        uni, _ = _dataset(4 * 32, key_space=1 << 30, seed=4)
        tr = _trainer(schema, mesh2)
        assert tr._wire_controller is not None
        assert tr.exchange_topology == "flat"    # 1-axis mesh
        h = monitor.hub()
        h.disable()
        ms = monitor.MemorySink()
        h.enable(ms)
        try:
            sw0 = monitor.STATS.snapshot().get("exchange.wire_switches", 0)
            for _ in range(2):                   # dup regime: f32 holds
                tr.train_pass(dup)
                assert tr.exchange_wire == "f32"
            wires = []
            for _ in range(3):                   # the drift
                tr.train_pass(uni)
                wires.append(tr.exchange_wire)
        finally:
            h.disable()
        # hysteresis=2: pass 1 after the drift challenges, pass 2 flips
        assert wires == ["f32", "bf16", "bf16"]
        ev = [r for r in ms.records
              if r.get("name") == "exchange_wire_adapted"]
        assert len(ev) == 1
        f = ev[0]["fields"]
        assert f["prev"] == "f32" and f["wire"] == "bf16"
        assert f["streak"] == 2 and set(f["costs"]) == set(exchange.WIRES)
        assert flight.validate_event(ev[0]) == []
        assert (monitor.STATS.snapshot()["exchange.wire_switches"]
                - sw0) == 1
        flights = [r for r in ms.records
                   if r.get("type") == "flight_record"]
        assert len(flights) == 5
        for r in flights:
            assert flight.validate_flight_record(r) == []
            assert r["extra"]["exchange_topology"] == "flat"
        # the record carries the pass's ACTIVE wire and the controller's
        # verdict for the next one — the flip pass shows the handover
        assert [r["extra"]["exchange_wire"] for r in flights] \
            == ["f32"] * 4 + ["bf16"]
        assert flights[3]["extra"]["exchange_wire_next"] == "bf16"
        assert flights[-1]["extra"]["exchange_wire_next"] == "bf16"
    finally:
        set_flags(table_layout="auto", exchange_wire="auto",
                  exchange_adaptive=False)


def test_adaptive_wire_via_boxps_end_pass(mesh2):
    """Fleet-driven scopes adapt at BoxPS.end_pass(trainer=...) — the
    boundary mirror of the tier re-evaluation — and surface the next
    wire in the end_pass dict."""
    from paddlebox_tpu.fleet.boxps import BoxPS
    set_flags(table_layout="sharded", exchange_wire="f32",
              exchange_adaptive=True)
    try:
        uni, schema = _dataset(2 * 32, key_space=1 << 30, seed=6)
        tr = _trainer(schema, mesh2)
        tr._wire_controller.hysteresis = 1       # flip on first evidence
        box = BoxPS(tr.store)
        box.begin_pass()
        tr.train_pass(uni, metrics=box.metrics)
        out = box.end_pass(trainer=tr)
        assert out["exchange_wire_next"] == "bf16"
        assert tr.exchange_wire == "bf16"
    finally:
        set_flags(table_layout="auto", exchange_wire="auto",
                  exchange_adaptive=False)
