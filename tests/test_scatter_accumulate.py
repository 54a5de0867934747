"""Fused scatter-accumulate push vs the XLA scatter reference.

The Pallas kernel runs in interpret mode on CPU (like gather_pool /
binned_push); the reference is sharded.push's scatter engine — scatter-add
merge into a full-table accumulator + one fused update pass. The parity
discipline is test_exchange.py's: gathers and the row-wise optimizer move
exact bits, so parity is asserted bit-for-bit under EXACT arithmetic
(lattice grads + a power-of-two SGD step), pinning lane routing, the
premerge, pad skipping, and the in-kernel update exactly; an adagrad
companion bounds the compile-fusion ulp variance at allclose. Covers the
engine resolver (auto classes, forced values, quantized
tables filtered), the pad-clobber regression the predicated write-back
exists for, empty/all-pad batches, the 2-shard routed apply (premerged
lanes routed then cross-device-merged).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddlebox_tpu.config import flags
from paddlebox_tpu.embedding import (EmbeddingConfig, HostEmbeddingStore,
                                     PassWorkingSet, exchange, quant,
                                     sharded)
from paddlebox_tpu.native.key_index import dedup_plan
from paddlebox_tpu.ops import pallas_kernels as pk
from paddlebox_tpu.parallel import make_mesh


@pytest.fixture(scope="module")
def mesh2():
    return make_mesh(2)


@pytest.fixture()
def engine_flag():
    old = flags.push_engine
    yield
    flags.push_engine = old


def _cfg(**kw):
    kw.setdefault("dim", 4)
    kw.setdefault("optimizer", "sgd")
    kw.setdefault("learning_rate", 0.0625)   # power of two: exact step
    return EmbeddingConfig(**kw)


def _table(cfg, n_rows, seed=0, width=None):
    """Device table at `width` columns — by default whole 128-lane
    tiles, the form the kernel's row DMAs take (flags.table_pad_width).
    The pad columns carry junk: they must ride the update untouched."""
    rng = np.random.default_rng(seed)
    if width is None:
        width = -(-cfg.row_width // 128) * 128
    t = (rng.integers(-512, 512, size=(n_rows, width))
         / 1024.0).astype(np.float32)
    t[:, 0] = rng.integers(0, 20, size=n_rows)       # show
    t[:, 1] = rng.integers(0, 5, size=n_rows)        # clk
    t[0] = 0.0                                       # null-row contract
    return jnp.asarray(t)


def _tokens(cfg, n_rows, n_tok, seed=1, dup_mod=None):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n_rows, size=n_tok).astype(np.int32)
    if dup_mod:
        idx = (idx % dup_mod).astype(np.int32)
    grads = (rng.integers(-512, 512, size=(n_tok, cfg.grad_width))
             / 1024.0).astype(np.float32)
    shows = (idx > 0).astype(np.float32)
    clks = (rng.integers(0, 2, n_tok) * shows).astype(np.float32)
    grads[idx == 0] = 0.0                            # null rows carry zeros
    return idx, grads, shows, clks


def _premerged(cfg, idx, grads, shows, clks, n_rows):
    """Host dedup plan + device premerge — the lanes the fused engine
    consumes in production (one lane per unique row, pads out-of-range)."""
    o, u, s, r, e = dedup_plan(idx, n_rows, n_rows, 1)
    dplan = tuple(map(jnp.asarray, (o, np.zeros(0, np.int32),
                                    np.zeros(0, np.int32), u, s)))
    uniq, mg, ms, mc, kplan = sharded.plan_premerge(
        jnp.asarray(idx), jnp.asarray(grads), jnp.asarray(shows),
        jnp.asarray(clks), dplan)
    return uniq, mg, ms, mc, kplan


# ---------------------------------------------------------------------------
# kernel parity (interpret mode — hardware-free, SURVEY.md §4)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim,dup_mod", [
    (4, None),        # narrow
    (4, 8),           # duplicate-heavy (the multi-hot merge shape)
    (64, None),       # wide rows (the dim64 floor point's class)
])
def test_kernel_interpret_bit_identical_to_scatter(dim, dup_mod):
    c = _cfg(dim=dim)
    table = _table(c, 64)
    idx, grads, shows, clks = _tokens(c, 64, 300, dup_mod=dup_mod)
    ref = np.asarray(sharded.push(table, jnp.asarray(idx),
                                  jnp.asarray(grads), jnp.asarray(shows),
                                  jnp.asarray(clks), c))
    uniq, mg, ms, mc, _ = _premerged(c, idx, grads, shows, clks, 64)
    out = pk.scatter_accumulate(table, uniq, mg, ms, mc, c,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_kernel_matches_jnp_reference_bitwise():
    """The off-TPU production path (jnp reference) and the kernel are
    the same math — a drift between the two copies must fail here, not
    corrupt a CPU-validated run silently."""
    c = _cfg()
    table = _table(c, 64)
    idx, grads, shows, clks = _tokens(c, 64, 200, seed=5)
    uniq, mg, ms, mc, _ = _premerged(c, idx, grads, shows, clks, 64)
    out_k = pk.scatter_accumulate(table, uniq, mg, ms, mc, c,
                                  interpret=True)
    out_j = pk.scatter_accumulate(table, uniq, mg, ms, mc, c)  # jnp (CPU)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_j))


def test_kernel_adagrad_close():
    """Adagrad companion (the test_exchange discipline): sqrt/divide
    fuses differently across program shapes — bounded, not bitwise."""
    c = _cfg(optimizer="adagrad", learning_rate=0.05)
    table = _table(c, 64, seed=2)
    idx, grads, shows, clks = _tokens(c, 64, 200, seed=7)
    ref = np.asarray(sharded.push(table, jnp.asarray(idx),
                                  jnp.asarray(grads), jnp.asarray(shows),
                                  jnp.asarray(clks), c))
    uniq, mg, ms, mc, _ = _premerged(c, idx, grads, shows, clks, 64)
    out = pk.scatter_accumulate(table, uniq, mg, ms, mc, c,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5,
                               atol=1e-6)


def test_untouched_rows_keep_exact_bits_and_pads_never_write():
    """Rows no lane names keep their exact bits, and pad lanes (out of
    range OR zero-touch) never issue a write — including the clobber
    case the predicated write-back exists for: pads clamp their read to
    row 0 while a REAL row-0 lane updates it; an unconditional clamped
    write would race the real update with stale bits."""
    c = _cfg()
    n = 64
    table = _table(c, n, seed=3)
    # one real row-0 lane (zero payload — the premerged null lane), two
    # real rows, then out-of-range pads and an in-range zero-touch pad
    idx = np.array([0, 3, 9, n, n + 1, 0], np.int32)
    tch = np.array([1, 1, 1, 1, 1, 0], np.float32)
    grads = np.zeros((6, c.grad_width), np.float32)
    grads[1:3] = 0.25
    shows = np.array([0, 1, 1, 1, 1, 0], np.float32)
    clks = np.zeros(6, np.float32)
    for interpret in (True, None):       # kernel and jnp reference
        out = np.asarray(pk.scatter_accumulate(
            table, jnp.asarray(idx), jnp.asarray(grads),
            jnp.asarray(shows), jnp.asarray(clks), c,
            touched=jnp.asarray(tch), interpret=interpret))
        ref = np.asarray(sharded.push(
            table, jnp.asarray(idx[:3]), jnp.asarray(grads[:3]),
            jnp.asarray(shows[:3]), jnp.asarray(clks[:3]), c))
        np.testing.assert_array_equal(out, ref)
        # row 0 held its zero bits through the concurrent pad reads
        np.testing.assert_array_equal(out[0], 0.0)
        untouched = np.setdiff1d(np.arange(n), idx[:3])
        np.testing.assert_array_equal(out[untouched],
                                      np.asarray(table)[untouched])


def test_all_pad_batch_leaves_table_bit_identical():
    """A fully-masked batch premerges to the null lane + pads: the only
    write is row 0's zero-payload update, a fixed point — the table is
    bit-identical after the push (empty-batch contract)."""
    c = _cfg()
    table = _table(c, 64, seed=4)
    idx = np.zeros(100, np.int32)                 # every token masked
    grads = np.zeros((100, c.grad_width), np.float32)
    shows = np.zeros(100, np.float32)
    clks = np.zeros(100, np.float32)
    uniq, mg, ms, mc, _ = _premerged(c, idx, grads, shows, clks, 64)
    out = pk.scatter_accumulate(table, uniq, mg, ms, mc, c,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(table))


def test_padded_table_width_columns_pass_through():
    """Pad columns past row_width ride apply_updates untouched, same as
    the scatter engine — in the kernel at whole lane tiles, and in the
    jnp reference at any width (table_pad_width=row_width+5)."""
    c = _cfg()
    idx, grads, shows, clks = _tokens(c, 64, 120, seed=8)
    uniq, mg, ms, mc, _ = _premerged(c, idx, grads, shows, clks, 64)
    for width, interpret in ((None, True), (c.row_width + 5, None)):
        table = _table(c, 64, seed=6, width=width)
        ref = np.asarray(sharded.push(table, jnp.asarray(idx),
                                      jnp.asarray(grads),
                                      jnp.asarray(shows),
                                      jnp.asarray(clks), c))
        out = pk.scatter_accumulate(table, uniq, mg, ms, mc, c,
                                    interpret=interpret)
        np.testing.assert_array_equal(np.asarray(out), ref)
        np.testing.assert_array_equal(
            np.asarray(out)[:, c.row_width:],
            np.asarray(table)[:, c.row_width:])


def test_geometry_bounds():
    assert pk.scatter_accumulate_geometry(64, 128) is not None
    assert pk.scatter_accumulate_geometry(64, 512) is not None
    assert pk.scatter_accumulate_geometry(64, 640) is None   # width cap
    assert pk.scatter_accumulate_geometry(0, 128) is None
    # partial lane tiles: what the v5e compiler refuses
    # (tests/test_aot_tpu_compile.py) the geometry refuses too
    for width in (13, 37, 69, 133):
        assert pk.scatter_accumulate_geometry(64, width) is None
    # off-TPU the forced engine runs the jnp reference at any width
    assert pk.scatter_accumulate_supported(64, 13)
    assert not pk.scatter_accumulate_supported(64, 1024)


# ---------------------------------------------------------------------------
# engine resolver (THE selection function — compiled dispatch == record)
# ---------------------------------------------------------------------------

def test_resolver_forced(engine_flag):
    c = _cfg()
    flags.push_engine = "scatter_accumulate"
    assert pk.resolve_push_engine(c, 64, premerged=True) == \
        "scatter_accumulate"
    # the fused engine REQUIRES premerged unique lanes — forced
    # without them falls back to the scatter, reported truthfully
    assert pk.resolve_push_engine(c, 64, premerged=False) == \
        "xla_scatter"
    # quantized tables filtered (the fused engine updates f32 rows)
    assert pk.resolve_push_engine(c, 64, premerged=True,
                                  storage_f32=False) == "xla_scatter"
    # width past the per-row-DMA cap filtered
    assert pk.resolve_push_engine(c, 64, premerged=True,
                                  table_width=1024) == "xla_scatter"
    flags.push_engine = "xla_scatter"
    assert pk.resolve_push_engine(c, 64, premerged=True) == \
        "xla_scatter"
    flags.push_engine = "nope"
    with pytest.raises(ValueError, match="push_engine"):
        pk.resolve_push_engine(c, 64, premerged=True)


def test_resolver_auto_classes(engine_flag):
    """Auto off-TPU never picks a kernel engine (CPU production runs the
    scatter; the jnp fused path is a forced parity/A/B tool only)."""
    flags.push_engine = "auto"
    c = _cfg()
    assert pk.resolve_push_engine(c, 4096, premerged=True) == \
        "xla_scatter"
    assert pk.resolve_push_engine(c, 4096, premerged=False) == \
        "xla_scatter"


def test_forced_fused_disables_binned_geometry(engine_flag):
    """binned_push_geometry must not hand out block windows the fused
    dispatch will never consume (wasted host plan + H2D)."""
    c = _cfg(dim=8, optimizer="adagrad", learning_rate=0.05)
    flags.push_engine = "auto"
    base = pk._bp_geometry(c, 1 << 16)
    assert base is not None and base[2] >= 2      # binned-eligible class
    flags.push_engine = "scatter_accumulate"
    assert pk.binned_push_geometry(c, 1 << 16) is None
    flags.push_engine = "xla_scatter"
    assert pk.binned_push_geometry(c, 1 << 16) is None


def test_push_dispatch_forced_fused_bit_identical(engine_flag):
    """sharded.push's dispatch (the resolver's verdict) routes premerged
    lanes through the fused engine — bit-identical to the scatter path."""
    c = _cfg()
    table = _table(c, 64, seed=9)
    idx, grads, shows, clks = _tokens(c, 64, 150, seed=10)
    ref = np.asarray(sharded.push(table, jnp.asarray(idx),
                                  jnp.asarray(grads), jnp.asarray(shows),
                                  jnp.asarray(clks), c))
    uniq, mg, ms, mc, kplan = _premerged(c, idx, grads, shows, clks, 64)
    flags.push_engine = "scatter_accumulate"
    out = sharded.push(table, uniq, mg, ms, mc, c, plan=kplan,
                       premerged=True)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_quant_table_keeps_scatter_engines(engine_flag):
    """A quantized table must never reach the fused engine even when
    forced — the dispatch falls back and stays correct."""
    c = _cfg(storage="int8", dim=8)
    store = HostEmbeddingStore(c)
    rng = np.random.default_rng(11)
    keys = rng.choice(1 << 30, size=40, replace=False).astype(np.uint64)
    ws = PassWorkingSet.begin_pass(store, keys, make_mesh(1))
    assert quant.is_quant(ws.table)
    idx, grads, shows, clks = _tokens(c, ws.num_keys, 80, seed=12)
    ref = sharded.push(ws.table, jnp.asarray(idx), jnp.asarray(grads),
                       jnp.asarray(shows), jnp.asarray(clks), c)
    flags.push_engine = "scatter_accumulate"
    out = sharded.push(ws.table, jnp.asarray(idx), jnp.asarray(grads),
                       jnp.asarray(shows), jnp.asarray(clks), c)
    np.testing.assert_array_equal(np.asarray(out.fp), np.asarray(ref.fp))
    np.testing.assert_array_equal(np.asarray(out.qx), np.asarray(ref.qx))


# ---------------------------------------------------------------------------
# routed apply (the same kernel serves the exchange — test_exchange's
# lattice-grad discipline)
# ---------------------------------------------------------------------------

def _device_plans(idx_flat, n_rows, n_dev):
    parts = [dedup_plan(a, n_rows, n_rows, 1)
             for a in idx_flat.reshape(n_dev, -1)]
    Z = jnp.zeros(0, jnp.int32)
    return (jnp.asarray(np.concatenate([p[0] for p in parts])), Z, Z,
            jnp.asarray(np.concatenate([p[1] for p in parts])),
            jnp.asarray(np.concatenate([p[2] for p in parts])))


def _ws(cfg, n_keys, mesh):
    store = HostEmbeddingStore(cfg)
    keys = np.random.default_rng(7).choice(
        1 << 40, size=n_keys, replace=False).astype(np.uint64)
    return store, PassWorkingSet.begin_pass(store, keys, mesh)


def test_routed_fused_bit_identical_to_single_shard(mesh2, engine_flag):
    """2-shard routed apply under the fused engine: per-source premerge
    → f32 wire → cross-device lane merge → scatter_accumulate equals the
    single-shard scatter push bit-for-bit under exact arithmetic."""
    c = _cfg()
    store, ws = _ws(c, 60, mesh2)
    rng = np.random.default_rng(13)
    idx = rng.integers(0, ws.num_keys + 1, size=64).astype(np.int32)
    grads = (rng.integers(-512, 512, size=(64, c.grad_width))
             / 1024.0).astype(np.float32)
    shows = (idx > 0).astype(np.float32)
    clks = (rng.integers(0, 2, 64) * shows).astype(np.float32)
    grads[idx == 0] = 0.0
    plan = _device_plans(idx, ws.padded_rows, 2)
    args = tuple(map(jnp.asarray, (idx, grads, shows, clks)))
    want = np.asarray(sharded.push(ws.table, *args, c))

    flags.push_engine = "scatter_accumulate"

    def body(tshard, i, g, sh, ck, *p):
        return exchange.routed_push(tshard, i, g, sh, ck, c, ("dp",),
                                    2.0, wire="f32", plan=p)

    out = jax.jit(jax.shard_map(
        body, mesh=mesh2, in_specs=(P("dp"),) * 10,
        out_specs=P("dp")))(ws.table, *args, *plan)
    np.testing.assert_array_equal(np.asarray(out), want)


def test_routed_fused_premerged_deferred_bit_identical(mesh2,
                                                       engine_flag):
    """The deferred-apply form (PR-2 PushOperandStager program): the
    step premerges onto unique lanes and the apply routes them through
    the fused tail — bit-identical to the inline fused exchange."""
    c = _cfg()
    store, ws = _ws(c, 60, mesh2)
    rng = np.random.default_rng(15)
    idx = rng.integers(0, ws.num_keys + 1, size=64).astype(np.int32)
    grads = (rng.integers(-512, 512, size=(64, c.grad_width))
             / 1024.0).astype(np.float32)
    shows = (idx > 0).astype(np.float32)
    clks = (rng.integers(0, 2, 64) * shows).astype(np.float32)
    grads[idx == 0] = 0.0
    plan = _device_plans(idx, ws.padded_rows, 2)
    args = tuple(map(jnp.asarray, (idx, grads, shows, clks)))
    want = np.asarray(sharded.push(ws.table, *args, c))

    flags.push_engine = "scatter_accumulate"

    def deferred(tshard, i, g, sh, ck, *p):
        mg, ms, mc = sharded.deferred_push_operands(i, g, sh, ck, p)
        return exchange.routed_push(tshard, p[3], mg, ms, mc, c, ("dp",),
                                    2.0, wire="f32", premerged=True)

    out = jax.jit(jax.shard_map(
        deferred, mesh=mesh2, in_specs=(P("dp"),) * 10,
        out_specs=P("dp")))(ws.table, *args, *plan)
    np.testing.assert_array_equal(np.asarray(out), want)


# ---------------------------------------------------------------------------
# trainer end-to-end (forced fused engine on the single-shard CPU path:
# the host plan + in-step premerge + fused jnp apply, incl. the deferred
# push-overlap program)
# ---------------------------------------------------------------------------

def _trainer_fixture(seed=3):
    from paddlebox_tpu.data import DataFeedSchema, SlotDataset
    from paddlebox_tpu.data.parser import parse_multislot_lines
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.train import Trainer, TrainerConfig

    num_slots, vocab = 3, 40
    rng = np.random.default_rng(21)
    schema = DataFeedSchema.ctr(num_sparse=num_slots, num_float=1,
                                batch_size=16, max_len=2)
    lines = []
    for _ in range(64):
        parts = [f"1 {int(rng.random() < 0.3)}", f"1 {rng.normal():.4f}"]
        for s in range(num_slots):
            k = rng.integers(1, 3)
            ids = rng.integers(0, vocab, size=k) + s * 1000003
            parts.append(f"{len(ids)} {' '.join(str(i) for i in ids)}")
        lines.append(" ".join(parts))
    ds = SlotDataset(schema)
    ds.records = parse_multislot_lines(lines, schema)
    store = HostEmbeddingStore(EmbeddingConfig(dim=4, learning_rate=0.1))
    model = DeepFMModel(num_slots=num_slots, emb_dim=4, dense_dim=1,
                        hidden=(8,))
    tr = Trainer(model, store, schema, make_mesh(1),
                 TrainerConfig(global_batch_size=16), seed=seed)
    return tr, ds, store


@pytest.mark.skipif(not hasattr(jax, "shard_map"),
                    reason="the jitted step needs jax.shard_map "
                           "(same bar as the suite's trainer tests)")
def test_trainer_forced_fused_matches_auto(engine_flag):
    """Full train_pass parity: forcing the fused engine (host dedup plan
    forced on, in-step premerge, jnp fused apply — incl. the deferred
    push-overlap apply program) reproduces the auto engine's losses and
    persisted rows (pooling/merge are linear; adagrad-free SGD-like
    parity is not available here, so bounded like the fused-pull test)."""

    def run(engine):
        flags.push_engine = engine
        tr, ds, store = _trainer_fixture()
        if engine == "scatter_accumulate":
            assert tr._use_plan          # forced fused engages the plan
        out = tr.train_pass(ds)
        tr.flush_sparse()
        keys = ds.unique_keys()
        return out, store.peek_rows(np.unique(keys))

    out_f, rows_f = run("scatter_accumulate")
    out_a, rows_a = run("auto")
    assert abs(out_f["loss_mean"] - out_a["loss_mean"]) < 1e-5
    assert abs(out_f["auc"] - out_a["auc"]) < 1e-6
    np.testing.assert_allclose(rows_f, rows_a, rtol=1e-5, atol=1e-6)


def test_trainer_records_push_engine(engine_flag):
    """The trainer's resolver helper (the flight record's source)
    names the engine the compiled dispatch contains."""
    tr, ds, store = _trainer_fixture()
    keys = ds.unique_keys()
    ws = PassWorkingSet.begin_pass(store, np.unique(keys), tr.mesh)
    flags.push_engine = "auto"
    assert tr.resolved_push_engine(ws) == "xla_scatter"   # CPU auto
    flags.push_engine = "scatter_accumulate"
    tr2, ds2, store2 = _trainer_fixture(seed=4)
    keys2 = ds2.unique_keys()
    ws2 = PassWorkingSet.begin_pass(store2, np.unique(keys2), tr2.mesh)
    assert tr2.push_premerged(ws2)
    assert tr2.resolved_push_engine(ws2) == "scatter_accumulate"


def test_binned_enable_knob_never_silently_voids_a_force(engine_flag):
    """flags.binned_push=False is an ablation knob, not a second silent
    gate on an explicit force: the forced binned_kernel resolution must
    not depend on it (geometry + backend are the contract — on CPU both
    settings fall back identically)."""
    c = _cfg(dim=8, optimizer="adagrad", learning_rate=0.05)
    flags.push_engine = "binned_kernel"
    old = flags.binned_push
    try:
        flags.binned_push = True
        with_knob = pk.resolve_push_engine(c, 1 << 16, premerged=False)
        flags.binned_push = False
        without = pk.resolve_push_engine(c, 1 << 16, premerged=False)
        assert with_knob == without
    finally:
        flags.binned_push = old
