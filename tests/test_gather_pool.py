"""Fused gather-pool pull vs the unfused fused_seqpool_cvm reference.

The Pallas kernel runs in interpret mode on CPU (like binned_push); the
reference is the unfused path the models otherwise take — a full-row
gather + per-token filter/quant + per-slot sum pool. Covers forward
parity over the reference kernel family's knobs (per-slot show/clk
thresholds, embed-threshold filter, quant gating), the edge geometries
(empty slots, all-pad batches, duplicate-heavy multi-hot), and grad
parity through the custom VJP.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddlebox_tpu.config import flags
from paddlebox_tpu.embedding import sharded
from paddlebox_tpu.embedding.config import EmbeddingConfig
from paddlebox_tpu.ops import pallas_kernels
from paddlebox_tpu.ops.seqpool_cvm import (PooledSlots,
                                           fused_gather_seqpool_cvm,
                                           fused_seqpool_cvm)


def _mk(B=4, S=3, L=2, dim=4, n=64, seed=0, mask_p=0.7):
    """Table with counter-like show/clk (CVM logs need nonneg pools) and
    the NULL-row contract (row 0 all zeros, like a pass working set),
    padded to whole 128-lane tiles — the device-table form the kernel's
    row DMAs take (flags.table_pad_width); the pad columns carry junk
    the pool must never read."""
    cfg = EmbeddingConfig(dim=dim, optimizer="adagrad", learning_rate=0.05)
    rng = np.random.default_rng(seed)
    width = -(-cfg.row_width // 128) * 128
    table = rng.normal(size=(n, width)).astype(np.float32)
    table[:, 0] = rng.integers(0, 20, size=n)        # show
    table[:, 1] = rng.integers(0, 5, size=n)         # clk
    table[0] = 0.0
    idx = rng.integers(1, n, size=(B, S * L)).astype(np.int32)
    mask = rng.random((B, S * L)) < mask_p
    seg = np.repeat(np.arange(S, dtype=np.int32), L)
    return cfg, jnp.asarray(table), idx, mask, seg


def _ref_pulled(table, idx, mask, cfg):
    """The unfused pull the models otherwise see (grad-transparent: the
    trainer never differentiates through lookup's optimization barrier,
    so the reference uses the plain gather)."""
    B, T = idx.shape
    idx0 = jnp.asarray(np.where(mask, idx, 0)).reshape(-1)
    P = cfg.pull_width
    return jnp.take(table, idx0, axis=0)[:, :P].reshape(B, T, P)


@pytest.mark.parametrize("B,S,L,dim", [
    (4, 3, 2, 4),      # multi-hot
    (8, 5, 1, 4),      # one-hot (L=1), >8 in-flight DMAs per tile
    (4, 2, 3, 128),    # wide rows: two lane tiles per row (W=256)
])
def test_kernel_interpret_matches_reference_pool(B, S, L, dim):
    cfg, table, idx, mask, seg = _mk(B=B, S=S, L=L, dim=dim)
    idx0 = np.where(mask, idx, 0).astype(np.int32)
    out = pallas_kernels.gather_pool(table, jnp.asarray(idx0), cfg, S, L,
                                     interpret=True)
    P = cfg.pull_width
    ref = np.asarray(table)[idx0.reshape(-1), :P].reshape(B, S, L, P).sum(
        axis=2)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("use_cvm", [True, False])
def test_fused_op_forward_parity(use_cvm):
    cfg, table, idx, mask, seg = _mk()
    got = fused_gather_seqpool_cvm(table, jnp.asarray(idx),
                                   jnp.asarray(mask), seg, 3, cfg,
                                   use_cvm=use_cvm, interpret=True)
    want = fused_seqpool_cvm(_ref_pulled(table, idx, mask, cfg),
                             jnp.asarray(mask), seg, 3, use_cvm=use_cvm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_fused_op_per_slot_thresholds_and_quant():
    cfg, table, idx, mask, seg = _mk(seed=2)
    thr = np.array([0.5, -1.0, 3.0], np.float32)   # per-slot diff-thres
    kw = dict(need_filter=True, threshold=thr, show_coeff=0.3,
              clk_coeff=0.9, embed_threshold=0.4, quant_ratio=8)
    got = fused_gather_seqpool_cvm(table, jnp.asarray(idx),
                                   jnp.asarray(mask), seg, 3, cfg,
                                   interpret=True, **kw)
    want = fused_seqpool_cvm(_ref_pulled(table, idx, mask, cfg),
                             jnp.asarray(mask), seg, 3, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # quant gating off on the same inputs must differ from on (the knob
    # does something) and still match its own reference
    kw_off = dict(kw, quant_ratio=0)
    got_off = fused_gather_seqpool_cvm(table, jnp.asarray(idx),
                                       jnp.asarray(mask), seg, 3, cfg,
                                       interpret=True, **kw_off)
    want_off = fused_seqpool_cvm(_ref_pulled(table, idx, mask, cfg),
                                 jnp.asarray(mask), seg, 3, **kw_off)
    np.testing.assert_allclose(np.asarray(got_off), np.asarray(want_off),
                               rtol=1e-6, atol=1e-6)
    assert np.abs(np.asarray(got) - np.asarray(got_off)).max() > 0


def test_fused_op_empty_slots_and_all_pad():
    cfg, table, idx, mask, seg = _mk(seed=3)
    mask = mask.copy()
    mask[0, :] = False            # all-pad example
    mask[:, 2:4] = False          # slot 1 empty in every example
    got = fused_gather_seqpool_cvm(table, jnp.asarray(idx),
                                   jnp.asarray(mask), seg, 3, cfg,
                                   use_cvm=True, flatten=False,
                                   interpret=True)
    want = fused_seqpool_cvm(_ref_pulled(table, idx, mask, cfg),
                             jnp.asarray(mask), seg, 3, use_cvm=True,
                             flatten=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # empty segments pool to the zero row: log(0+1)=0 CVM columns, zero
    # embedx
    np.testing.assert_array_equal(np.asarray(got)[0], 0.0)
    np.testing.assert_array_equal(np.asarray(got)[:, 1, :], 0.0)
    # fully-masked batch
    none = np.zeros_like(mask)
    got0 = fused_gather_seqpool_cvm(table, jnp.asarray(idx),
                                    jnp.asarray(none), seg, 3, cfg,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(got0), 0.0)


@pytest.mark.parametrize("need_filter,embed_threshold",
                         [(False, 0.0), (True, 0.0),
                          (False, 0.3), (True, 0.3)])
def test_fused_op_grad_parity(need_filter, embed_threshold):
    """Grad parity through the custom VJP vs the unfused autodiff
    reference — including the duplicate-heavy merge (every token drawn
    from 8 rows, so the VJP's dedup path actually folds duplicates) and
    the embed_threshold drop mask (the VJP re-derives the forward's keep
    predicate from the raw rows; a predicate drift between the copies
    must fail here, not corrupt training silently)."""
    cfg, table, idx, mask, seg = _mk(B=6, S=3, L=4, n=64, seed=4)
    idx = (idx % 8 + 1).astype(np.int32)          # heavy duplication
    kw = dict(need_filter=need_filter, threshold=0.5,
              embed_threshold=embed_threshold)
    w = jnp.asarray(np.random.default_rng(5).normal(
        size=(6, 3 * cfg.pull_width)).astype(np.float32))

    def fused_loss(t):
        out = fused_gather_seqpool_cvm(t, jnp.asarray(idx),
                                       jnp.asarray(mask), seg, 3, cfg,
                                       interpret=True, **kw)
        return jnp.sum(out * w)

    def ref_loss(t):
        out = fused_seqpool_cvm(_ref_pulled(t, idx, mask, cfg),
                                jnp.asarray(mask), seg, 3, **kw)
        return jnp.sum(out * w)

    g_fused = jax.grad(fused_loss)(table)
    g_ref = jax.grad(ref_loss)(table)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)


def test_pooled_slots_rejects_per_token_filters():
    pooled = PooledSlots(jnp.zeros((2, 3, 7), jnp.float32))
    with pytest.raises(ValueError, match="PooledSlots"):
        fused_seqpool_cvm(pooled, None, np.zeros(3, np.int64), 3,
                          need_filter=True)


def test_fused_op_rejects_create_threshold_configs():
    """Gated pulls (mf/expand create thresholds) would silently skip
    gate_pull through the fused gather — must raise, not diverge."""
    cfg, table, idx, mask, seg = _mk()
    gated = EmbeddingConfig(dim=4, optimizer="adagrad",
                            mf_create_threshold=2.0)
    with pytest.raises(ValueError, match="gate_pull"):
        fused_gather_seqpool_cvm(table, jnp.asarray(idx),
                                 jnp.asarray(mask), seg, 3, gated,
                                 interpret=True)


def test_pooled_grad_tokens_matches_unfused_expansion():
    """The trainer's backward half: expanding the pooled cotangent per
    token must equal the unfused path's per-token gpull[..., 2:]."""
    cfg, table, idx, mask, seg = _mk(B=5, S=3, L=2, seed=6)
    B, T = idx.shape
    rng = np.random.default_rng(7)
    gpooled = jnp.asarray(rng.normal(
        size=(B, 3, cfg.pull_width)).astype(np.float32))
    got = sharded.pooled_grad_tokens(gpooled, jnp.asarray(mask), seg, 3)
    # unfused: each token's pull cotangent is its slot's pooled row
    # masked — pooling is a per-segment sum
    want = (np.asarray(gpooled)[:, np.asarray(seg), 2:]
            * mask[..., None]).reshape(B * T, cfg.grad_width)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                               atol=1e-6)


def test_fused_pull_pool_reference_path_matches_lookup():
    """CPU (no kernel geometry on this backend): fused_pull_pool must be
    the exact lookup + reshape-sum, quant storage included."""
    cfg, table, idx, mask, seg = _mk(B=4, S=3, L=2)
    idx0 = jnp.asarray(np.where(mask, idx, 0))
    got = sharded.fused_pull_pool(table, idx0, cfg, 3, 2)
    want = sharded.lookup(table, idx0.reshape(-1), cfg).reshape(
        4, 3, 2, cfg.pull_width).sum(axis=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gather_pool_geometry_bounds():
    # the tile divides the batch (odd batches degrade to BB=1, still
    # valid); absurd widths fall back
    assert pallas_kernels.gather_pool_geometry(8, 3, 2, 128) is not None
    assert pallas_kernels.gather_pool_geometry(7, 3, 2, 128) == 1
    assert pallas_kernels.gather_pool_geometry(8, 3, 2, 1024) is None
    # wide rows shrink the tile instead of overflowing VMEM
    bb = pallas_kernels.gather_pool_geometry(4096, 26, 4, 128)
    assert bb is not None and 4096 % bb == 0
    wide = pallas_kernels.gather_pool_geometry(4096, 26, 4, 512)
    assert wide is not None and wide < bb


@pytest.mark.parametrize("width", [13, 37, 69, 133, 200])
def test_gather_pool_geometry_refuses_partial_lane_tiles(width):
    """The v5e compiler refuses row DMAs narrower or wider than whole
    128-lane tiles (tests/test_aot_tpu_compile.py holds the compiler's
    side), so the geometry must too — the logical row widths of dim
    8/32/64/128 tables among them."""
    assert pallas_kernels.gather_pool_geometry(4096, 26, 4, width) is None


def test_gather_pool_kernel_parity_across_batch_tiles():
    """Two batch tiles (B=128 at BB=64): each tile's ids ride their own
    SMEM window, padded from BB*T words to a whole 1024-word block — the
    padding must change only the tiling, never the pooled sums."""
    cfg, table, idx, mask, seg = _mk(B=128, S=1, L=1, dim=4, n=64,
                                     seed=9)
    idx0 = np.where(mask, idx, 0).astype(np.int32)
    assert pallas_kernels.gather_pool_geometry(
        128, 1, 1, int(table.shape[1])) == 64
    out = pallas_kernels.gather_pool(table, jnp.asarray(idx0), cfg, 1, 1,
                                     interpret=True)
    P = cfg.pull_width
    ref = np.asarray(table)[idx0.reshape(-1), :P].reshape(
        128, 1, 1, P).sum(axis=2)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6,
                               atol=1e-6)


def _trainer_fixture(engine_flag, seed=3):
    from paddlebox_tpu.data import DataFeedSchema, SlotDataset
    from paddlebox_tpu.data.parser import parse_multislot_lines
    from paddlebox_tpu.embedding import HostEmbeddingStore
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.train import Trainer, TrainerConfig

    num_slots, vocab = 3, 40
    rng = np.random.default_rng(11)
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
    old = flags.fused_gather_pool
    flags.fused_gather_pool = engine_flag
    try:
        store = HostEmbeddingStore(EmbeddingConfig(dim=4,
                                                   learning_rate=0.1))
        model = DeepFMModel(num_slots=num_slots, emb_dim=4, dense_dim=1,
                            hidden=(8,))
        tr = Trainer(model, store, schema, make_mesh(1),
                     TrainerConfig(global_batch_size=16), seed=seed)
    finally:
        flags.fused_gather_pool = old
    return tr, ds, store


def test_trainer_heuristic_selects_fused_for_multihot():
    tr, _, _ = _trainer_fixture("auto")
    assert tr.pull_engine == "fused_gather_pool"   # max_len 2 multi-hot
    tr_off, _, _ = _trainer_fixture("off")
    assert tr_off.pull_engine == "gather_seqpool"


def test_trainer_heuristic_single_hot_narrow_stays_unfused():
    from paddlebox_tpu.data import DataFeedSchema
    from paddlebox_tpu.embedding import HostEmbeddingStore
    from paddlebox_tpu.models import DeepFMModel
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.train import Trainer, TrainerConfig

    schema = DataFeedSchema.ctr(num_sparse=3, num_float=1, batch_size=16,
                                max_len=1)
    store = HostEmbeddingStore(EmbeddingConfig(dim=4, learning_rate=0.1))
    tr = Trainer(DeepFMModel(num_slots=3, emb_dim=4, dense_dim=1,
                             hidden=(8,)),
                 store, schema, make_mesh(1),
                 TrainerConfig(global_batch_size=16))
    assert tr.pull_engine == "gather_seqpool"
    # wide-dim single-hot selects fused
    store_w = HostEmbeddingStore(EmbeddingConfig(dim=64,
                                                 learning_rate=0.1))
    tr_w = Trainer(DeepFMModel(num_slots=3, emb_dim=64, dense_dim=1,
                               hidden=(8,)),
                   store_w, schema, make_mesh(1),
                   TrainerConfig(global_batch_size=16))
    assert tr_w.pull_engine == "fused_gather_pool"


@pytest.mark.skipif(not hasattr(jax, "shard_map"),
                    reason="the jitted step needs jax.shard_map "
                           "(same bar as the suite's trainer tests)")
def test_trainer_fused_matches_unfused_training():
    """Full train_pass + eval_pass parity: the fused engine must produce
    the same losses, predictions, and persisted table rows as the
    unfused step (pooling is linear, so the math is identical up to
    reduction order)."""

    def run(engine_flag):
        tr, ds, store = _trainer_fixture(engine_flag)
        out = tr.train_pass(ds)
        ev = tr.eval_pass(ds)
        tr.flush_sparse()
        keys = ds.unique_keys()
        return out, ev, store.peek_rows(np.unique(keys))

    out_f, ev_f, rows_f = run("on")
    out_u, ev_u, rows_u = run("off")
    assert abs(out_f["loss_mean"] - out_u["loss_mean"]) < 1e-5
    assert abs(out_f["auc"] - out_u["auc"]) < 1e-6
    assert abs(ev_f["auc"] - ev_u["auc"]) < 1e-6
    np.testing.assert_allclose(rows_f, rows_u, rtol=1e-5, atol=1e-6)
