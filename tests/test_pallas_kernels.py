"""Pallas merge-update kernel vs the XLA reference path (interpret mode on
CPU; the same kernel compiles with Mosaic on TPU)."""

import numpy as np
import jax.numpy as jnp
import pytest

from paddlebox_tpu.embedding import sharded
from paddlebox_tpu.embedding.config import EmbeddingConfig
from paddlebox_tpu.embedding.optim import apply_updates
from paddlebox_tpu.ops import pallas_kernels


@pytest.mark.parametrize("opt", ["sgd", "adagrad", "adam", "ftrl"])
@pytest.mark.parametrize("n", [64, 100])   # 100: ragged edge block
def test_merge_update_matches_xla_path(opt, n):
    cfg = EmbeddingConfig(dim=4, optimizer=opt, learning_rate=0.1)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(n, cfg.row_width)).astype(np.float32))
    acc = np.zeros((n, cfg.grad_width + 3), np.float32)
    touched = rng.choice(n, size=n // 3, replace=False)
    acc[touched, :cfg.grad_width] = rng.normal(
        size=(len(touched), cfg.grad_width))
    acc[touched, cfg.grad_width] = 1.0      # show
    acc[touched, cfg.grad_width + 1] = 0.5  # clk
    acc[touched, cfg.grad_width + 2] = 1.0  # touch count
    acc = jnp.asarray(acc)

    got = pallas_kernels.merge_update(table, acc, cfg, block_rows=32,
                                      interpret=True)
    ref_rows = apply_updates(table, acc[:, :cfg.grad_width],
                             acc[:, cfg.grad_width],
                             acc[:, cfg.grad_width + 1], cfg)
    want = jnp.where((acc[:, cfg.grad_width + 2] > 0)[:, None],
                     ref_rows, table)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # untouched rows bit-identical
    untouched = np.setdiff1d(np.arange(n), touched)
    np.testing.assert_array_equal(np.asarray(got)[untouched],
                                  np.asarray(table)[untouched])


def test_vma_plumbing_api_canary():
    """merge_update's shard_map handshake is jax.typeof(x).vma →
    ShapeDtypeStruct(vma=...). It can only EXECUTE on real TPU (the Pallas
    interpreter rejects any kernel under a check_vma shard_map — even a
    pure copy trips its while_loop carry typing in JAX 0.9.0), so pin the
    two API halves here: a JAX upgrade that drops either breaks this test
    in CI instead of erroring first on a TPU pod."""
    import jax
    from jax.sharding import PartitionSpec as P
    from paddlebox_tpu.parallel import make_mesh

    mesh = make_mesh(8)
    axes = tuple(mesh.axis_names)
    seen = []

    def body(x):
        vma = getattr(jax.typeof(x), "vma", None)
        seen.append(vma)
        return x

    x = jnp.zeros((64, 4), jnp.float32)
    jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(axes),),
                          out_specs=P(axes)))(x)
    assert seen and seen[0], "jax.typeof(...).vma no longer set in shard_map"
    s = jax.ShapeDtypeStruct((4, 4), jnp.float32, vma=seen[0])
    assert s.shape == (4, 4)


def test_routed_push_with_flag_on_cpu_mesh(monkeypatch):
    """routed_push under shard_map with PBTPU_PALLAS=1 on the CPU mesh:
    exercises the interpret+vma fallback inside merge_update (the kernel
    itself runs only on real TPU; its math is identical by construction
    and covered on-chip)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from paddlebox_tpu.parallel import make_mesh

    monkeypatch.setenv("PBTPU_PALLAS", "1")
    cfg = EmbeddingConfig(dim=4, optimizer="adagrad", learning_rate=0.1)
    rng = np.random.default_rng(2)
    mesh = make_mesh(8)
    axes = tuple(mesh.axis_names)
    n, tokens = 64 * 8, 128           # 64 rows per shard
    table = jnp.asarray(rng.normal(size=(n, cfg.row_width))
                        .astype(np.float32))
    idx = jnp.asarray(rng.integers(1, n, size=tokens * 8)
                      .astype(np.int32))
    grads = jnp.asarray(rng.normal(size=(tokens * 8, cfg.grad_width))
                        .astype(np.float32))
    ones = jnp.ones((tokens * 8,), jnp.float32)

    def body(tshard, idx_l, g_l, s_l, c_l):
        return sharded.routed_push(tshard, idx_l, g_l, s_l, c_l, cfg, axes)

    fused = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes), P(axes)),
        out_specs=P(axes)))(table, idx, grads, ones, ones)
    monkeypatch.setenv("PBTPU_PALLAS", "0")
    base = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes), P(axes)),
        out_specs=P(axes)))(table, idx, grads, ones, ones)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(base),
                               rtol=1e-5, atol=1e-5)


def test_push_flag_gated(monkeypatch):
    """PBTPU_PALLAS=1 routes push through the kernel with equal results."""
    cfg = EmbeddingConfig(dim=4, optimizer="adagrad", learning_rate=0.1)
    rng = np.random.default_rng(1)
    n, tokens = 64, 40
    table = jnp.asarray(rng.normal(size=(n, cfg.row_width)).astype(np.float32))
    idx = jnp.asarray(rng.integers(1, n, size=tokens).astype(np.int32))
    grads = jnp.asarray(rng.normal(size=(tokens, cfg.grad_width))
                        .astype(np.float32))
    ones = jnp.ones((tokens,), jnp.float32)

    monkeypatch.delenv("PBTPU_PALLAS", raising=False)
    base = sharded.push(table, idx, grads, ones, ones, cfg)
    monkeypatch.setenv("PBTPU_PALLAS", "1")
    fused = sharded.push(table, idx, grads, ones, ones, cfg)
    np.testing.assert_allclose(np.asarray(base), np.asarray(fused),
                               rtol=1e-6, atol=1e-6)
