"""The gated short convolution of ops/short_conv.py in the Pallas
interpreter, at small sizes: against its plain ``jax.numpy`` twin, values
and all four cotangents, across time blocks (the halo) and in one block; a
finite-difference check of ``dw``; what the geometry refuses, off the chip
and on it."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddlebox_tpu.ops import short_conv as sc

NAMES = ("B", "C", "x", "w")
# (batch, T, d, K, block_t, block_d): three time blocks and two channel
# blocks; one block of everything; four taps over blocks of 16 rows; two
# taps
SHAPES = {"several_time_blocks": (2, 24, 32, 3, 8, 16),
          "one_block": (2, 8, 16, 3, 256, 512),
          "four_taps": (1, 32, 8, 4, 16, 8),
          "two_taps": (1, 16, 8, 2, 8, 8)}


def _inputs(shape, seed=0):
    n, T, d, K = shape[:4]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (*(jax.random.normal(k, (n, T, d)) for k in ks[:3]),
            jax.random.uniform(ks[3], (K, d), minval=-K ** -0.5,
                               maxval=K ** -0.5))


def _blocked(shape):
    return lambda *a: sc.short_conv(*a, block_t=shape[4], block_d=shape[5])


@pytest.mark.parametrize("case", sorted(SHAPES))
def test_kernels_equal_the_plain_twin(case):
    shape = SHAPES[case]
    args = _inputs(shape)
    assert sc.conv_geometry(*shape[1:4], *shape[4:]) is not None
    grads = lambda fn: jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3)))(*args)
    y, want = jax.jit(_blocked(shape))(*args), sc.short_conv_reference(*args)
    # float32 on both sides, the taps' sum in the same order
    np.testing.assert_allclose(y, want, atol=1e-6 * float(jnp.abs(want).max()))
    for name, g, w in zip(NAMES, grads(_blocked(shape)),
                          grads(sc.short_conv_reference)):
        # dw sums over batch and time in another order
        np.testing.assert_allclose(
            g, w, atol=3e-6 * float(jnp.abs(w).max()), err_msg=name)


def test_a_token_reaches_across_the_block_edge_and_no_further():
    shape = SHAPES["several_time_blocks"]
    B, C, x, w = _inputs(shape, seed=1)
    f = jax.jit(_blocked(shape))
    moved = jnp.abs(f(B, C, x.at[:, 7].add(1.0), w) - f(B, C, x, w)
                    ).max(axis=(0, 2))
    # position 7 is the first block's last row: with three taps it reaches
    # itself and the second block's first two rows
    np.testing.assert_array_equal(np.asarray(moved > 1e-6),
                                  np.isin(np.arange(24), [7, 8, 9]))
    # and the first rows of a sequence see zeros before them, not the
    # previous sequence's last rows
    first = f(B, C, x, w)[:, 0]
    np.testing.assert_allclose(first, C[:, 0] * w[2] * B[:, 0] * x[:, 0],
                               atol=1e-6)


def test_dw_by_finite_differences():
    shape = SHAPES["several_time_blocks"]
    B, C, x, w = _inputs(shape, seed=2)
    probe = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    loss = lambda w: jnp.sum(_blocked(shape)(B, C, x, w) * probe)
    dw = jax.grad(loss)(w)
    h = 1e-2        # the loss is linear in w: the difference is exact
    for j, c in ((0, 0), (1, 5), (2, 31)):
        step = jnp.zeros_like(w).at[j, c].set(h)
        fd = (loss(w + step) - loss(w - step)) / (2 * h)
        np.testing.assert_allclose(dw[j, c], fd, rtol=2e-3, atol=1e-3)


def test_geometry_the_kernels_refuse_is_the_twin(monkeypatch):
    assert sc.conv_geometry(8192, 2048, 3) == (256, 512)
    assert sc.conv_geometry(32, 64, 3) == (32, 64)
    assert sc.conv_geometry(20, 64, 3) is None          # no whole 8-row tiles
    assert sc.conv_geometry(640, 64, 3) is None         # no whole blocks
    assert sc.conv_geometry(32, 64, 1) is None          # no convolution
    assert sc.conv_geometry(32, 64, 10) is None         # past the halo
    B, C, x, w = _inputs((1, 20, 8, 3))
    np.testing.assert_array_equal(sc.short_conv(B, C, x, w),
                                  sc.short_conv_reference(B, C, x, w))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sc.conv_geometry(8192, 2048, 3) == (256, 512)    # the cell's
    assert sc.conv_geometry(32, 64, 3) is None          # half a lane tile
    with pytest.raises(ValueError, match="taps"):
        sc.short_conv(B, C, x, w[:, :4])
