"""The chunked state-space scan of ops/ssm_scan.py in the Pallas
interpreter, at small sizes: against the literal recurrence, values and
gradients, across chunk edges, with heads side by side in a lane tile and
with one head a tile; what it refuses, off the chip and on it."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddlebox_tpu.ops import ssm_scan as ss

NAMES = ("x", "dt", "A_log", "B", "C", "D")
# (B, T, H, P, G, N, chunk): two heads a tile in two groups, three chunks;
# four heads of a group in two tiles of two (the chip's layout: P = 64)
SHAPES = {"heads_share_a_tile": (2, 24, 4, 8, 2, 16, 8),
          "two_tiles_a_group": (1, 16, 4, 64, 1, 8, 8),
          "one_chunk": (2, 8, 2, 8, 1, 8, 128)}


def _inputs(shape, seed=0):
    B, T, H, P, G, N, _ = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (B, T, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 1.0),
            jnp.log(jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)),
            jax.random.normal(ks[3], (B, T, G, N)),
            jax.random.normal(ks[4], (B, T, G, N)),
            jax.random.normal(ks[5], (H,)))


@pytest.mark.parametrize("case", sorted(SHAPES))
def test_chunked_scan_equals_the_literal_recurrence(case):
    shape = SHAPES[case]
    args = _inputs(shape)
    chunked = lambda *a: ss.ssm_scan(*a, chunk=shape[-1])
    grads = lambda fn: jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=tuple(range(6))))(*args)
    with jax.default_matmul_precision("highest"):
        y, want = jax.jit(chunked)(*args), ss.ssm_scan_reference(*args)
        got_g, want_g = grads(chunked), grads(ss.ssm_scan_reference)
    # float32 sums in another order (a chunk's products against 8 to 24
    # single steps): a few ulps of the largest term
    np.testing.assert_allclose(y, want, atol=1e-5 * float(jnp.abs(want).max()))
    for name, g, w in zip(NAMES, got_g, want_g):
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_the_state_crosses_the_chunk_edge():
    shape = SHAPES["heads_share_a_tile"]
    x, dt, A_log, Bm, Cm, D = _inputs(shape, seed=1)
    dt = dt * 0.05                       # slow decays: the past stays seen
    with jax.default_matmul_precision("highest"):
        y = ss.ssm_scan(x, dt, A_log, Bm, Cm, D, chunk=8)
        moved = ss.ssm_scan(x.at[:, 0].add(1.0), dt, A_log, Bm, Cm, D,
                            chunk=8)
        want = ss.ssm_scan_reference(x.at[:, 0].add(1.0), dt, A_log, Bm, Cm,
                                     D)
    # position 0 reaches the last position of the third chunk, and by the
    # amount the recurrence says
    assert float(jnp.abs(moved - y)[:, -1].max()) > 1e-3
    np.testing.assert_allclose(moved[:, -1], want[:, -1], atol=2e-5)


def test_a_length_that_is_no_multiple_of_the_chunk_is_refused():
    args = _inputs((1, 12, 2, 8, 1, 8, 8))
    with pytest.raises(ValueError, match="chunks of 8"):
        ss.ssm_scan(*args, chunk=8)
    with pytest.raises(ValueError, match="group"):
        ss.ssm_scan(args[0], args[1][:, :, :1], *args[2:], chunk=4)


def test_bfloat16_operands_keep_float32_decays():
    shape = SHAPES["heads_share_a_tile"]
    x, dt, A_log, Bm, Cm, D = _inputs(shape, seed=2)
    bf = lambda v: v.astype(jnp.bfloat16)
    y = ss.ssm_scan(bf(x), dt, A_log, bf(Bm), bf(Cm), D, chunk=8)
    want = ss.ssm_scan_reference(x, dt, A_log, Bm, Cm, D)
    assert y.dtype == jnp.bfloat16
    # operands rounded to 8 bits of mantissa, sums in float32
    np.testing.assert_allclose(y.astype(jnp.float32), want,
                               atol=0.03 * float(jnp.abs(want).max()))
    g = jax.grad(lambda d: jnp.sum(ss.ssm_scan(
        bf(x), d, A_log, bf(Bm), bf(Cm), D, chunk=8).astype(jnp.float32)))(dt)
    assert g.dtype == jnp.float32 and bool(jnp.isfinite(g).all())


def test_geometry_the_chip_refuses_is_refused(monkeypatch):
    assert ss.scan_geometry(8, 2, 8, 16) == (2, 16)
    assert ss.scan_geometry(128, 8, 64, 128) == (2, 128)
    assert ss.scan_geometry(128, 3, 64, 128) == (1, 64)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ss.scan_geometry(128, 8, 64, 128) == (2, 128)    # the cell's
    assert ss.scan_geometry(128, 4, 128, 128) == (1, 128)
    assert ss.scan_geometry(8, 2, 8, 16) is None            # no lane tiles
    assert ss.scan_geometry(128, 3, 64, 128) is None        # half a tile
    assert ss.scan_geometry(64, 8, 64, 128) is None         # chunk
    # and the scan raises there: the literal recurrence in place of the
    # kernels would be 4096 steps a sequence under no kernel's name
    x, dt, A_log, Bm, Cm, D = _inputs(SHAPES["heads_share_a_tile"])
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        ss.ssm_scan(x, dt, A_log, Bm, Cm, D, chunk=8)
