"""The grouped matrix products of ops/grouped_matmul.py in the Pallas
interpreter, at small sizes: ``pbtpu_gmm`` and both cotangents against
``lax.ragged_dot``'s and its VJP's over group sizes that are uneven, off
the row tile, empty (first, in the middle, last) and short of the rows;
the tile metadata against a plain loop; bfloat16 operands under float32
sums; the tile rule at the three token cells' operands and every rung of
their ladders. (The interpreter leaves nan wherever a kernel stored
nothing, so a row or a block that was wrongly left alone shows.)"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddlebox_tpu.ops import grouped_matmul as gm
from paddlebox_tpu.parallel.expert import route_rungs

# (sizes, rows, K, N, row tile, (tk, tn) or None for the rule's)
CASES = {
    "uneven_off_the_tile": ([3, 9, 20], 32, 32, 16, 8, None),
    "empty_first_middle_last": ([0, 5, 0, 17, 3, 0], 40, 32, 16, 8, None),
    "short_of_the_rows": ([7, 2, 6], 48, 16, 32, 16, None),
    "rows_off_the_tile": ([5, 11, 4], 20, 16, 16, 8, None),
    "nothing_held": ([0, 0, 0], 16, 16, 16, 8, None),
    "one_group_of_all": ([24], 24, 16, 16, 8, None),
    "k_and_n_in_tiles": ([7, 9, 4, 0, 12], 32, 256, 384, 16, (128, 128)),
    "a_tile_of_many_groups": ([1, 2, 1, 0, 3, 1], 16, 16, 16, 16, None),
}


def _operands(case, dtype=jnp.float32):
    sizes, rows, k, n, tm, tiles = CASES[case]
    sizes = jnp.asarray(sizes, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (rows, k)).astype(dtype)
    w = (jax.random.normal(ks[1], (sizes.shape[0], k, n)) * k ** -0.5
         ).astype(dtype)
    probe = jax.random.normal(ks[2], (rows, n))
    live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
    return sizes, x, w, probe, live, gm.group_tiles(sizes, rows, tm), tiles


def _masked(product, x, w, probe, live):
    """The product as the share layer uses it: nothing flows through the
    rows past the last group, in either direction."""
    y = jnp.where(live, product(jnp.where(live, x, 0), w), 0)
    return jnp.sum(y * probe), y


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_product_and_both_cotangents_equal_ragged_dot(case):
    sizes, x, w, probe, live, meta, tiles = _operands(case)
    if tiles is None:
        product = lambda x, w: gm.grouped_matmul(x, w, meta)
    else:       # the kernels themselves, in the tiles the case names
        product = _tiled_product(meta, tiles)
    run = lambda p: jax.jit(jax.value_and_grad(
        lambda x, w: _masked(p, x, w, probe, live), argnums=(0, 1),
        has_aux=True))(x, w)
    (_, y), (dx, dw) = run(product)
    (_, y_want), (dx_want, dw_want) = run(
        lambda x, w: gm.grouped_matmul_reference(x, w, sizes))
    assert y.dtype == jnp.float32
    for name, got, want in (("y", y, y_want), ("dx", dx, dx_want),
                            ("dw", dw, dw_want)):
        np.testing.assert_allclose(
            got, want, atol=1e-5 * max(1.0, float(jnp.abs(want).max())),
            err_msg=name)
    # a group with no rows: zeros, not what the buffer held
    assert not np.asarray(dw)[np.asarray(sizes) == 0].any()


def _tiled_product(meta, tiles):
    tk, tn = tiles

    @jax.custom_vjp
    def product(x, w):
        return gm.pbtpu_gmm(x, w, meta, tiles=tiles)

    def back(kept, dy):
        x, w = kept
        return (gm.pbtpu_gmm(dy, w, meta, transposed=True, tiles=(tn, tk)),
                gm.pbtpu_tgmm(x, dy, meta, tiles=tiles))

    product.defvjp(lambda x, w: (product(x, w), (x, w)), back)
    return product


@pytest.mark.parametrize("case", ["uneven_off_the_tile",
                                  "empty_first_middle_last"])
def test_bfloat16_operands_give_float32_sums(case):
    sizes, x, w, probe, live, meta, _ = _operands(case, jnp.bfloat16)
    (_, y), (dx, dw) = jax.jit(jax.value_and_grad(
        lambda x, w: _masked(lambda x, w: gm.grouped_matmul(x, w, meta),
                             x, w, probe, live),
        argnums=(0, 1), has_aux=True))(x, w)
    assert (y.dtype, dx.dtype, dw.dtype) == (jnp.float32, jnp.bfloat16,
                                             jnp.bfloat16)
    # the same operands, widened: sums in float32 lose nothing a float32
    # product of them has (a bfloat16 sum over K = 32 would lose 2^-8)
    wide = gm.grouped_matmul_reference(x.astype(jnp.float32),
                                       w.astype(jnp.float32), sizes)
    np.testing.assert_allclose(y, jnp.where(live, wide, 0), rtol=1e-6,
                               atol=1e-6)
    # and the cotangents are float32 sums of bfloat16 operands, the
    # output's cotangent among them, rounded once on the way out
    dy = jnp.where(live, probe, 0).astype(jnp.bfloat16).astype(jnp.float32)
    _, back = jax.vjp(lambda x, w: gm.grouped_matmul_reference(x, w, sizes),
                      x.astype(jnp.float32), w.astype(jnp.float32))
    dx_wide, dw_wide = back(dy)
    np.testing.assert_allclose(dx.astype(jnp.float32),
                               jnp.where(live, dx_wide, 0), rtol=2 ** -7,
                               atol=1e-6)
    np.testing.assert_allclose(dw.astype(jnp.float32), dw_wide,
                               rtol=2 ** -7, atol=1e-6)


def _visits_by_loop(sizes, tile):
    """(group, row tile) of every visit, in order, by the plain rule: a
    group's tiles are those it has a row in; a group with no rows is
    visited once, at a tile that holds a live row (tile 0 if none does)."""
    ends = np.cumsum(sizes)
    last = max(-(-int(ends[-1]) // tile) - 1, 0)
    out = []
    for g, (size, end) in enumerate(zip(sizes, ends)):
        start = end - size
        if size == 0:
            out.append((g, min(start // tile, last)))
        else:
            out += [(g, t) for t in range(start // tile, -(-end // tile))]
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_tile_metadata_equals_a_plain_loop(case):
    sizes, rows, _, _, tm, _ = CASES[case]
    meta = gm.group_tiles(jnp.asarray(sizes, jnp.int32), rows, tm)
    want = _visits_by_loop(np.asarray(sizes), tm)
    n = int(meta.visits)
    assert n == len(want) <= meta.groups.shape[0]
    assert list(zip(np.asarray(meta.groups)[:n].tolist(),
                    np.asarray(meta.tiles)[:n].tolist())) == want
    np.testing.assert_array_equal(meta.offsets,
                                  np.concatenate([[0], np.cumsum(sizes)]))
    # the entries past the visits stay inside the arrays (an index map
    # may read them)
    assert int(jnp.max(meta.tiles)) <= max(-(-sum(sizes) // tm) - 1, 0)
    assert int(jnp.max(meta.groups)) < len(sizes)


def test_fewer_rows_than_the_metadata_was_made_for_take_it_unchanged():
    """A rung under the whole chunk: the metadata is the chunk's."""
    sizes, x, w, _, live, _, _ = _operands("short_of_the_rows")
    meta = gm.group_tiles(sizes, 4 * x.shape[0], 16)
    y = gm.grouped_matmul(x, w, meta)
    want = gm.grouped_matmul_reference(x, w, sizes)
    np.testing.assert_allclose(jnp.where(live, y, 0), want, atol=1e-5)
    with pytest.raises(ValueError, match="rows are more than"):
        gm.pbtpu_gmm(jnp.tile(x, (8, 1)), w, meta)


# the token cells' grouped products: (tokens a chunk, choices a token,
# experts, held, D, H) — benchmark/configs/*.json, trainer.expert_chunk
CELLS = {"smallthinker_21b_ep4": (4096, 6, 64, 16, 2560, 768),
         "nemotron3_nano_ep16": (4096, 6, 128, 8, 2688, 1856),
         "lfm2_24b_a2b_ep8": (4096, 4, 64, 8, 2048, 1536),
         "kanana2_30b_a3b_ep8": (4096, 6, 128, 16, 2048, 768),
         "kimi_linear_48b_a3b_ep32": (4096, 8, 256, 8, 2304, 1024)}


def _legal(tile, dim):
    return dim % tile == 0 and (tile == dim or tile % 128 == 0)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_tile_rule_gives_legal_tiles_at_every_rung(cell):
    tokens, choices, experts, held, d, h = CELLS[cell]
    rows = tokens * choices
    tm = gm.row_tile(rows * held // experts, held)
    # a held expert's fair load spans two row tiles, or the least tile
    assert tm == 128 or (tm in (256, 512) and 2 * tm <= rows // experts)
    assert gm.row_tile(8 * rows * held // experts, held) > tm
    for rung in route_rungs(rows, held, experts):
        assert -(-rung // tm) + held - 1 <= -(-rows // tm) + held - 1
    for k, n in ((d, h), (h, d)):
        tk, tn = gm.gmm_tiles(tm, k, n)
        assert _legal(tk, k) and _legal(tn, n)
        assert tk == k      # a group's weights are streamed once
        assert gm._gmm_bytes(tm, tk, tn, k, 2, 2) <= gm._VMEM_TILES
        tk, tn = gm.tgmm_tiles(tm, k, n)
        assert _legal(tk, k) and _legal(tn, n)
        assert gm._tgmm_bytes(tm, tk, tn, 2, 2, 2) <= gm._VMEM_TILES
