"""The grouped matrix products of the held experts, forward and backward.

    y[r]  = x[r] @ w[g(r)]            pbtpu_gmm        (rows, K) x (G, K, N)
    dx[r] = dy[r] @ w[g(r)]^T         pbtpu_gmm, the weights read transposed
    dw[g] = x[rows of g]^T @ dy[...]  pbtpu_tgmm       -> (G, K, N)

``x (rows, K)`` is sorted by group: group ``g`` owns the ``sizes[g]`` rows
after those of the groups before it, ``w (G, K, N)`` holds a matrix a
group. Rows past the last group belong to none: their tiles are not
visited, and what the outputs hold there is undefined (the caller masks
them, on both sides). A group with no rows gives a ``dw`` of zeros.
Operands go to the matrix unit as they come (bfloat16 on the chip), every
sum is float32 over the whole of K (``tgmm``: over the whole group), and
the outputs are float32 — but for ``dw`` under differentiation, which
leaves the kernel as the weights' dtype, the float32 sum rounded once.

The algorithm is that of JAX's ``pallas.ops.tpu.megablox``: the rows are
cut into tiles of ``row_tile``, and a *visit* is one (group, row tile)
pair in which the group has a row — a tile that holds the boundary of two
groups is visited once for each, and each visit keeps its own rows
(``gmm``: a masked store; ``tgmm``: masked operands). The visits — which
group, which tile, how many — are the tile metadata, ``group_tiles``: a few
small integer arrays computed from ``sizes`` alone, so one set serves every
call over the same sorted rows whatever its K and N: a chunk's forward
products, their recomputation and both cotangents. They reach the kernels
as scalar prefetch and steer the block index maps; the grid's visit axis
is as long as the visits, so tiles past the last group cost nothing.

The tiles follow the operands, by one rule (``row_tile``, ``gmm_tiles``,
``tgmm_tiles``):

* the row tile is the largest of 512 / 256 / 128 that the mean group
  (rows / G) holds twice — every group boundary costs one more visit of a
  whole tile, so a tile as large as the groups spends half its visits on
  boundaries; and Mosaic unrolls a block's product, so a kernel's code
  doubles with the tile, in every one of the copies a program holds;
* K and N tiles are as large as ``_VMEM_TILES`` bytes hold double-buffered.
  ``gmm`` keeps K whole where it can: the grid runs N tiles, then visits,
  then K tiles, and with one K tile a group's ``(K, tn)`` weight block stays
  put over the run of row tiles of that group — it is streamed once a group
  and N tile, not once a row tile. ``tgmm`` runs visits innermost and sums
  a group's ``(tk, tn)`` block in a float32 accumulator, so each group's
  gradient is written once; of the tile pairs that fit it takes the one
  that reads the operands the fewest times over.

A dimension is cut only into equal tiles of whole 128 lanes (or not at
all), so no K tile is ragged; the last row tile may be (``rows`` need not
be whole tiles: the masks cover it).

Names in a profile: ``pbtpu_gmm``, ``pbtpu_tgmm``. Off a TPU the kernels
run in the Pallas interpreter (tests: tiny shapes) — except inside a
``check_vma`` shard_map, where the interpreter cannot run: a trainer on a
CPU mesh takes ``grouped_matmul_reference``, which is ``lax.ragged_dot``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddlebox_tpu.ops.flash_attention import LANES, out_struct

_ROW_TILES = (512, 256, 128)
# what the tiles of one call may take of a core's 128 MiB of VMEM, double
# buffers and the accumulator included, and the limit the compiler is given
_VMEM_TILES = 40 << 20
_VMEM_LIMIT = 64 << 20


def grouped_matmul_reference(x, w, sizes):
    """The plain form: XLA's grouped product (off a TPU it writes zeros
    past the last group), float32 sums and result."""
    return lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)


# -- the tile metadata -------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GroupTiles:
    """The visits of a grouped product over rows sorted by group, in
    order: ``offsets (G + 1,)`` the groups' first rows, ``groups`` and
    ``tiles`` (V,) each visit's group and row tile, ``visits`` () how many
    of the V there are; ``row_tile`` is static."""
    offsets: jax.Array
    groups: jax.Array
    tiles: jax.Array
    visits: jax.Array
    row_tile: int = dataclasses.field(metadata=dict(static=True))


def row_tile(rows: int, groups: int) -> int:
    """The largest row tile the mean group holds twice."""
    return next((t for t in _ROW_TILES if 2 * t * groups <= rows),
                _ROW_TILES[-1])


def group_tiles(sizes: jax.Array, rows: int, tile: int) -> GroupTiles:
    """The visits of ``sizes (G,)`` int32 over at most ``rows`` sorted rows
    in row tiles of ``tile``. A group is visited once a row tile it has a
    row in, in order; a group with no rows is visited once too (``tgmm``
    zeroes its block there; ``gmm`` stores nothing), at a tile that holds
    a live row. V = the row tiles + G - 1 bounds the visits of any sizes,
    and any fewer rows than ``rows`` that still hold ``sum(sizes)``."""
    g = sizes.shape[0]
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    last = jnp.maximum(-(-ends[-1] // tile) - 1, 0)
    first = jnp.minimum(offsets[:-1] // tile, last)
    count = jnp.where(sizes > 0, -(-ends // tile) - first, 1)
    upto = jnp.cumsum(count)
    visit = jnp.arange(-(-rows // tile) + g - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(upto, visit, side="right", method="compare_all"),
        g - 1).astype(jnp.int32)
    tiles = jnp.minimum(first[group] + visit - (upto - count)[group], last)
    return GroupTiles(offsets, group, tiles.astype(jnp.int32),
                      upto[-1].astype(jnp.int32), int(tile))


# -- the K and N tiles -------------------------------------------------------

def _cuts(n: int) -> list[int]:
    """What a dimension of n may be tiled by, descending: itself, and its
    divisors of whole lane tiles."""
    return [n] + [t for t in range(n - LANES, 0, -LANES)
                  if n % t == 0 and t % LANES == 0]


def _gmm_bytes(tm, tk, tn, k, x_bytes, w_bytes):
    acc = tm * tn * 4 if tk < k else 0
    return 2 * (tm * tk * x_bytes + tk * tn * w_bytes + tm * tn * 4) + acc


def gmm_tiles(tm: int, k: int, n: int, x_bytes: int = 2,
              w_bytes: int = 2) -> tuple[int, int]:
    """(tk, tn) for ``gmm`` over a contraction of k and an output width of
    n: K whole and the widest N tile that fits beside it; where no N tile
    does, the largest K tile under the narrowest."""
    fits = lambda tk, tn: _gmm_bytes(tm, tk, tn, k, x_bytes,
                                     w_bytes) <= _VMEM_TILES
    for tn in _cuts(n):
        if fits(k, tn):
            return k, tn
    tn = _cuts(n)[-1]
    return next((tk for tk in _cuts(k) if fits(tk, tn)), _cuts(k)[-1]), tn


def _tgmm_bytes(tm, tk, tn, x_bytes, dy_bytes, out_bytes):
    return (2 * (tm * tk * x_bytes + tm * tn * dy_bytes
                 + tk * tn * out_bytes) + tk * tn * 4)


def tgmm_tiles(tm: int, k: int, n: int, x_bytes: int = 2,
               dy_bytes: int = 2, out_bytes: int = 2) -> tuple[int, int]:
    """(tk, tn) for ``tgmm``'s (k, n) blocks: of the pairs that fit, the
    one that reads the operands the fewest times over (x once an N tile,
    dy once a K tile); the largest block among equals."""
    pairs = [(tk, tn) for tk in _cuts(k) for tn in _cuts(n)
             if _tgmm_bytes(tm, tk, tn, x_bytes, dy_bytes, out_bytes)
             <= _VMEM_TILES] or [(_cuts(k)[-1], _cuts(n)[-1])]
    return min(pairs, key=lambda p: (k * x_bytes * (n // p[1])
                                     + n * dy_bytes * (k // p[0]),
                                     -p[0] * p[1]))


# -- the kernels -------------------------------------------------------------

def _mine(meta, visit, shape, tm):
    """Which rows of the visit's tile are its group's, (tm, width)."""
    offsets, groups, tiles = meta
    g = groups[visit]
    row = tiles[visit] * tm + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _gmm_kernel(offsets, groups, tiles, x_ref, w_ref, o_ref, *acc, tm, nk,
                transposed):
    visit, ki = pl.program_id(1), pl.program_id(2)
    part = lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (1 if transposed else 0,)), ((), ())),
        preferred_element_type=jnp.float32)

    def store(total):
        mine = _mine((offsets, groups, tiles), visit, o_ref.shape, tm)
        # the other rows of the tile are another visit's, before or after
        # this one while the block stays in VMEM, or nobody's
        o_ref[...] = jnp.where(mine, total, o_ref[...])

    if nk == 1:
        store(part)
        return
    acc, = acc

    @pl.when(ki == 0)
    def _():
        acc[...] = part

    @pl.when(ki > 0)
    def _():
        acc[...] += part

    @pl.when(ki == nk - 1)
    def _():
        store(acc[...])


def _tgmm_kernel(offsets, groups, tiles, x_ref, dy_ref, o_ref, acc, *, tm):
    visit, last = pl.program_id(2), pl.num_programs(2) - 1
    meta = (offsets, groups, tiles)
    # rows of another group, or of none (they may hold anything), add
    # nothing: a select, since 0 * nan is nan
    x = jnp.where(_mine(meta, visit, x_ref.shape, tm), x_ref[...], 0)
    dy = jnp.where(_mine(meta, visit, dy_ref.shape, tm), dy_ref[...], 0)
    part = lax.dot_general(x, dy, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)
    group = groups[visit]
    opens = (visit == 0) | (groups[jnp.maximum(visit - 1, 0)] != group)

    @pl.when(opens)
    def _():
        acc[...] = part

    @pl.when(jnp.logical_not(opens))
    def _():
        acc[...] += part

    @pl.when((visit == last) | (groups[jnp.minimum(visit + 1, last)]
                                != group))
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _params(interpret: bool):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)}


def _check(x, meta: GroupTiles, what: str):
    if x.ndim != 2:
        raise ValueError(f"{what}: x {x.shape} is not (rows, K)")
    if -(-x.shape[0] // meta.row_tile) + meta.offsets.shape[0] - 2 \
            > meta.groups.shape[0]:
        raise ValueError(f"{what}: {x.shape[0]} rows are more than the "
                         f"metadata's {meta.groups.shape[0]} visits in tiles "
                         f"of {meta.row_tile} were made for")


def _interpreted(interpret):
    return jax.default_backend() != "tpu" if interpret is None \
        else bool(interpret)


def pbtpu_gmm(x, w, meta: GroupTiles, *, transposed: bool = False,
              tiles: tuple[int, int] | None = None,
              interpret: bool | None = None):
    """``x[r] @ w[g(r)]`` -> (rows, N) float32; ``transposed``: ``w`` is
    (G, N, K) and read as its transpose, through the index map.
    ``tiles``: (tk, tn) in place of ``gmm_tiles``'s."""
    _check(x, meta, "pbtpu_gmm")
    m, k = x.shape
    n = w.shape[1] if transposed else w.shape[2]
    if w.shape[0] + 1 != meta.offsets.shape[0] \
            or w.shape[2 if transposed else 1] != k:
        raise ValueError(f"pbtpu_gmm: x {x.shape} against w {w.shape}"
                         f"{' transposed' if transposed else ''} in "
                         f"{meta.offsets.shape[0] - 1} groups")
    tm = meta.row_tile
    tk, tn = tiles or gmm_tiles(tm, k, n, x.dtype.itemsize, w.dtype.itemsize)
    nk = k // tk
    w_spec = pl.BlockSpec((None, tn, tk), lambda j, v, i, o, g, t:
                          (g[v], j, i)) if transposed else \
        pl.BlockSpec((None, tk, tn), lambda j, v, i, o, g, t: (g[v], i, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, nk=nk, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, meta.visits, nk),
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda j, v, i, o, g, t: (t[v], i)),
                      w_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, i, o, g, t: (t[v], j)),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if nk > 1 else [])),
        out_shape=out_struct((m, n), jnp.float32, x),
        name="pbtpu_gmm", **_params(_interpreted(interpret)),
    )(meta.offsets, meta.groups, meta.tiles, x, w)


def pbtpu_tgmm(x, dy, meta: GroupTiles, *, out_dtype=jnp.float32,
               tiles: tuple[int, int] | None = None,
               interpret: bool | None = None):
    """``x[rows of g]^T @ dy[rows of g]`` -> (G, K, N), float32 sums
    stored as ``out_dtype``, zeros for a group with no rows. ``tiles``:
    (tk, tn) in place of ``tgmm_tiles``'s."""
    _check(x, meta, "pbtpu_tgmm")
    if dy.ndim != 2 or dy.shape[0] != x.shape[0]:
        raise ValueError(f"pbtpu_tgmm: x {x.shape} and dy {dy.shape} are "
                         "not the same rows")
    (m, k), n = x.shape, dy.shape[1]
    tm = meta.row_tile
    tk, tn = tiles or tgmm_tiles(tm, k, n, x.dtype.itemsize,
                                 dy.dtype.itemsize,
                                 jnp.dtype(out_dtype).itemsize)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, k // tk, meta.visits),
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda j, i, v, o, g, t: (t[v], i)),
                      pl.BlockSpec((tm, tn),
                                   lambda j, i, v, o, g, t: (t[v], j))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda j, i, v, o, g, t: (g[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=out_struct((meta.offsets.shape[0] - 1, k, n), out_dtype,
                             x),
        name="pbtpu_tgmm", **_params(_interpreted(interpret)),
    )(meta.offsets, meta.groups, meta.tiles, x, dy)


# -- the op ------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _product(x, w, meta, interpret):
    return pbtpu_gmm(x, w, meta, interpret=interpret)


def _product_fwd(x, w, meta, interpret):
    return pbtpu_gmm(x, w, meta, interpret=interpret), (x, w, meta)


def _product_bwd(interpret, kept, dy):
    x, w, meta = kept
    # the cotangent goes to the matrix unit as the operands did. A group's
    # float32 sums leave ``tgmm`` as what they are the cotangent of —
    # rounded once, in the kernel: (G, K, N) in float32 would be written
    # and read again for the cast, twice the bytes of the result
    dy = dy.astype(x.dtype)
    dx = pbtpu_gmm(dy, w, meta, transposed=True, interpret=interpret)
    dw = pbtpu_tgmm(x, dy, meta, out_dtype=w.dtype, interpret=interpret)
    return dx.astype(x.dtype), dw, None


_product.defvjp(_product_fwd, _product_bwd)


def grouped_matmul(x, w, meta: GroupTiles, *,
                   interpret: bool | None = None):
    """``x[r] @ w[g(r)]`` for rows sorted into the groups ``meta`` is the
    ``group_tiles`` of, float32 sums and result, differentiable in ``x``
    and ``w``. ``interpret``: None = the Mosaic kernels on a TPU, the
    Pallas interpreter elsewhere."""
    vma = getattr(jax.typeof(x), "vma", frozenset())
    if _interpreted(interpret) and vma:
        return grouped_matmul_reference(x, w, jnp.diff(meta.offsets))
    # inside shard_map the weights are parameters and the metadata may be
    # anything: the kernels take them varying as the rows are, and the sum
    # of ``dw`` over the mesh is the cast's own transpose
    def varying(a):
        apart = tuple(vma - getattr(jax.typeof(a), "vma", frozenset()))
        return lax.pcast(a, apart, to="varying") if apart else a

    w, meta = jax.tree.map(varying, (w, meta))
    return _product(x, w, meta, _interpreted(interpret))
