"""The gated short convolution of an LFM2 mixer, between its two
projections, forward and backward.

    u[t] = B[t] * x[t]
    y[t] = C[t] * sum_{j < K} w[j] * u[t - (K - 1) + j]       u before the
                                                              sequence is 0

``B, C, x, y (batch, T, d)``, ``w (K, d)``: a causal convolution over time
of ``K`` taps, depthwise (a channel sees only itself), between two gates;
no bias, no activation. It is elementwise work, bound by the chip's
bandwidth: the forward pass reads three arrays and writes one, the
backward pass reads four and writes three (and ``dw``, a channel's sums).

    dC[t] = dy[t] * v[t],   v the convolution's output (recomputed)
    du[s] = sum_j w[j] * (dy * C)[s + (K - 1) - j]            anti-causal
    dB    = du * x,   dx = du * B
    dw[j] = sum over batch and t of (dy * C)[t] * u[t - (K - 1) + j]

One program instance takes a ``(block_t, block_d)`` tile of every array.
What a tile needs of its neighbours in time — the ``K - 1`` rows before it
(forward, and ``dw``), the ``K - 1`` rows after it (``du``) — arrives as a
second, 8-row view of the same arrays (the halo: the previous block's last
rows, the next block's first), so no instance depends on another and the
grid is parallel throughout. Inside a tile the shifted copies are sublane
rotations with the rows that wrapped replaced from the halo. ``dw`` leaves
the kernel as one partial sum a tile and is added up outside.

Everything is computed in float32 and returned in the inputs' dtype; no
operand is cast down to save bandwidth.

Names in a profile: ``pbtpu_short_conv_fwd``, ``pbtpu_short_conv_bwd``. Off
a TPU the kernels run in the Pallas interpreter (tests: tiny shapes) —
except inside a ``check_vma`` shard_map, where the interpreter cannot run:
a trainer on a CPU mesh takes the plain ``short_conv_reference``. Where
the geometry is not the kernels' (``conv_geometry``: ``T`` in whole blocks
of whole 8-row tiles, and on a TPU ``d`` in whole blocks of whole lane
tiles) ``short_conv`` is the reference too — never a kernel under another
name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddlebox_tpu.ops.flash_attention import LANES, out_struct

_HALO = 8           # rows of the halo's view: one sublane tile


def short_conv_reference(B, C, x, w):
    """The plain form: K shifted products over the whole sequence."""
    T, K = x.shape[1], w.shape[0]
    u = jnp.pad(B * x, ((0, 0), (K - 1, 0), (0, 0)))
    return C * sum(w[j] * u[:, j:j + T] for j in range(K))


def conv_geometry(T: int, d: int, K: int, block_t: int = 256,
                  block_d: int = 512):
    """(block_t, block_d) for the kernels, or None where the tile layout
    refuses the shape (the caller then takes the reference)."""
    bt, bd = min(block_t, T), min(block_d, d)
    if T % bt or bt % _HALO or d % bd or not 1 <= K - 1 <= _HALO:
        return None
    if jax.default_backend() == "tpu" and bd % LANES:
        return None
    return bt, bd


# -- what both kernels compute of a tile -------------------------------------

def _f32(ref):
    return ref[...].astype(jnp.float32)


def _shifted(a, edge, back: int):
    """a (bt, bd) moved `back` rows later in time (`back` < 0: earlier);
    the rows that wrapped come from `edge` (8, bd), the neighbouring
    block's nearest rows: its last for back > 0, its first for back < 0."""
    bt = a.shape[0]
    if back == 0:
        return a
    row = lax.broadcasted_iota(jnp.int32, a.shape, 0)
    out = pltpu.roll(a, back % bt, 0)
    for r in range(abs(back)):
        at, src = (r, _HALO - back + r) if back > 0 else (bt + back + r, r)
        out = jnp.where(row == at, edge[src:src + 1, :], out)
    return out


def _edge(first_ref, second_ref, keep):
    """The halo's product of two arrays, zero where there is no
    neighbour (before the sequence, after it)."""
    return jnp.where(keep, _f32(first_ref) * _f32(second_ref), 0.0)


def _fwd_kernel(b_ref, c_ref, x_ref, w_ref, bh_ref, xh_ref, y_ref, *, K):
    u = _f32(b_ref) * _f32(x_ref)
    before = _edge(bh_ref, xh_ref, pl.program_id(1) > 0)
    w = _f32(w_ref)
    v = sum(w[j:j + 1, :] * _shifted(u, before, K - 1 - j) for j in range(K))
    y_ref[...] = (_f32(c_ref) * v).astype(y_ref.dtype)


def _bwd_kernel(b_ref, c_ref, x_ref, w_ref, dy_ref, bh_ref, xh_ref, dyh_ref,
                ch_ref, db_ref, dc_ref, dx_ref, dw_ref, *, K, nt):
    i = pl.program_id(1)
    b, x, w = _f32(b_ref), _f32(x_ref), _f32(w_ref)
    u = b * x
    before = _edge(bh_ref, xh_ref, i > 0)
    dv = _f32(dy_ref) * _f32(c_ref)
    after = _edge(dyh_ref, ch_ref, i < nt - 1)
    v = jnp.zeros_like(u)
    du = jnp.zeros_like(u)
    dw = jnp.zeros(dw_ref.shape, jnp.float32)
    tap = lax.broadcasted_iota(jnp.int32, dw.shape, 0)
    for j in range(K):
        wj = w[j:j + 1, :]
        uj = _shifted(u, before, K - 1 - j)
        v += wj * uj
        du += wj * _shifted(dv, after, j - (K - 1))
        dw = jnp.where(tap == j, jnp.sum(dv * uj, axis=0, keepdims=True), dw)
    dc_ref[...] = (_f32(dy_ref) * v).astype(dc_ref.dtype)
    db_ref[...] = (du * x).astype(db_ref.dtype)
    dx_ref[...] = (du * b).astype(dx_ref.dtype)
    dw_ref[...] = dw


def _specs(bt, bd, K, nt):
    """BlockSpecs for a grid (batch, time block, channel block): a tile,
    the taps, the 8 rows before a tile and the 8 rows after it (clamped
    at the sequence's ends, where the kernel reads them as zero)."""
    per = bt // _HALO
    tile = pl.BlockSpec((None, bt, bd), lambda b, i, c: (b, i, c))
    taps = pl.BlockSpec((K, bd), lambda b, i, c: (0, c))
    before = pl.BlockSpec(
        (None, _HALO, bd), lambda b, i, c: (b, jnp.maximum(i * per - 1, 0), c))
    after = pl.BlockSpec(
        (None, _HALO, bd),
        lambda b, i, c: (b, jnp.minimum((i + 1) * per, nt * per - 1), c))
    return tile, taps, before, after


def _params(interpret: bool):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"))}


def _forward(B, C, x, w, blocks, interpret):
    n, T, d = x.shape
    bt, bd = blocks
    K = w.shape[0]
    tile, taps, before, _ = _specs(bt, bd, K, T // bt)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, K=K),
        grid=(n, T // bt, d // bd),
        in_specs=[tile, tile, tile, taps, before, before],
        out_specs=tile,
        out_shape=out_struct(x.shape, x.dtype, x),
        name="pbtpu_short_conv_fwd", **_params(interpret),
    )(B, C, x, w, B, x)


def _backward(B, C, x, w, dy, blocks, interpret):
    n, T, d = x.shape
    bt, bd = blocks
    K, nt = w.shape[0], T // bt
    tile, taps, before, after = _specs(bt, bd, K, nt)
    partial = pl.BlockSpec((None, None, _HALO, bd),
                           lambda b, i, c: (b, i, 0, c))
    dB, dC, dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K, nt=nt),
        grid=(n, nt, d // bd),
        in_specs=[tile, tile, tile, taps, tile, before, before, after, after],
        out_specs=[tile, tile, tile, partial],
        out_shape=[out_struct(x.shape, x.dtype, x)] * 3
        + [out_struct((n, nt, _HALO, d), jnp.float32, x)],
        name="pbtpu_short_conv_bwd", **_params(interpret),
    )(B, C, x, w, dy, B, x, dy, C)
    return dB, dC, dx, jnp.sum(dw[:, :, :K], axis=(0, 1)).astype(w.dtype)


# -- the op ------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _conv(B, C, x, w, blocks, interpret):
    return _forward(B, C, x, w, blocks, interpret)


def _conv_fwd(B, C, x, w, blocks, interpret):
    return _forward(B, C, x, w, blocks, interpret), (B, C, x, w)


def _conv_bwd(blocks, interpret, res, dy):
    return _backward(*res, dy, blocks, interpret)


_conv.defvjp(_conv_fwd, _conv_bwd)


def short_conv(B, C, x, w, *, block_t: int = 256, block_d: int = 512,
               interpret: bool | None = None):
    """``C * conv_K(B * x)`` over time, blocked. ``interpret``: None = the
    Mosaic kernels on a TPU, the Pallas interpreter elsewhere."""
    if not B.shape == C.shape == x.shape or x.ndim != 3 \
            or w.shape[1:] != x.shape[2:]:
        raise ValueError(f"B {B.shape}, C {C.shape}, x {x.shape} are not one "
                         f"(batch, T, d) with taps w {w.shape} of (K, d)")
    blocks = conv_geometry(x.shape[1], x.shape[2], w.shape[0], block_t,
                           block_d)
    if blocks is None:
        return short_conv_reference(B, C, x, w)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    vma = getattr(jax.typeof(x), "vma", frozenset())
    if interpret and vma:
        return short_conv_reference(B, C, x, w)
    # inside shard_map the taps are a parameter, the same on every chip:
    # the kernels take them varying as the activations are, and the sum of
    # ``dw`` over the mesh is the cast's own transpose
    apart = tuple(vma - getattr(jax.typeof(w), "vma", frozenset()))
    if apart:
        w = lax.pcast(w, apart, to="varying")
    return _conv(B, C, x, w, blocks, bool(interpret))
