"""The Mamba-2 state-space scan (SSD) in chunks, forward and backward.

For every head ``h`` with a state ``S`` of ``(P, N)`` (``P`` the head's
channels, ``N`` the state size), over the positions of one sequence:

    a_t = exp(dt_t A),  A = -exp(A_log)            a decay a head and step
    S_t = a_t S_{t-1} + dt_t x_t B_t^T             x_t (P,), B_t (N,)
    y_t = S_t C_t + D x_t                          C_t (N,)

``x (B, T, H, P)``, ``dt (B, T, H)`` (the step sizes, already positive),
``A_log, D (H,)``, ``Bm, Cm (B, T, G, N)`` with ``H = hg * G``: head ``h``
reads group ``h // hg``. ``ssm_scan_reference`` is that recurrence taken
literally. ``ssm_scan`` is its chunked form: with ``cum_t`` the sum of
``dt A`` from the chunk's first position through ``t``,

    y_t     = sum_{s <= t in chunk} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s
              + exp(cum_t) S_in C_t                          (+ D x_t)
    S_out   = exp(cum_last) S_in + sum_s exp(cum_last - cum_s) dt_s x_s B_s^T

so a chunk is four matrix products a head (``C B^T`` once a group) and the
states pass from chunk to chunk. One program instance takes one chunk of
one group: its heads share ``C B^T`` and add up into the group's ``dB``
and ``dC``; the chunks of a sequence run in order (backward: in reverse)
with the state in VMEM. Heads narrower than a lane tile are taken
``128 // P`` at a time, side by side in the lanes, each under its own mask
of decays. The backward pass recomputes within the chunk from the chunk's
entering state, the only thing the forward pass stores beside ``y``.

Every exponent is a sum of ``dt A <= 0``, so nothing overflows; decays and
their cumulative sums are float32 whatever ``x`` is, the matrix products
take operands of ``x``'s dtype (the caller casts ``x``, ``Bm``, ``Cm`` to
bfloat16 on the chip, as it does for attention) with float32 sums. The
cumulative sums themselves, ``D x`` and ``A_log`` are plain JAX around the
kernels.

Names in a profile: ``pbtpu_ssm_fwd``, ``pbtpu_ssm_bwd``. Off a TPU the
kernels run in the Pallas interpreter (tests: tiny shapes) — except inside
a ``check_vma`` shard_map, where the interpreter cannot run: a trainer on
a CPU mesh takes ``ssm_scan_reference``. On a TPU the geometry must be
lane-aligned (chunk and ``N`` multiples of 128, the heads' lanes whole
tiles); where it is not, ``ssm_scan`` raises, as for a length that is no
multiple of the chunk — never 4096 steps of the reference in silence,
never a kernel under another name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddlebox_tpu.ops.flash_attention import LANES, NT, out_struct

_FAR = -1e30        # exp(_FAR) == 0: the masked half of a chunk's decays


def ssm_scan_reference(x, dt, A_log, Bm, Cm, D):
    """The literal recurrence, one position at a time, in float32."""
    B, T, H, P = x.shape
    hg = H // Bm.shape[2]
    f32 = lambda v: v.astype(jnp.float32)
    A = -jnp.exp(f32(A_log))

    def step(S, inp):
        xt, dtt, bt, ct = inp              # (B,H,P) (B,H) (B,G,N) (B,G,N)
        bt, ct = (jnp.repeat(v, hg, axis=1) for v in (bt, ct))
        S = jnp.exp(dtt * A)[..., None, None] * S \
            + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        return S, jnp.sum(S * ct[:, :, None, :], axis=-1)

    S0 = jnp.zeros((B, H, P, Bm.shape[-1]), jnp.float32)
    vma = tuple(getattr(jax.typeof(x), "vma", ()))
    if vma:         # inside shard_map the carry varies as the inputs do
        S0 = lax.pcast(S0, vma, to="varying")
    _, y = lax.scan(step, S0, tuple(
        jnp.moveaxis(f32(v), 1, 0) for v in (x, dt, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1) + f32(D)[:, None] * f32(x)
    return y.astype(x.dtype)


def scan_geometry(chunk: int, heads_per_group: int, P: int, N: int):
    """(heads a lane tile, lanes a tile) for the kernels, or None where
    the chip's tile layout refuses the shape (``ssm_scan`` then raises)."""
    hp = 1 if P >= LANES else min(heads_per_group, LANES // P)
    while heads_per_group % hp:
        hp -= 1
    if jax.default_backend() == "tpu" and (
            chunk % LANES or N % LANES or (hp * P) % LANES):
        return None
    return hp, hp * P


# -- what both kernels compute of a chunk -----------------------------------

def _dot(a, b):
    return lax.dot(a, b, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    return lax.dot_general(a, b, NT, preferred_element_type=jnp.float32)


class _Chunk:
    """One chunk of one group inside a kernel: the per-head columns of
    ``dt`` and ``cum`` spread over a tile's lanes, and each head's masked
    matrix of decays."""

    def __init__(self, dt_ref, cumc_ref, cumr_ref, L, P, hp, Wt):
        self.dt_ref, self.cumc_ref, self.cumr_ref = dt_ref, cumc_ref, cumr_ref
        self.L, self.hp = L, hp
        lane_head = lax.broadcasted_iota(jnp.int32, (L, Wt), 1) // P
        self.lanes = [lane_head == k for k in range(hp)]
        self.lanes_of_row = [m[:1] for m in self.lanes]         # (1, Wt)
        row_head = lax.broadcasted_iota(jnp.int32, (Wt, 1), 0) // P
        self.rows = [row_head == k for k in range(hp)]
        t = lax.broadcasted_iota(jnp.int32, (L, L), 0)
        s = lax.broadcasted_iota(jnp.int32, (L, L), 1)
        self.causal = s <= t

    def spread(self, cols, masks=None):
        """Per-head values (broadcastable against a tile) onto the lanes
        (or, with ``masks=self.rows``, the rows) of their heads."""
        masks = self.lanes if masks is None else masks
        out = jnp.where(masks[0], cols[0], 0.0)
        for m, c in zip(masks[1:], cols[1:]):
            out = jnp.where(m, c, out)
        return out

    def head_sums(self, v):
        """(L, 1) a head of the tile: v summed over the head's lanes."""
        return [jnp.sum(jnp.where(m, v, 0.0), axis=1, keepdims=True)
                for m in self.lanes]

    def dt(self, q):
        return self.spread([self.dt_ref[:, j:j + 1] for j in self.heads(q)])

    def cum(self, q):
        return self.spread([self.cumc_ref[:, j:j + 1] for j in self.heads(q)])

    def last(self, j):
        """cum at the chunk's last position, head j: (1, 1)."""
        return self.cumc_ref[self.L - 1:self.L, j:j + 1]

    def last_row(self, q):
        """The same over the lanes of tile q's heads: (1, Wt) (one row,
        so that it spreads over lanes first and over sublanes after)."""
        return self.spread([self.last(j) for j in self.heads(q)],
                           self.lanes_of_row)

    def heads(self, q):
        return range(q * self.hp, (q + 1) * self.hp)

    def decays(self, j):
        """exp(cum_t - cum_s) for s <= t, else 0: (L, L), head j."""
        seg = self.cumc_ref[:, j:j + 1] - self.cumr_ref[j:j + 1, :]
        return jnp.exp(jnp.where(self.causal, seg, _FAR))


# -- forward ---------------------------------------------------------------

def _fwd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref, y_ref,
                st_ref, state, *, L, P, hp, Wt, nt):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    ch = _Chunk(dt_ref, cumc_ref, cumr_ref, L, P, hp, Wt)
    cd = x_ref.dtype
    bm, cm = b_ref[...], c_ref[...]
    gm = _dot_nt(cm, bm)                                    # C B^T (L, L)
    for q in range(nt):
        lanes = slice(q * Wt, (q + 1) * Wt)
        cum = ch.cum(q)
        xdt = x_ref[:, lanes].astype(jnp.float32) * ch.dt(q)
        s_in = state[lanes, :]                              # (Wt, N)
        st_ref[lanes, :] = s_in
        y = jnp.exp(cum) * _dot_nt(cm, s_in.astype(cd))
        for k, j in enumerate(ch.heads(q)):
            m = (gm * ch.decays(j)).astype(cd)
            y += _dot(m, jnp.where(ch.lanes[k], xdt, 0.0).astype(cd))
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        w = (jnp.exp(ch.last_row(q) - cum) * xdt).T.astype(cd)   # (Wt, L)
        carry = ch.spread([jnp.exp(ch.last(j)) for j in ch.heads(q)],
                          ch.rows)
        state[lanes, :] = carry * s_in + _dot(w, bm)


def _specs(L, W, N, hg, nc, *, reverse: bool):
    """BlockSpecs for a grid (B, G, chunk): token-major arrays (B, T, G *
    lanes), the per-head columns (B, G, T, hg) and rows (B, G, hg, T), and
    the states (B, nc, G * W, N)."""
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    tok = lambda width: pl.BlockSpec((None, L, width),
                                     lambda b, g, c: (b, at(c), g))
    col = pl.BlockSpec((None, None, L, hg), lambda b, g, c: (b, g, at(c), 0))
    row = pl.BlockSpec((None, None, hg, L), lambda b, g, c: (b, g, 0, at(c)))
    st = pl.BlockSpec((None, None, W, N), lambda b, g, c: (b, at(c), g, 0))
    return tok(W), tok(N), col, row, st


def _params(interpret: bool):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


def _forward(x, dtc, cumc, cumr, bm, cm, geom, interpret):
    B, T, HP = x.shape
    _, G, _, hg = dtc.shape
    L, P, hp, Wt = geom
    N, W, nc = bm.shape[-1] // G, HP // G, T // L
    x_spec, n_spec, col, row, st = _specs(L, W, N, hg, nc, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, L=L, P=P, hp=hp, Wt=Wt, nt=W // Wt),
        grid=(B, G, nc),
        in_specs=[x_spec, col, col, row, n_spec, n_spec],
        out_specs=[x_spec, st],
        out_shape=[out_struct(x.shape, x.dtype, x),
                   out_struct((B, nc, HP, N), jnp.float32, x)],
        scratch_shapes=[pltpu.VMEM((W, N), jnp.float32)],
        name="pbtpu_ssm_fwd", **_params(interpret),
    )(x, dtc, cumc, cumr, bm, cm)


# -- backward --------------------------------------------------------------

def _bwd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref, st_ref,
                dy_ref, dx_ref, ddt_ref, dcumc_ref, dcumr_ref, db_ref, dc_ref,
                dstate, *, L, P, hp, Wt, nt, hg):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate[...] = jnp.zeros_like(dstate)

    ch = _Chunk(dt_ref, cumc_ref, cumr_ref, L, P, hp, Wt)
    cd = x_ref.dtype
    bm, cm = b_ref[...], c_ref[...]
    gm = _dot_nt(cm, bm)
    col_of = lax.broadcasted_iota(jnp.int32, (L, hg), 1)
    row_of = lax.broadcasted_iota(jnp.int32, (hg, L), 0)
    at_last = lax.broadcasted_iota(jnp.int32, (L, 1), 0) == L - 1
    dgm = jnp.zeros((L, L), jnp.float32)
    db = jnp.zeros(bm.shape, jnp.float32)
    dc = jnp.zeros(cm.shape, jnp.float32)
    ddt = jnp.zeros((L, hg), jnp.float32)
    dcumc = jnp.zeros((L, hg), jnp.float32)
    dcumr = jnp.zeros((hg, L), jnp.float32)
    for q in range(nt):
        lanes = slice(q * Wt, (q + 1) * Wt)
        heads = list(ch.heads(q))
        x = x_ref[:, lanes].astype(jnp.float32)
        dy = dy_ref[:, lanes]
        dy32 = dy.astype(jnp.float32)
        dtq, cum = ch.dt(q), ch.cum(q)
        xdt = x * dtq
        s_in, ds_out = st_ref[lanes, :], dstate[lanes, :]
        # the carried state's part of y: y += exp(cum) (C S_in^T)
        r = _dot_nt(cm, s_in.astype(cd))
        dr = dy32 * jnp.exp(cum)
        dc += _dot(dr.astype(cd), s_in.astype(cd))
        ds_in = _dot(dr.T.astype(cd), cm)
        # the chunk's end state: S_out = carry S_in + (F xdt)^T B
        f = jnp.exp(ch.last_row(q) - cum)
        w = f * xdt
        dw = _dot_nt(bm, ds_out.astype(cd))
        db += _dot(w.astype(cd), ds_out.astype(cd))
        dxdt = f * dw
        carry = ch.spread([jnp.exp(ch.last(j)) for j in heads], ch.rows)
        ds_in += carry * ds_out
        # d cum, the parts that are a tile wide: + dy . y_carried at t,
        # - dW . W at s; and at the chunk's last position what S_out's
        # decays gather
        wide = ch.head_sums(dr * r - dw * w)
        gathered = ch.head_sums(dw * w)
        held = jnp.sum(carry * s_in * ds_out, axis=1, keepdims=True)
        for k, j in enumerate(heads):
            # within the chunk: y += (gm * decays) xdt, head by head
            decay = ch.decays(j)
            m = gm * decay
            dm = _dot_nt(jnp.where(ch.lanes[k], dy32, 0.0).astype(cd),
                         xdt.astype(cd))
            dxdt += jnp.where(ch.lanes[k], _dot(m.T.astype(cd), dy), 0.0)
            dgm += dm * decay
            qm = dm * m
            to_last = jnp.sum(gathered[k]) + jnp.sum(
                jnp.where(ch.rows[k], held, 0.0))
            dcum_j = wide[k] + jnp.sum(qm, axis=1, keepdims=True) \
                + jnp.where(at_last, to_last, 0.0)
            dcumc = jnp.where(col_of == j, dcum_j, dcumc)
            dcumr = jnp.where(row_of == j,
                              -jnp.sum(qm, axis=0, keepdims=True), dcumr)
        dx_ref[:, lanes] = (dxdt * dtq).astype(dx_ref.dtype)
        for j, v in zip(heads, ch.head_sums(dxdt * x)):
            ddt = jnp.where(col_of == j, v, ddt)
        dstate[lanes, :] = ds_in
    dc += _dot(dgm.astype(cd), bm)
    db += _dot(dgm.T.astype(cd), cm)
    db_ref[...] = db.astype(db_ref.dtype)
    dc_ref[...] = dc.astype(dc_ref.dtype)
    ddt_ref[...] = ddt
    dcumc_ref[...] = dcumc
    dcumr_ref[...] = dcumr


def _backward(x, dtc, cumc, cumr, bm, cm, states, dy, geom, interpret):
    B, T, HP = x.shape
    _, G, _, hg = dtc.shape
    L, P, hp, Wt = geom
    N, W, nc = bm.shape[-1] // G, HP // G, T // L
    x_spec, n_spec, col, row, st = _specs(L, W, N, hg, nc, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, L=L, P=P, hp=hp, Wt=Wt, nt=W // Wt,
                          hg=hg),
        grid=(B, G, nc),
        in_specs=[x_spec, col, col, row, n_spec, n_spec, st, x_spec],
        out_specs=[x_spec, col, col, row, n_spec, n_spec],
        out_shape=[out_struct(x.shape, x.dtype, x),
                   out_struct(dtc.shape, jnp.float32, x),
                   out_struct(cumc.shape, jnp.float32, x),
                   out_struct(cumr.shape, jnp.float32, x),
                   out_struct(bm.shape, bm.dtype, x),
                   out_struct(cm.shape, cm.dtype, x)],
        scratch_shapes=[pltpu.VMEM((W, N), jnp.float32)],
        name="pbtpu_ssm_bwd", **_params(interpret),
    )(x, dtc, cumc, cumr, bm, cm, states, dy)


# -- the op ----------------------------------------------------------------

def _columns(v, G: int):
    """(B, T, H) -> (B, G, T, hg): a group's heads as the minor axis."""
    B, T, H = v.shape
    return v.reshape(B, T, G, H // G).transpose(0, 2, 1, 3)


def _heads(v):
    """(B, G, T, hg) -> (B, T, H)."""
    B, G, T, hg = v.shape
    return v.transpose(0, 2, 1, 3).reshape(B, T, G * hg)


def _chunk_cumsum(a, L: int, reverse: bool = False):
    B, T, H = a.shape
    return lax.cumsum(a.reshape(B, T // L, L, H), axis=2,
                      reverse=reverse).reshape(B, T, H)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(x, dt, a, bm, cm, G, geom, interpret):
    """x (B, T, H * P), dt, a = dt A (B, T, H) float32, bm, cm (B, T,
    G * N): y (B, T, H * P) without the D x term."""
    return _scan_fwd(x, dt, a, bm, cm, G, geom, interpret)[0]


def _scan_fwd(x, dt, a, bm, cm, G, geom, interpret):
    cum = _columns(_chunk_cumsum(a, geom[0]), G)
    dtc, cumr = _columns(dt, G), jnp.swapaxes(cum, 2, 3)
    y, states = _forward(x, dtc, cum, cumr, bm, cm, geom, interpret)
    return y, (x, dtc, cum, cumr, bm, cm, states)


def _scan_bwd(G, geom, interpret, res, dy):
    dx, ddt, dcumc, dcumr, db, dc = _backward(*res, dy, geom, interpret)
    dcum = _heads(dcumc + jnp.swapaxes(dcumr, 2, 3))
    return dx, _heads(ddt), _chunk_cumsum(dcum, geom[0], reverse=True), db, dc


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssm_scan(x, dt, A_log, Bm, Cm, D, *, chunk: int = 128,
             interpret: bool | None = None):
    """The chunked scan; ``interpret``: None = the Mosaic kernels on a
    TPU, the Pallas interpreter elsewhere. A length that is no multiple of
    ``chunk`` is refused, and on a TPU a geometry that is not lane-aligned
    (``scan_geometry``)."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    if H % G or Bm.shape != Cm.shape or dt.shape != (B, T, H):
        raise ValueError(f"x {x.shape}, dt {dt.shape} do not group over "
                         f"B {Bm.shape}, C {Cm.shape}")
    L = min(int(chunk), T)
    if T % L:
        raise ValueError(f"a sequence of {T} positions does not divide into "
                         f"chunks of {L}")
    tiles = scan_geometry(L, H // G, P, N)
    if tiles is None:
        raise ValueError(
            f"the chip's kernels take chunks and states of whole 128-lane "
            f"tiles and heads that fill them: chunk {L}, state {N}, "
            f"{H // G} heads of {P} a group do not")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if interpret and getattr(jax.typeof(x), "vma", frozenset()):
        return ssm_scan_reference(x, dt, A_log, Bm, Cm, D)
    dt = dt.astype(jnp.float32)
    a = dt * -jnp.exp(A_log.astype(jnp.float32))
    y = _scan(x.reshape(B, T, H * P), dt, a, Bm.reshape(B, T, G * N),
              Cm.reshape(B, T, G * N), G, (L, P, *tiles), bool(interpret))
    y = y.reshape(x.shape).astype(jnp.float32) \
        + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return y.astype(x.dtype)
