"""Kimi Delta Attention (KDA): the gated delta rule with a decay per
channel, in chunks, forward and backward.

For every head, a state ``S`` of ``(K, V)`` and, over the positions of one
sequence,

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                   S_0 = 0

``q, k (B, H, T, K)``, ``v (B, H, T, V)``, ``log_a (B, H, T, K)`` (a decay a
channel and position, ``<= 0``), ``beta (B, H, T)``. ``kda_reference`` is
that recurrence taken literally (fla-org's ``naive_recurrent_kda``: decay,
then the delta rule's correction, then the read). ``kda`` is its chunked
form: with ``G_t`` the sum of ``log a`` from the chunk's first position
through ``t`` (a vector over the K channels) and ``S_0`` the state a chunk
enters with,

    M[t, s] = beta_t sum_c k_tc k_sc exp(G_tc - G_sc)      s < t
    P[t, s] =        sum_c q_tc k_sc exp(G_tc - G_sc)      s <= t
    X       = (I + M)^-1                                   unit lower
    U       = X (beta v) - X (beta k * exp(G)) S_0         the chunk's
                                                           corrected values
    o       = (q * exp(G)) S_0 + P U
    S_out   = Diag(exp(G_last)) S_0 + (k * exp(G_last - G))^T U

(the WY / UT form of the chunk's products of ``I - beta k k^T``). One
program instance takes a block of ``hb`` heads of one sequence
(``kda_head_block``) and walks their chunks in order (backward: in
reverse) with the states (``dS`` backward) in VMEM; the forward pass
stores the state each chunk entered with, in float32 and nothing else,
and the backward pass recomputes the chunk from it. Every value of a
chunk carries the block's heads as a leading axis and every product is
one ``dot_general`` batched over it: the heads' chains of small products
are independent, so the block interleaves them on the MXU, and the
kernel's code is the same at any ``hb`` (Mosaic unrolls the batch while
it compiles, not while JAX traces and lowers: the persistent compile
cache keeps the one and not the other).

Exponents. A channel's cumulative log decay can pass -88 within a chunk
(``exp(A_log)`` up to 16 times an unbounded softplus), so ``exp(G_t)
exp(-G_s)`` would overflow. The chunk is taken in sub-chunks of ``sub``
positions, and a pair (t, s) of the grams and their cotangents is formed
against a reference point r as ``exp(G_t - r) exp(r - G_s)``, each factor
a normal float32. Where no channel of any head of the block falls by more
than ``LIMIT`` (80) across either half of a sub-chunk, r is the
sub-chunk's middle for every pair its rows or its columns are in: one
product a gram, the diagonal block included. Elsewhere (chosen on the
device, a sub-chunk and head block at a time; ``pair_subchunks`` counts
those visits) the sub-chunk's rows take its first position against
earlier columns and its columns its last against later rows, both
factors ``<= 1``, and its diagonal block is formed pair by pair as
``exp(G_t - G_s)``, ``t >= s``: exact for every head, so a block that
takes it for one head's sake only pays for it. A factor that underflows
to 0 stands for a product that is smaller still.

``X`` is made in two levels: the diagonal sub-chunks' inverses as
``(I - M_d)(I + M_d^2)(I + M_d^4)(I + M_d^8)`` (``M_d^sub = 0``; powers of
16 positions at most), then the sub-chunks below them one row of
sub-chunks at a time, as forward substitution by blocks (never powers of
the whole chunk, whose entries grow as binomials of its length where keys
repeat).

Sizes: chunks of 128 positions and sub-chunks of 16. A chunk's ``(C, C)``
matrices, their transposes and the state's ``(K, C)`` products are then
whole (8, 128) tiles of the chip, and the states kept for the backward
pass are 128 x H x K x V x 4 B a sequence of 16,384 (268 MB at 32 heads
of 128); 16 is the reference-point span of fla-org's chunked kernels,
and the diagonal's pair-by-pair work grows with it. The head block is the
largest divisor of H whose program instance ``_vmem_bytes`` estimates
under ``VMEM_BUDGET``, the limit Mosaic is given.

``beta`` enters the kernels as the products ``beta k`` and ``beta v``,
made in the write that casts them (it multiplies nothing else), so no
column of one value a position is carried in the chip's tiled layout.
``G`` and its cotangent are float32, plain JAX around the kernels; q, k,
beta k, beta v arrive in the caller's dtype (bfloat16 on the chip) and
everything inside the kernels is float32, the products at full precision.
The l2 norms of q and k and the scale of q are the caller's (the model
fuses them into the cast).

Names in a profile: ``pbtpu_kda_fwd``, ``pbtpu_kda_bwd``. Off a TPU the
kernels run in the Pallas interpreter (tests: tiny shapes) — except inside
a ``check_vma`` shard_map, where the interpreter cannot run: a trainer on
a CPU mesh takes ``kda_reference``. On a TPU the chunk and the heads'
widths must be whole 128-lane tiles; where they are not, ``kda`` raises,
as for a length that is no multiple of the chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddlebox_tpu.ops.flash_attention import LANES, out_struct

CHUNK = 128         # positions a chunk
SUB = 16            # positions a sub-chunk (a reference point's span)
_FAR = -1e30        # exp(_FAR) == 0: the masked half of a sub-chunk
LIMIT = 80.0        # the largest exponent a factor may carry: exp(+-80) is
                    # a normal float32, and so is a product of two factors
VMEM_BUDGET = 48 << 20  # what a program instance may take of a core's
                        # 128 MiB of VMEM, and the limit Mosaic is given
_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def kda_reference(q, k, v, log_a, beta):
    """The literal recurrence, one position at a time, in float32
    (elementwise products and sums: no matrix product's precision)."""
    f32 = lambda x: x.astype(_F32)
    B, H, T, K = q.shape

    def step(S, inp):
        qt, kt, vt, gt, bt = inp          # (B,H,K) x2, (B,H,V), (B,H,K), (B,H)
        S = jnp.exp(gt)[..., None] * S
        corr = vt - jnp.sum(kt[..., None] * S, axis=-2)
        S = S + (bt[..., None] * kt)[..., None] * corr[..., None, :]
        return S, jnp.sum(qt[..., None] * S, axis=-2)

    S0 = jnp.zeros((B, H, K, v.shape[-1]), _F32)
    vma = tuple(getattr(jax.typeof(q), "vma", ()))
    if vma:         # inside shard_map the carry varies as the inputs do
        S0 = lax.pcast(S0, vma, to="varying")
    _, o = lax.scan(step, S0, tuple(
        jnp.moveaxis(f32(x), 2, 0) for x in (q, k, v, log_a, beta)))
    return jnp.moveaxis(o, 0, 2).astype(q.dtype)


def kda_geometry(chunk: int, sub: int, K: int, V: int) -> bool:
    """Whether the kernels take the shape: the sub-chunk divides the
    chunk and, on a TPU, the chunk and both head widths are whole 128-lane
    tiles."""
    if chunk % sub or sub % 8:
        return False
    return jax.default_backend() != "tpu" or not (
        chunk % LANES or K % LANES or V % LANES)


def _vmem_bytes(hb: int, C: int, K: int, V: int) -> int:
    """What one program instance of the backward kernel (the larger)
    holds in VMEM at a block of ``hb`` heads, every value counted as
    float32: its blocks double-buffered (q, k, beta k, G and their four
    cotangents; beta v, dO and its cotangent; the state the chunk entered
    with), its scratch (dS, k, P, M, X) and the chunk's live values, 16
    of ``(C, C)`` and 24 of ``(C, max(K, V))`` (grams, inverse, their
    cotangents and transposes; the rows' factors and products)."""
    W = max(K, V)
    blocks = 2 * (8 * C * K + 3 * C * V + K * V)
    scratch = K * V + C * K + 3 * C * C
    live = 16 * C * C + 24 * C * W
    return 4 * hb * (blocks + scratch + live)


def kda_head_block(H: int, C: int, K: int, V: int) -> int:
    """The heads a program instance takes: the largest divisor of ``H``
    whose instance ``_vmem_bytes`` estimates under ``VMEM_BUDGET`` (one
    head where none does)."""
    return max((hb for hb in range(1, H + 1) if H % hb == 0
                and _vmem_bytes(hb, C, K, V) <= VMEM_BUDGET), default=1)


# -- what both kernels compute of a chunk -----------------------------------
# every value of a chunk is (hb, rows, columns): the block's heads lead,
# and every product is batched over them

def _dot(a, b):
    return lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))),
                           precision=_HIGHEST, preferred_element_type=_F32)


def _dot_nt(a, b):
    return lax.dot_general(a, b, (((2,), (2,)), ((0,), (0,))),
                           precision=_HIGHEST, preferred_element_type=_F32)


def _t(x):
    """Each head's matrix transposed."""
    return jnp.swapaxes(x, 1, 2)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _column(row):
    """(hb, 1, n) -> (hb, n, 1), through the diagonal (no transpose of a
    row)."""
    n = row.shape[-1]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=2, keepdims=True)


def _row(col):
    """(hb, n, 1) -> (hb, 1, n)."""
    n = col.shape[1]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, col, 0.0), axis=1, keepdims=True)


class _Chunk:
    """One chunk of a block of heads inside a kernel: ``G`` and ``k`` in
    refs (a position's row is read by its index), the sub-chunks'
    reference points and masks.

    Each sub-chunk is taken one of two ways, chosen on the device by
    ``lax.cond`` for the whole block. Where no channel's ``G``, in any
    head of the block, falls by more than ``LIMIT`` from the sub-chunk's
    first position to its middle or from its middle to its last, one
    reference at the middle serves every pair its rows or columns are in
    (its diagonal block too): each factor is ``exp(min(x, LIMIT))`` with
    ``x`` the exact exponent wherever the pair is causal, so one product
    a gram and a cotangent. Elsewhere the rows take their first position
    as the reference against earlier columns, the columns their last
    against later rows, and the diagonal block is formed pair by pair
    (``pairs``)."""

    def __init__(self, g_ref, k_ref, C, c):
        self.g_ref, self.k_ref, self.C, self.c = g_ref, k_ref, C, c
        self.g, self.k = g_ref[...], k_ref[...]
        self.nb = C // c
        self.local = _iota((c, 1), 0)                   # a sub-chunk's rows
        self.cols = _iota((c, C), 1)
        self.pos = _iota((C, 1), 0)

    def rows(self, i):
        return slice(i * self.c, (i + 1) * self.c)

    def _at(self, t):
        return self.g[:, t:t + 1, :]

    def middle(self, i):
        """Sub-chunk i's one reference r, G at its middle position: (the
        rows' factor exp(min(G_t - r, LIMIT)) (hb, c, K); the columns'
        exp(min(r - G_s, LIMIT)) (hb, C, K), 0 after the sub-chunk; the
        rows' exp(min(G_t - r, LIMIT)) over the chunk (hb, C, K), 0
        before it; the sub-chunk's columns' exp(min(r - G_s, LIMIT)) (hb,
        c, K); whether every factor of every head is exact)."""
        c, rows = self.c, self.rows(i)
        r = self._at(i * c + c // 2)
        fits = jnp.max(jnp.maximum(self._at(i * c) - r,
                                   r - self._at(i * c + c - 1))) <= LIMIT
        up = jnp.exp(jnp.minimum(self.g - r, LIMIT))
        down = jnp.exp(jnp.minimum(r - self.g, LIMIT))
        return (up[:, rows], jnp.where(self.pos < (i + 1) * c, down, 0.0),
                jnp.where(self.pos >= i * c, up, 0.0), down[:, rows], fits)

    def first(self, i):
        """Rows of sub-chunk i against the columns before it: (the rows'
        factor exp(G_t - r) (hb, c, K), k's exp(r - G_s) (hb, C, K)), r =
        G at the sub-chunk's first position; columns from i on are
        clamped (and masked by the caller)."""
        r = self._at(i * self.c)
        return (jnp.exp(self.g[:, self.rows(i)] - r),
                jnp.exp(jnp.minimum(r - self.g, 0.0)))

    def last(self, i):
        """Columns of sub-chunk i against the rows after it: (the
        columns' factor exp(r - G_s) (hb, c, K), the rows' exp(G_t - r)
        (hb, C, K)), r = G at the sub-chunk's last position."""
        r = self._at((i + 1) * self.c - 1)
        return (jnp.exp(r - self.g[:, self.rows(i)]),
                jnp.exp(jnp.minimum(self.g - r, 0.0)))

    def pairs(self, i, body, init):
        """Folds ``body(j, s, e, carry)`` over the positions s of
        sub-chunk i (j its index within), e = exp(G_t - G_s) for the
        sub-chunk's rows t >= s, else 0: (hb, c, K)."""
        g_i = self.g[:, self.rows(i)]

        def one(j, carry):
            s = i * self.c + j
            gs = self.g_ref[:, pl.ds(s, 1), :]
            e = jnp.exp(jnp.where(self.local >= j, g_i - gs, _FAR))
            return body(j, s, e, carry)

        return lax.fori_loop(0, self.c, one, init)

    def grams(self, q, kb, p_ref, m_ref):
        """P (inclusive) and M (strict) of the chunk into their refs."""
        for i in range(self.nb):
            rows = self.rows(i)
            q_i, kb_i = q[:, rows], kb[:, rows]
            lo, kr, _, _, fits = self.middle(i)

            def near():
                return (_dot_nt(q_i * lo, self.k * kr),
                        _dot_nt(kb_i * lo, self.k * kr))

            def apart():
                lo, kr = self.first(i)
                before = self.cols < i * self.c
                p0 = jnp.where(before, _dot_nt(q_i * lo, self.k * kr), 0.0)
                m0 = jnp.where(before, _dot_nt(kb_i * lo, self.k * kr), 0.0)

                def col(j, s, e, carry):
                    p, m = carry
                    ke = self.k_ref[:, pl.ds(s, 1), :] * e
                    at = self.cols == s
                    p = jnp.where(at, jnp.sum(q_i * ke, axis=2,
                                              keepdims=True), p)
                    m = jnp.where(at, jnp.sum(kb_i * ke, axis=2,
                                              keepdims=True), m)
                    return p, m

                return self.pairs(i, col, (p0, m0))

            p_ref[:, rows, :], m_ref[:, rows, :] = lax.cond(fits, near, apart)
        r, s = _iota((self.C, self.C), 0), _iota((self.C, self.C), 1)
        return (jnp.where(s <= r, p_ref[...], 0.0),
                jnp.where(s < r, m_ref[...], 0.0))

    def inverse(self, m, x_ref):
        """(I + M)^-1 for M strictly lower."""
        C, c = self.C, self.c
        r, s = _iota((C, C), 0), _iota((C, C), 1)
        same = r // c == s // c
        md = jnp.where(same, m, 0.0)
        powers = [md]
        while 2 ** len(powers) < c:
            powers.append(_dot(powers[-1], powers[-1]))
        d = jnp.broadcast_to((r == s).astype(_F32), m.shape)
        for pw in reversed(powers[1:]):
            d = d + _dot(pw, d)
        d = d - _dot(md, d)
        e = _dot(d, jnp.where(same, 0.0, m))      # D M_off: below the blocks
        x_ref[...] = d
        for i in range(1, self.nb):
            rows = self.rows(i)
            x_ref[:, rows, :] = d[:, rows] - _dot(e[:, rows], x_ref[...])
        return x_ref[...]

    def gram_grads(self, dp, dm, q, kb):
        """(sum_s dP_ts k_s e_ts, sum_s dM_ts k_s e_ts, sum_t (dP_ts q_t +
        dM_ts kb_t) e_ts) a sub-chunk at a time, e_ts = exp(G_t - G_s):
        the cotangents the grams send to q, kb and k (dP and dM are 0
        where s > t)."""
        dpt, dmt = _t(dp), _t(dm)
        out = []
        for i in range(self.nb):
            rows = self.rows(i)
            dp_i, dm_i = dp[:, rows], dm[:, rows]
            q_i, kb_i = q[:, rows], kb[:, rows]
            lo, kr, er, hi, fits = self.middle(i)

            def near():
                return (lo * _dot(dp_i, self.k * kr),
                        lo * _dot(dm_i, self.k * kr),
                        hi * (_dot(dpt[:, rows], q * er)
                              + _dot(dmt[:, rows], kb * er)))

            def apart():
                lo, kr = self.first(i)
                before = self.cols < i * self.c
                rq = lo * _dot(jnp.where(before, dp_i, 0.0), self.k * kr)
                rkb = lo * _dot(jnp.where(before, dm_i, 0.0), self.k * kr)
                hi, er = self.last(i)
                after = self.cols >= (i + 1) * self.c
                ck = hi * (_dot(jnp.where(after, dpt[:, rows], 0.0), q * er)
                           + _dot(jnp.where(after, dmt[:, rows], 0.0),
                                  kb * er))

                def pair(j, s, e, carry):
                    rq, rkb, ck = carry
                    at = self.cols == s
                    dpc = jnp.sum(jnp.where(at, dp_i, 0.0), axis=2,
                                  keepdims=True)
                    dmc = jnp.sum(jnp.where(at, dm_i, 0.0), axis=2,
                                  keepdims=True)
                    ke = self.k_ref[:, pl.ds(s, 1), :] * e
                    got = jnp.sum((dpc * q_i + dmc * kb_i) * e, axis=1,
                                  keepdims=True)
                    return (rq + dpc * ke, rkb + dmc * ke,
                            jnp.where(self.local == j, ck + got, ck))

                return self.pairs(i, pair, (rq, rkb, ck))

            out.append(lax.cond(fits, near, apart))
        return out


def _load(ref):
    return ref[...].astype(_F32)


# -- forward ---------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, o_ref, st_ref, state,
                kf, p_ref, m_ref, x_ref, *, C, c):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    s0 = state[...]
    st_ref[...] = s0
    q, kb, vb = _load(q_ref), _load(kb_ref), _load(vb_ref)
    kf[...] = _load(k_ref)
    ch = _Chunk(g_ref, kf, C, c)
    p, m = ch.grams(q, kb, p_ref, m_ref)
    x = ch.inverse(m, x_ref)
    g, k = ch.g, ch.k
    eg = jnp.exp(g)
    u = _dot(x, vb) - _dot(_dot(x, kb * eg), s0)
    o_ref[...] = (_dot(q * eg, s0) + _dot(p, u)).astype(o_ref.dtype)
    gl = g[:, C - 1:C, :]
    state[...] = _column(jnp.exp(gl)) * s0 + _dot(_t(k * jnp.exp(gl - g)), u)


def _specs(hb, C, K, V, nc, *, reverse: bool):
    """BlockSpecs for a grid (B, H // hb, chunk): head-major (B, H, T,
    width) arrays and the states (B, H, nc, K, V), ``hb`` heads a
    block."""
    at = (lambda i: nc - 1 - i) if reverse else (lambda i: i)
    tok = lambda width: pl.BlockSpec((None, hb, C, width),
                                     lambda b, h, i: (b, h, at(i), 0))
    st = pl.BlockSpec((None, hb, None, K, V),
                      lambda b, h, i: (b, h, at(i), 0, 0))
    return tok(K), tok(V), st


def _params(interpret: bool):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_BUDGET)}


def _scratch(hb, C, K, V):
    return [pltpu.VMEM((hb, K, V), _F32), pltpu.VMEM((hb, C, K), _F32),
            pltpu.VMEM((hb, C, C), _F32), pltpu.VMEM((hb, C, C), _F32),
            pltpu.VMEM((hb, C, C), _F32)]


def _forward(q, k, kb, vb, g, geom, interpret):
    B, H, T, K = q.shape
    V = vb.shape[-1]
    C, c, hb = geom
    nc = T // C
    k_spec, v_spec, st = _specs(hb, C, K, V, nc, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, C=C, c=c),
        grid=(B, H // hb, nc),
        in_specs=[k_spec, k_spec, k_spec, v_spec, k_spec],
        out_specs=[v_spec, st],
        out_shape=[out_struct((B, H, T, V), q.dtype, q),
                   out_struct((B, H, nc, K, V), _F32, q)],
        scratch_shapes=_scratch(hb, C, K, V),
        name="pbtpu_kda_fwd", **_params(interpret),
    )(q, k, kb, vb, g)


# -- backward --------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, st_ref, do_ref,
                dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref,
                dstate, kf, p_ref, m_ref, x_ref, *, C, c):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate[...] = jnp.zeros_like(dstate)

    s0, ds = st_ref[...], dstate[...]
    q, kb, vb, do = _load(q_ref), _load(kb_ref), _load(vb_ref), _load(do_ref)
    kf[...] = _load(k_ref)
    ch = _Chunk(g_ref, kf, C, c)
    p, m = ch.grams(q, kb, p_ref, m_ref)
    x = ch.inverse(m, x_ref)
    g, k = ch.g, ch.k
    eg = jnp.exp(g)
    kgb, qg = kb * eg, q * eg
    w = _dot(x, kgb)
    u = _dot(x, vb) - _dot(w, s0)
    gl = g[:, C - 1:C, :]
    kd = k * jnp.exp(gl - g)
    r, s = _iota((C, C), 0), _iota((C, C), 1)
    # o = qg S_0 + P U;  S_out = Diag(exp(G_last)) S_0 + kd^T U
    du = _dot(_t(p), do) + _dot(kd, ds)
    dp = jnp.where(s <= r, _dot_nt(do, u), 0.0)
    dqg, dkd = _dot_nt(do, s0), _dot_nt(u, ds)
    # U = X (beta v) - X (beta k exp(G)) S_0, X = (I + M)^-1
    dvb = _dot(_t(x), du)
    dm = jnp.where(s < r, -_dot_nt(dvb, u), 0.0)
    dkgb = -_dot_nt(dvb, s0)
    dstate[...] = (_dot(_t(qg), do) + _column(jnp.exp(gl)) * ds
                   - _dot(_t(w), du))
    held = _row(jnp.sum(ds * s0, axis=2, keepdims=True)) * jnp.exp(gl)
    dgl = jnp.sum(dkd * kd, axis=1, keepdims=True) + held
    at_last = _iota((c, 1), 0) == c - 1
    parts = ch.gram_grads(dp, dm, q, kb)
    for i, (rq, rkb, ck) in enumerate(parts):
        rows = ch.rows(i)
        dq_i = dqg[:, rows] * eg[:, rows] + rq
        dkb_i = dkgb[:, rows] * eg[:, rows] + rkb
        dk_i = dkd[:, rows] * jnp.exp(gl - g[:, rows]) + ck
        dg_i = (dqg[:, rows] * qg[:, rows] + dkgb[:, rows] * kgb[:, rows]
                - dkd[:, rows] * kd[:, rows] + q[:, rows] * rq
                + kb[:, rows] * rkb - k[:, rows] * ck)
        if i == ch.nb - 1:
            dg_i = dg_i + jnp.where(at_last, dgl, 0.0)
        dq_ref[:, rows, :] = dq_i.astype(dq_ref.dtype)
        dk_ref[:, rows, :] = dk_i.astype(dk_ref.dtype)
        dkb_ref[:, rows, :] = dkb_i.astype(dkb_ref.dtype)
        dg_ref[:, rows, :] = dg_i
    dvb_ref[...] = dvb.astype(dvb_ref.dtype)


def _backward(q, k, kb, vb, g, states, do, geom, interpret):
    B, H, T, K = q.shape
    V = vb.shape[-1]
    C, c, hb = geom
    nc = T // C
    k_spec, v_spec, st = _specs(hb, C, K, V, nc, reverse=True)
    like = lambda a: out_struct(a.shape, a.dtype, q)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, C=C, c=c),
        grid=(B, H // hb, nc),
        in_specs=[k_spec, k_spec, k_spec, v_spec, k_spec, st, v_spec],
        out_specs=[k_spec, k_spec, k_spec, v_spec, k_spec],
        out_shape=[like(q), like(k), like(kb), like(vb), like(g)],
        scratch_shapes=_scratch(hb, C, K, V),
        name="pbtpu_kda_bwd", **_params(interpret),
    )(q, k, kb, vb, g, states, do)


# -- the op ----------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, kb, vb, g, geom, interpret):
    return _forward(q, k, kb, vb, g, geom, interpret)[0]


def _kda_fwd(q, k, kb, vb, g, geom, interpret):
    o, states = _forward(q, k, kb, vb, g, geom, interpret)
    return o, (q, k, kb, vb, g, states)


def _kda_bwd(geom, interpret, res, do):
    return _backward(*res, do, geom, interpret)


_kda.defvjp(_kda_fwd, _kda_bwd)


def chunk_cumsum(x, chunk: int):
    """Sums along the positions (axis 2) from each chunk's first."""
    B, H, T, K = x.shape
    return lax.cumsum(x.reshape(B, H, T // chunk, chunk, K),
                      axis=3).reshape(x.shape)


def pair_subchunks(g, hb: int, sub: int):
    """Of the chunk sums ``g (B, H, T, K)``: the (sequence, block of
    ``hb`` heads, sub-chunk of ``sub`` positions) visits of a forward
    call that the kernels take pair by pair — where some channel of some
    head of the block falls by more than ``LIMIT`` from the sub-chunk's
    first position to its middle or from its middle to its last, the
    kernels' own test on the same values. Float32, no gradient."""
    B, H, T, K = g.shape
    x = lax.stop_gradient(g).reshape(B, H // hb, hb, T // sub, sub, K)
    mid = x[..., sub // 2, :]
    fall = jnp.max(jnp.maximum(x[..., 0, :] - mid, mid - x[..., sub - 1, :]),
                   axis=(2, 4))
    return jnp.sum(~(fall <= LIMIT)).astype(_F32)


def kda(q, k, v, log_a, beta, *, chunk: int = CHUNK, sub: int = SUB,
        interpret: bool | None = None):
    """The chunked rule: (the output, the visits ``pair_subchunks``
    counts at the kernels' head block). ``interpret``: None = the Mosaic
    kernels on a TPU, the Pallas interpreter elsewhere. A length that is
    no multiple of the chunk is refused, and on a TPU a geometry that is
    not lane-aligned (``kda_geometry``)."""
    B, H, T, K = q.shape
    V = v.shape[-1]
    if k.shape != q.shape or log_a.shape != q.shape \
            or v.shape[:3] != (B, H, T) or beta.shape != (B, H, T):
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}, log_a "
                         f"{log_a.shape}, beta {beta.shape} do not agree")
    C = min(int(chunk), T)
    c = min(int(sub), C)
    if T % C:
        raise ValueError(f"a sequence of {T} positions does not divide into "
                         f"chunks of {C}")
    if not kda_geometry(C, c, K, V):
        raise ValueError(
            f"the kernels take sub-chunks of whole 8-row tiles that divide "
            f"the chunk and, on the chip, chunks and heads of whole 128-lane "
            f"tiles: chunk {C}, sub-chunk {c}, heads {K} / {V} do not")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    hb = kda_head_block(H, C, K, V)
    g = chunk_cumsum(log_a.astype(_F32), C)
    pairs = pair_subchunks(g, hb, c)
    if interpret and getattr(jax.typeof(q), "vma", frozenset()):
        return kda_reference(q, k, v, log_a, beta), pairs
    b = beta.astype(_F32)[..., None]
    kb = (b * k.astype(_F32)).astype(k.dtype)
    vb = (b * v.astype(_F32)).astype(v.dtype)
    return _kda(q, k, kb, vb, g, (C, c, hb), bool(interpret)), pairs
