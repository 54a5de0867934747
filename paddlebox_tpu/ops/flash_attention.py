"""Blocked causal attention for ordered-token towers: one kernel family for
full and sliding-window layers, grouped-query heads, forward and backward.

    o[t] = sum_s softmax_s(q[t] . k[s] * scale) v[s]
    over  s <= t                      (causal), and with ``window`` also
          s >  t - window             (a token sees itself and the
                                       window - 1 tokens before it)

``q, k (B, H | KV, T, D)``, ``v (B, KV, T, Dv)`` with ``H = G * KV``: query
head ``h`` reads key-value head ``h // G``; the output is ``(B, H, T, Dv)``
(``Dv = D`` but in multi-head latent attention, whose queries and keys
carry a rotary part the values lack: 192 and 128). The ``T x T`` scores
never exist: each program instance holds one ``(block_q, block_k)`` tile,
keeps the running softmax (max, sum, accumulator) in VMEM, and writes
``o`` and the row log-sum-exp. Blocks that the mask empties — above the
diagonal, and with a window those more than ``window`` behind — are
neither computed nor fetched (the index maps clamp to the nearest needed
block, so the pipeline sees no new block to copy). The backward pass is
two kernels of the same shape (``dq``; ``dk, dv`` per query head, summed
over each group outside), recomputing the tile's probabilities from the
saved log-sum-exp.

Names in a profile: ``pbtpu_attention_fwd``, ``pbtpu_attention_dq``,
``pbtpu_attention_dkv``. Off a TPU the same kernels run in the Pallas
interpreter (tests: tiny shapes only) — except inside a ``check_vma``
shard_map, where the interpreter cannot run (``pallas_kernels.
merge_update`` has the reason): a trainer on a CPU mesh takes the plain
``attention_reference``. On a TPU the geometry must be lane-aligned (``T``
a multiple of the block, blocks multiples of 128, each head size a
multiple of 128, or 64 or 192); where it is not, ``attention`` is the
reference too — never a kernel under another name. A head of 64 or 192
channels is not whole lane tiles: its blocks take the whole head size as
their last dimension (``(block, 64)`` of an array whose last dimension is
64), so the same three kernels run it, a head of 64 with the products'
contraction (scores) or output (values, ``dq``, ``dk``, ``dv``) half as
wide as the MXU, one of 192 with the last of its two lane tiles half
full.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# head sizes that are not whole lane tiles the chip takes (a block's last
# dimension the whole head)
_PART_TILE_HEADS = (64, 192)
_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)   # exp(_MASK - m) == 0
NT = (((1,), (1,)), ((), ()))                      # a @ b.T
# what the op's VJP keeps, by the name a ``jax.checkpoint`` policy can save
# it under (``models/nn.recomputed``): the operands as they enter the
# kernel, the output, and the row log-sum-exp as one value a row
RESIDUAL_NAMES = ("pbtpu_attention_q", "pbtpu_attention_k",
                  "pbtpu_attention_v", "pbtpu_attention_o",
                  "pbtpu_attention_lse")


def attention_reference(q, k, v, *, window: int | None = None,
                        scale: float | None = None):
    """The unblocked form: the whole (T, T) score matrix, masked; ``v``
    may have a head size of its own."""
    B, H, T, D = q.shape
    G = H // k.shape[1]
    scale = D ** -0.5 if scale is None else scale
    kk, vv = (jnp.repeat(a, G, axis=1) for a in (k, v))
    s = jnp.einsum("bhtd,bhsd->bhts", q, kk,
                   preferred_element_type=jnp.float32) * scale
    t, c = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = c <= t
    if window:
        mask &= c > t - window
    p = jax.nn.softmax(jnp.where(mask, s, _MASK), axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p.astype(v.dtype), vv,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def block_geometry(T: int, D: int, block: int = 512):
    """(block_q, block_k) for the kernels, or None where the chip's tile
    layout refuses the shape (the caller then takes the reference)."""
    b = min(block, T)
    if T % b:
        return None
    if jax.default_backend() == "tpu" and (
            b % LANES or (D % LANES and D not in _PART_TILE_HEADS)):
        return None
    return b, b


# -- which blocks a block meets (traced ints or Python ints alike) ----------

def _kv_range(i, bq: int, bk: int, window):
    """First and last kv block that query block `i` reads."""
    hi = ((i + 1) * bq - 1) // bk
    lo = 0 if not window else jnp.maximum(i * bq - window + 1, 0) // bk
    return lo, hi


def _q_range(j, bq: int, bk: int, nq: int, window):
    """First and last query block that reads kv block `j`."""
    lo = (j * bk) // bq
    hi = nq - 1 if not window else jnp.minimum(
        ((j + 1) * bk + window - 2) // bq, nq - 1)
    return lo, hi


def _tile_mask(i, j, bq: int, bk: int, window):
    rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = cols <= rows
    if window:
        mask &= cols > rows - window
    return mask


def _lanes(x, n: int):
    """(rows, 128) lane-replicated statistics broadcast to n columns."""
    return x[:, :1] if n % LANES else jnp.tile(x, (1, n // LANES))


# -- forward ---------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                bq, bk, nk, window, scale):
    i, j = pl.program_id(2), pl.program_id(3)
    lo, hi = _kv_range(i, bq, bk, window)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _MASK)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when((j >= lo) & (j <= hi))
    def _tile():
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s = lax.dot_general(q, k, NT,
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(_tile_mask(i, j, bq, bk, window), s, _MASK)
        m_prev, l_prev = m_s[...], l_s[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _lanes(m_next, bk))
        l_s[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_s[...] = m_next
        acc_s[...] = acc_s[...] * _lanes(alpha, acc_s.shape[1]) + lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _store():
        l = l_s[...]
        o_ref[...] = (acc_s[...] / _lanes(l, acc_s.shape[1])
                      ).astype(o_ref.dtype)
        lse_ref[...] = m_s[...] + jnp.log(l)


def _specs(bq, bk, G, window, *, q_major: bool, nq: int):
    """BlockSpecs for a grid (B, H, major, minor): of a q-like and of a
    kv-like array whose last dimension is ``D`` (two functions of ``D``),
    and of the row statistics. q blocks follow the query axis, kv blocks
    the key axis, each clamped to the blocks the other axis' block
    needs."""
    def q_index(b, h, x, y):
        if q_major:
            return (b, h, x, 0)
        lo, hi = _q_range(x, bq, bk, nq, window)
        return (b, h, jnp.clip(y, lo, hi), 0)

    def kv_index(b, h, x, y):
        if not q_major:
            return (b, h // G, x, 0)
        lo, hi = _kv_range(x, bq, bk, window)
        return (b, h // G, jnp.clip(y, lo, hi), 0)

    return (lambda D: pl.BlockSpec((None, None, bq, D), q_index),
            lambda D: pl.BlockSpec((None, None, bk, D), kv_index),
            pl.BlockSpec((None, None, bq, LANES), q_index))


def _params(interpret: bool):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))}


def out_struct(shape, dtype, like):
    """An output's type: inside shard_map it varies over the mesh axes
    its operands do."""
    return jax.ShapeDtypeStruct(
        shape, dtype, vma=getattr(jax.typeof(like), "vma", frozenset()))


def _forward(q, k, v, window, scale, blocks, interpret):
    B, H, T, D = q.shape
    G, Dv = H // k.shape[1], v.shape[-1]
    bq, bk = blocks
    nq, nk = T // bq, T // bk
    q_spec, kv_spec, row_spec = _specs(bq, bk, G, window, q_major=True,
                                       nq=nq)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, nk=nk, window=window,
                          scale=scale),
        grid=(B, H, nq, nk),
        in_specs=[q_spec(D), kv_spec(D), kv_spec(Dv)],
        out_specs=[q_spec(Dv), row_spec],
        out_shape=[out_struct((B, H, T, Dv), q.dtype, q),
                   out_struct((B, H, T, LANES), jnp.float32, q)],
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, Dv), jnp.float32)],
        name="pbtpu_attention_fwd", **_params(interpret),
    )(q, k, v)


# -- backward --------------------------------------------------------------

def _probs(q, k, lse, i, j, bq, bk, window, scale):
    s = lax.dot_general(q, k, NT, preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - _lanes(lse, bk))
    return jnp.where(_tile_mask(i, j, bq, bk, window), p, 0.0)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, acc_s,
               *, bq, bk, nk, window, scale):
    i, j = pl.program_id(2), pl.program_id(3)
    lo, hi = _kv_range(i, bq, bk, window)

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when((j >= lo) & (j <= hi))
    def _tile():
        k, v, do = k_ref[...], v_ref[...], do_ref[...]
        p = _probs(q_ref[...], k, lse_ref[...], i, j, bq, bk, window, scale)
        dp = lax.dot_general(do, v, NT, preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(dl_ref[...], bk)) * scale
        acc_s[...] += lax.dot(ds.astype(k.dtype), k,
                              preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _store():
        dq_ref[...] = acc_s[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
                dk_s, dv_s, *, bq, bk, nq, window, scale):
    j, i = pl.program_id(2), pl.program_id(3)
    lo, hi = _q_range(j, bq, bk, nq, window)

    @pl.when(i == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    @pl.when((i >= lo) & (i <= hi))
    def _tile():
        q, v, do = q_ref[...], v_ref[...], do_ref[...]
        p = _probs(q, k_ref[...], lse_ref[...], i, j, bq, bk, window, scale)
        dv_s[...] += lax.dot(p.T.astype(do.dtype), do,
                             preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, NT, preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(dl_ref[...], bk)) * scale
        dk_s[...] += lax.dot(ds.T.astype(q.dtype), q,
                             preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _store():
        dk_ref[...] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_s[...].astype(dv_ref.dtype)


def _backward(q, k, v, o, lse, do, window, scale, blocks, interpret):
    """``lse`` (B, H, T): one value a row, replicated over the lanes here
    as ``delta`` is."""
    B, H, T, D = q.shape
    KV, Dv = k.shape[1], v.shape[-1]
    G = H // KV
    bq, bk = blocks
    nq, nk = T // bq, T // bk
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1,
                    keepdims=True)
    delta = jnp.broadcast_to(delta, (B, H, T, LANES))
    lse = jnp.broadcast_to(lse[..., None], (B, H, T, LANES))
    q_spec, kv_spec, row_spec = _specs(bq, bk, G, window, q_major=True,
                                       nq=nq)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, nk=nk, window=window,
                          scale=scale),
        grid=(B, H, nq, nk),
        in_specs=[q_spec(D), kv_spec(D), kv_spec(Dv), q_spec(Dv), row_spec,
                  row_spec],
        out_specs=q_spec(D),
        out_shape=out_struct(q.shape, q.dtype, q),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        name="pbtpu_attention_dq", **_params(interpret),
    )(q, k, v, do, lse, delta)
    q_spec, kv_spec, row_spec = _specs(bq, bk, G, window, q_major=False,
                                       nq=nq)
    # one dk, dv per QUERY head (its own output block, so heads of a group
    # never write the same block); the group's sum is taken outside
    out_spec = lambda D: pl.BlockSpec((None, None, bk, D),
                                      lambda b, h, x, y: (b, h, x, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, nq=nq, window=window,
                          scale=scale),
        grid=(B, H, nk, nq),
        in_specs=[q_spec(D), kv_spec(D), kv_spec(Dv), q_spec(Dv), row_spec,
                  row_spec],
        out_specs=[out_spec(D), out_spec(Dv)],
        out_shape=[out_struct((B, H, T, D), jnp.float32, q),
                   out_struct((B, H, T, Dv), jnp.float32, q)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, Dv), jnp.float32)],
        name="pbtpu_attention_dkv", **_params(interpret),
    )(q, k, v, do, lse, delta)
    fold = lambda g: g.reshape(B, KV, G, T, g.shape[-1]).sum(axis=2).astype(
        k.dtype)
    return dq, fold(dk), fold(dv)


# -- the op ----------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention(q, k, v, window, scale, blocks, interpret):
    return _forward(q, k, v, window, scale, blocks, interpret)[0]


def _attention_fwd(q, k, v, window, scale, blocks, interpret):
    """The residuals carry ``RESIDUAL_NAMES``: a layer recomputed under a
    policy that saves them runs no second forward kernel (``o``, ``lse``)
    and nothing that only led up to it (``q``, ``k``, ``v``). The kernel
    writes ``lse`` replicated over 128 lanes; its first lane is what is
    kept."""
    o, lse = _forward(q, k, v, window, scale, blocks, interpret)
    q, k, v, o, lse = (checkpoint_name(x, name) for x, name in zip(
        (q, k, v, o, lse[..., 0]), RESIDUAL_NAMES))
    return o, (q, k, v, o, lse)


def _attention_bwd(window, scale, blocks, interpret, res, do):
    return _backward(*res, do, window, scale, blocks, interpret)


_attention.defvjp(_attention_fwd, _attention_bwd)


def attention(q, k, v, *, window: int | None = None,
              scale: float | None = None, block: int = 512,
              interpret: bool | None = None):
    """Causal (optionally sliding-window) grouped-query attention, blocked;
    the scale defaults to the query head's ``D ** -0.5``.
    ``interpret``: None = the Mosaic kernels on a TPU, the Pallas
    interpreter elsewhere."""
    B, H, T, D = q.shape
    if H % k.shape[1] or k.shape[-1] != D or k.shape[:3] != v.shape[:3]:
        raise ValueError(f"q {q.shape} does not group over k {k.shape} "
                         f"and v {v.shape}")
    scale = float(D ** -0.5 if scale is None else scale)
    window = int(window) if window and window < T else None
    blocks = block_geometry(T, D, block)
    if blocks is None or block_geometry(T, v.shape[-1], block) is None:
        return attention_reference(q, k, v, window=window, scale=scale)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if interpret and getattr(jax.typeof(q), "vma", frozenset()):
        return attention_reference(q, k, v, window=window, scale=scale)
    return _attention(q, k, v, window, scale, blocks, bool(interpret))
