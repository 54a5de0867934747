"""Minimal functional NN building blocks.

The reference's dense towers are static-graph ``fluid.layers.fc`` stacks
(python/paddle/fluid/layers); here parameters are plain pytrees (dicts of
arrays) built/applied by pure functions — no module framework needed, and
everything jits/shards transparently. bfloat16 compute is applied at the
matmul boundary (MXU-friendly) while params stay float32.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.monitor import device_scope
from paddlebox_tpu.ops.flash_attention import RESIDUAL_NAMES, attention


def dense_init(key, in_dim: int, out_dim: int, scale: str = "glorot"):
    if scale == "glorot":
        std = (2.0 / (in_dim + out_dim)) ** 0.5
    else:
        std = 0.01
    w = jax.random.normal(key, (in_dim, out_dim), jnp.float32) * std
    return {"w": w, "b": jnp.zeros((out_dim,), jnp.float32)}


def dense_apply(p, x: jnp.ndarray, activation: str | None = None,
                compute_dtype=jnp.float32) -> jnp.ndarray:
    y = jnp.asarray(x, compute_dtype) @ jnp.asarray(p["w"], compute_dtype)
    y = y.astype(jnp.float32) + p["b"]
    if activation == "relu":
        y = jnp.maximum(y, 0.0)
    elif activation == "tanh":
        y = jnp.tanh(y)
    elif activation is not None:
        raise ValueError(activation)
    return y


def mlp_init(key, dims: Sequence[int]):
    keys = jax.random.split(key, len(dims) - 1)
    return [dense_init(k, dims[i], dims[i + 1]) for i, k in enumerate(keys)]


def mlp_apply(layers, x: jnp.ndarray, final_activation: str | None = None,
              compute_dtype=jnp.float32) -> jnp.ndarray:
    for i, p in enumerate(layers):
        last = i == len(layers) - 1
        act = final_activation if last else "relu"
        x = dense_apply(p, x, activation=act, compute_dtype=compute_dtype)
    return x


# -- what the ordered-token towers share ------------------------------------

def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def rope(x, theta: float, interleave: bool = False):
    """Rotary positions on (B, T, heads, head_dim), all dims, half-split:
    the pair (x[i], x[i + head_dim / 2]) turns by t * theta^(-2 i / dim);
    ``interleave``, adjacent pairs: (x[2 i], x[2 i + 1]) by the same
    angle."""
    T, dim = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    if interleave:
        # each angle twice; each channel's partner by a full-precision
        # product with a signed permutation, fused into the turn (a roll is
        # written out): exact but for values near float32's limits
        cos, sin = (jnp.repeat(f(ang), 2, axis=-1)[None, :, None, :]
                    for f in (jnp.cos, jnp.sin))
        swap = np.kron(np.eye(dim // 2), [[0, 1], [-1, 0]]).astype(np.float32)
        partner = jnp.einsum("bthr,rs->bths", x, swap, precision="highest")
        return x * cos + partner * sin
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def causal_attention(q, k, v, window=None):
    """Causal grouped-query attention over token-major heads: q (B, T,
    heads, head_dim), k, v (B, T, kv_heads, head_dim) -> (B, T, heads *
    head_dim) in q's dtype, by ``ops/flash_attention.attention`` (a window
    of ``window`` positions where given). On the chip the kernel's
    products take bfloat16 operands (the device's default precision for a
    float32 product) and float32 sums."""
    B, T = q.shape[:2]
    cd = jnp.bfloat16 if jax.default_backend() == "tpu" else q.dtype
    o = attention(*(jnp.swapaxes(t, 1, 2).astype(cd) for t in (q, k, v)),
                  window=window)
    return jnp.swapaxes(o, 1, 2).reshape(B, T, -1).astype(q.dtype)


def chunked_swiglu(m, w1, w3, w2, chunk_tokens: int):
    """The dense SwiGLU MLP ``(silu(m w1) * (m w3)) w2`` over m (N, d),
    ``chunk_tokens`` at a time (a bound on memory, not mathematics), a
    chunk recomputed in the backward pass."""
    n = m.shape[0]
    chunk = min(chunk_tokens, n)
    if n % chunk:
        raise ValueError(f"{n} tokens do not divide into chunks of "
                         f"{chunk}")
    one = jax.checkpoint(
        lambda mc: (jax.nn.silu(mc @ w1) * (mc @ w3)) @ w2)
    if chunk == n:
        return one(m)
    return jax.lax.map(one, m.reshape(n // chunk, chunk, -1)
                       ).reshape(n, -1)


def recomputed(fn, static_argnums=(), keep=RESIDUAL_NAMES):
    """``fn`` with its intermediates rebuilt in the backward pass
    (``jax.checkpoint``), but for what an attention op inside it names
    (``flash_attention.RESIDUAL_NAMES``): the backward kernels read q, k,
    v, the output and the row statistics the forward pass left, so the
    recomputation runs neither the forward kernel nor the projections,
    norms and rotations ahead of it. A layer with no attention inside
    keeps nothing. ``keep``, a subset of the names, rebuilds the others: a
    tower whose keys and values are cheap to make again and large to keep
    names what it keeps. (PERF.md section 6 has the readings with the
    output and statistics alone and with all five, and the memory of
    multi-head latent attention's three.)"""
    return jax.checkpoint(
        fn, static_argnums=static_argnums,
        policy=jax.checkpoint_policies.save_only_these_names(*keep))


@device_scope("head_loss")
def next_token_loss(params, h, local_ids, mask, eps: float, head_chunk: int):
    """Mean over positions t < T - 1 of the cross entropy of position
    t's logits against the id at t + 1, one value an example; the
    logits — ``RMSNorm(h, params["norm_f"]) @ params["head"]`` over the
    vocabulary slice — of ``head_chunk`` positions at a time, recomputed
    in the backward pass."""
    B, T, d = h.shape
    x = rms_norm(h, params["norm_f"], eps)
    targets = jnp.concatenate(
        [local_ids[:, 1:], jnp.zeros((B, 1), local_ids.dtype)], axis=1)
    counted = jnp.concatenate(
        [mask[:, 1:] & mask[:, :-1], jnp.zeros((B, 1), bool)], axis=1)
    chunk = min(head_chunk, T)
    if T % chunk:
        raise ValueError(f"seq_len {T} does not divide into head chunks "
                         f"of {chunk}")

    @jax.checkpoint
    def nll_of(xc, tc):
        logits = xc @ params["head"]                  # (B, c, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, tc[..., None],
                                         axis=-1)[..., 0]

    parts = lambda a: jnp.moveaxis(
        a.reshape(B, T // chunk, chunk, *a.shape[2:]), 1, 0)
    nll = jax.lax.map(lambda c: nll_of(*c), (parts(x), parts(targets)))
    nll = jnp.moveaxis(nll, 0, 1).reshape(B, T)
    w = counted.astype(nll.dtype)
    return jnp.sum(nll * w, axis=1) / jnp.maximum(jnp.sum(w, axis=1), 1)


def vocabulary_ids(pb, key_index_bits: int):
    """A sequence slot's ids within its vocabulary, the targets of a
    next-token loss: the key's low ``key_index_bits`` bits less one, the
    format of a slot file whose key is ``(slot + 1) << bits | (id + 1)``
    (a model's ``batch_extras`` host stage)."""
    ids = np.asarray(pb.ids, np.int64)
    local = (ids & ((1 << key_index_bits) - 1)) - 1
    return np.where(pb.mask, local, 0).astype(np.int32)
