"""A test's fixture: a tower that reads its tokens in order and brings its
own loss. One slot of T ordered ids over one vocabulary; a token's input
is its row's embedding plus a learned position vector plus its row's
``w`` on every column; one causal block (single-head attention, then a
two-layer MLP, both residual, RMS-normed inputs); a head over the
vocabulary; the example's loss is the mean over positions t < T - 1 of
the cross entropy of position t's logits against the id at t + 1.

The reference half only: the program has no tower that reads ordered
tokens yet (``benchmark/README.md``, what a configuration waits for).
"""

import jax
import jax.numpy as jnp


def _sizes(cfg):
    a = cfg["model_args"]
    return a["emb_dim"], a["mlp_hidden"], a["vocab"], a["seq_len"]


def init_params(key, cfg):
    d, h, vocab, seq = _sizes(cfg)
    shapes = {"pos": (seq, d), "q": (d, d), "k": (d, d), "v": (d, d),
              "o": (d, d), "up": (d, h), "down": (h, d), "head": (d, vocab)}
    keys = jax.random.split(key, len(shapes))
    return {name: jax.random.normal(k, shape, jnp.float32) * shape[0] ** -0.5
            for k, (name, shape) in zip(keys, sorted(shapes.items()))}


def _norm(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)


def example_losses(params, pulled, mask, dense, labels, local_ids, cfg):
    d = _sizes(cfg)[0]
    x = pulled[..., 3:] + pulled[..., 2:3] + params["pos"]
    h = _norm(x)
    scores = jnp.einsum("btd,bsd->bts", h @ params["q"], h @ params["k"])
    seq = x.shape[1]
    causal = jnp.tril(jnp.ones((seq, seq), bool)) & mask[:, None, :]
    att = jax.nn.softmax(jnp.where(causal, scores * d ** -0.5, -1e30),
                         axis=-1)
    x = x + jnp.einsum("bts,bsd->btd", att, h @ params["v"]) @ params["o"]
    x = x + jax.nn.gelu(_norm(x) @ params["up"]) @ params["down"]
    logp = jax.nn.log_softmax((_norm(x) @ params["head"])[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, local_ids[:, 1:, None], axis=-1)[..., 0]
    counted = (mask[:, 1:] & mask[:, :-1]).astype(nll.dtype)
    return jnp.sum(nll * counted, axis=1) / jnp.maximum(
        jnp.sum(counted, axis=1), 1)


def macs_per_example(cfg):
    d, h, vocab, seq = _sizes(cfg)
    return seq * (4 * d * d + 2 * seq * d + 2 * d * h + d * vocab)


def tower_sizes(cfg):
    """(dense parameters, activation floats per example)."""
    d, h, vocab, seq = _sizes(cfg)
    return (seq * d + 4 * d * d + 2 * d * h + d * vocab,
            seq * (6 * d + 2 * seq + h + vocab))
