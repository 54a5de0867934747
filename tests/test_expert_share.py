"""The share layer of parallel/expert.py: one chip's held experts of a
layer routed over all experts — against a dense masked computation, at
every imbalance, in chunks, and the shares of all chips adding up to the
uncut reference's layer."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddlebox_tpu.models.smallthinker import SmallThinkerModel
from paddlebox_tpu.parallel.expert import held_expert_ffn, route_top_k

N, D, F, E, K = 48, 16, 8, 8, 3


def _weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (N, D)),
            jax.random.normal(ks[1], (D, E)),
            jax.random.normal(ks[2], (E, D, F)) * D ** -0.5,
            jax.random.normal(ks[3], (E, D, F)) * D ** -0.5,
            jax.random.normal(ks[4], (E, F, D)) * F ** -0.5)


def _dense(x, probs, experts, wg, wu, wd, first, count):
    """Every held expert over every token, masked by the routing."""
    y = jnp.zeros_like(x)
    for e in range(first, first + count):
        weight = jnp.sum(jnp.where(experts == e, probs, 0), axis=-1)
        out = (jnp.maximum(x @ wg[e], 0) * (x @ wu[e])) @ wd[e]
        y = y + weight[:, None] * out
    return y


def _share(x, probs, experts, wg, wu, wd, first, count, **kw):
    sl = slice(first, first + count)
    return held_expert_ffn(x, probs, experts, wg[sl], wu[sl], wd[sl],
                           (first, count), **kw)


@pytest.mark.parametrize("held", [(0, 2), (2, 2), (5, 3), (0, 8)])
def test_share_equals_dense_masked(held):
    x, router, wg, wu, wd = _weights()
    with jax.default_matmul_precision("highest"):
        probs, experts = route_top_k(x @ router, K)
        y, sizes = _share(x, probs, experts, wg, wu, wd, *held)
        want = _dense(x, probs, experts, wg, wu, wd, *held)
    np.testing.assert_allclose(y, want, atol=2e-5)
    counts = np.bincount(np.asarray(experts).ravel(), minlength=E)
    np.testing.assert_array_equal(sizes, counts[held[0]:held[0] + held[1]])
    # p is normalised over all K choices, held here or not
    np.testing.assert_allclose(np.asarray(probs).sum(-1), 1.0, atol=1e-6)


def test_route_is_softmax_over_all_renormalised():
    x, router, *_ = _weights(1)
    logits = x @ router
    probs, experts = route_top_k(logits, K)
    full = jax.nn.softmax(logits, axis=-1)
    picked = jnp.take_along_axis(full, experts, axis=-1)
    np.testing.assert_allclose(
        probs, picked / picked.sum(-1, keepdims=True), atol=1e-6)


@pytest.mark.parametrize("case", ["all_to_one_held", "none_held"])
def test_no_token_dropped_at_any_imbalance(case):
    x, _, wg, wu, wd = _weights(2)
    held = (2, 2)
    logits = np.zeros((N, E), np.float32)
    if case == "all_to_one_held":
        logits[:, [2, 0, 7]] = [9.0, 5.0, 4.0]      # every token: 2, 0, 7
    else:
        logits[:, [0, 1, 7]] = [9.0, 5.0, 4.0]      # nothing held here
    with jax.default_matmul_precision("highest"):
        probs, experts = route_top_k(jnp.asarray(logits), K)
        y, sizes = _share(x, probs, experts, wg, wu, wd, *held)
        want = _dense(x, probs, experts, wg, wu, wd, *held)
    if case == "all_to_one_held":
        np.testing.assert_array_equal(sizes, [N, 0])
        assert float(jnp.abs(want).max()) > 0.1
    else:
        np.testing.assert_array_equal(sizes, [0, 0])
        assert float(jnp.abs(y).max()) == 0.0
    np.testing.assert_allclose(y, want, atol=2e-5)


def test_chunks_and_gradients_match_dense():
    x, router, wg, wu, wd = _weights(3)
    held = (1, 4)

    def through(fn):
        def loss(x, router, wg, wu, wd):
            probs, experts = route_top_k(x @ router, K)
            return jnp.sum(fn(x, probs, experts, wg, wu, wd) ** 2)
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
                x, router, wg, wu, wd)

    want = through(lambda *a: _dense(*a, *held))
    for chunk in (N, 16):
        got = through(lambda *a: _share(*a, *held, chunk_tokens=chunk)[0])
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for g, w in zip(got[1], want[1]):
            np.testing.assert_allclose(g, w, atol=3e-4)


def test_four_shares_add_up_to_the_uncut_reference_layer():
    """One layer cut over 4 chips (2 of 8 experts each): attention counted
    once, the four held parts add up to what the plain reference gives
    for the whole layer with every expert held."""
    ref = importlib.import_module("benchmark.reference.smallthinker")
    args = dict(hidden_size=32, num_attention_heads=4,
                num_key_value_heads=2, head_dim=8, moe_ffn_hidden_size=16,
                router_experts=8, experts_per_token=3, experts_held=8,
                first_expert=0, layer_kinds=[1], sliding_window_size=6,
                rope_theta=1500000, rms_norm_eps=1e-6, vocab_size=64,
                seq_len=16)
    cfg = {"model_args": args}
    layer = ref.init_params(jax.random.PRNGKey(4), cfg)["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32)) * 0.5
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([ref._layer(layer, h[b], 1, args)
                           for b in range(2)])
        parts, after_attention = [], None
        for first in (0, 2, 4, 6):
            model = SmallThinkerModel(**{**args, "experts_held": 2,
                                         "first_expert": first})
            mine = {**layer, **{k: layer[k][first:first + 2]
                                for k in ("w_gate", "w_up", "w_down")}}
            out, _ = model._layer(mine, h, 1)
            none = {**mine, "w_down": jnp.zeros_like(mine["w_down"])}
            attn_only, _ = model._layer(none, h, 1)   # h' alone
            parts.append(out - attn_only)
            after_attention = attn_only
    np.testing.assert_allclose(after_attention + sum(parts), uncut,
                               atol=3e-5)
    assert float(jnp.abs(sum(parts)).max()) > 1e-2
