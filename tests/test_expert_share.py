"""The share layer of parallel/expert.py: one chip's held experts of a
layer routed over all experts — against a dense masked computation, at
every imbalance, in chunks, for the three expert bodies (gated ReGLU and
SwiGLU, non-gated relu squared) and both routing rules (softmax over the
chosen logits; sigmoid scores chosen with a correction bias), and the
shares of all chips adding up to the uncut reference's layer, what every
chip computes alike counted once."""

import functools
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddlebox_tpu.models.lfm2_moe import Lfm2MoeModel
from paddlebox_tpu.models.nemotron_h import NemotronHModel
from paddlebox_tpu.models.smallthinker import SmallThinkerModel
from paddlebox_tpu.parallel import expert
from paddlebox_tpu.parallel.expert import (held_expert_ffn,
                                           route_sigmoid_top_k, route_top_k)

N, D, F, E, K = 48, 16, 8, 8, 3
BODIES = ("reglu", "relu2", "swiglu")
RULES = ("softmax", "sigmoid")
SCALE = 2.5


def _atol(rule, base):
    """The repo's own limit for the softmax rule, whose weights sum to 1.
    The sigmoid rule's weights sum to SCALE, so its outputs, and the
    float32 rounding of their sums, are SCALE times as large."""
    return base * (SCALE if rule == "sigmoid" else 1.0)


def _route(logits, rule, bias=None):
    if rule == "softmax":
        return route_top_k(logits, K)
    bias = jnp.zeros(logits.shape[-1:]) if bias is None else bias
    return route_sigmoid_top_k(logits, bias, K, SCALE, 1e-20)


def _weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (N, D)),
            jax.random.normal(ks[1], (D, E)),
            jax.random.normal(ks[2], (E, D, F)) * D ** -0.5,
            jax.random.normal(ks[3], (E, D, F)) * D ** -0.5,
            jax.random.normal(ks[4], (E, F, D)) * F ** -0.5)


def _dense(x, probs, experts, wg, wu, wd, first, count, body="reglu"):
    """Every held expert over every token, masked by the routing."""
    y = jnp.zeros_like(x)
    for e in range(first, first + count):
        weight = jnp.sum(jnp.where(experts == e, probs, 0), axis=-1)
        if body == "reglu":
            hidden = jnp.maximum(x @ wg[e], 0) * (x @ wu[e])
        elif body == "swiglu":
            gate = x @ wg[e]
            hidden = gate * jax.nn.sigmoid(gate) * (x @ wu[e])
        else:
            hidden = jnp.maximum(x @ wu[e], 0) ** 2
        y = y + weight[:, None] * (hidden @ wd[e])
    return y


def _share(x, probs, experts, wg, wu, wd, first, count, body="reglu", **kw):
    return _share_and_route(x, probs, experts, wg, wu, wd, first, count,
                            body, **kw)[:2]


def _share_and_route(x, probs, experts, wg, wu, wd, first, count,
                     body="reglu", **kw):
    sl = slice(first, first + count)
    return held_expert_ffn(x, probs, experts,
                           None if body == "relu2" else wg[sl], wu[sl],
                           wd[sl], (first, count), wu.shape[0], body=body,
                           **kw)


@pytest.mark.parametrize("rule", RULES)
@pytest.mark.parametrize("body", BODIES)
@pytest.mark.parametrize("held", [(0, 2), (2, 2), (5, 3), (0, 8)])
def test_share_equals_dense_masked(held, body, rule):
    x, router, wg, wu, wd = _weights()
    with jax.default_matmul_precision("highest"):
        probs, experts = _route(x @ router, rule)
        y, sizes = _share(x, probs, experts, wg, wu, wd, *held, body)
        want = _dense(x, probs, experts, wg, wu, wd, *held, body)
    np.testing.assert_allclose(y, want, atol=_atol(rule, 2e-5))
    counts = np.bincount(np.asarray(experts).ravel(), minlength=E)
    np.testing.assert_array_equal(sizes, counts[held[0]:held[0] + held[1]])
    # the weights are normalised over all K choices, held here or not
    np.testing.assert_allclose(np.asarray(probs).sum(-1),
                               1.0 if rule == "softmax" else SCALE,
                               atol=_atol(rule, 1e-6))


def test_sigmoid_route_chooses_by_the_bias_and_weighs_without_it():
    x, router, *_ = _weights(1)
    logits = x @ router
    bias = jnp.zeros((E,)).at[5].set(10.0).at[0].set(-10.0)
    weights, experts = _route(logits, "sigmoid", bias)
    plain_w, plain_e = _route(logits, "sigmoid")
    chosen = np.asarray(experts)
    assert (chosen == 5).any(axis=1).all() and not (chosen == 0).any()
    assert (np.asarray(plain_e) == 0).any()
    # the weights are the scores themselves, renormalised and scaled: the
    # bias is in none of them
    s = jax.nn.sigmoid(logits)
    picked = jnp.take_along_axis(s, experts, axis=-1)
    np.testing.assert_allclose(
        weights, SCALE * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    g = jax.grad(lambda b: jnp.sum(_route(logits, "sigmoid", b)[0] ** 2))(
        bias)
    assert float(jnp.abs(g).max()) == 0.0
    assert plain_w.shape == (N, K)


def test_route_is_softmax_over_all_renormalised():
    x, router, *_ = _weights(1)
    logits = x @ router
    probs, experts = route_top_k(logits, K)
    full = jax.nn.softmax(logits, axis=-1)
    picked = jnp.take_along_axis(full, experts, axis=-1)
    np.testing.assert_allclose(
        probs, picked / picked.sum(-1, keepdims=True), atol=1e-6)


@pytest.mark.parametrize("rule", RULES)
@pytest.mark.parametrize("body", BODIES)
@pytest.mark.parametrize("case", ["all_to_one_held", "none_held"])
def test_no_token_dropped_at_any_imbalance(case, body, rule):
    x, _, wg, wu, wd = _weights(2)
    held = (2, 2)
    logits = np.zeros((N, E), np.float32)
    if case == "all_to_one_held":
        logits[:, [2, 0, 7]] = [9.0, 5.0, 4.0]      # every token: 2, 0, 7
    else:
        logits[:, [0, 1, 7]] = [9.0, 5.0, 4.0]      # nothing held here
    with jax.default_matmul_precision("highest"):
        probs, experts = _route(jnp.asarray(logits), rule)
        y, sizes = _share(x, probs, experts, wg, wu, wd, *held, body)
        want = _dense(x, probs, experts, wg, wu, wd, *held, body)
    if case == "all_to_one_held":
        np.testing.assert_array_equal(sizes, [N, 0])
        assert float(jnp.abs(want).max()) > 0.1
    else:
        np.testing.assert_array_equal(sizes, [0, 0])
        assert float(jnp.abs(y).max()) == 0.0
    np.testing.assert_allclose(y, want, atol=_atol(rule, 2e-5))


@pytest.mark.parametrize("rule", RULES)
@pytest.mark.parametrize("body", BODIES)
def test_chunks_and_gradients_match_dense(body, rule):
    x, router, wg, wu, wd = _weights(3)
    held = (1, 4)

    def through(fn):
        def loss(x, router, wg, wu, wd):
            probs, experts = _route(x @ router, rule)
            return jnp.sum(fn(x, probs, experts, wg, wu, wd) ** 2)
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
                x, router, wg, wu, wd)

    want = through(lambda *a: _dense(*a, *held, body))
    # the repo's own limit for the gated bodies, under either rule. The
    # squared body's gradients are 3 to 6 times as large here (500 to 900
    # against 156), so its limit is 8 float32 roundings of the largest:
    # the sums are taken in another order
    atol = 3e-4 if body != "relu2" else 1e-6 * max(
        float(jnp.abs(g).max()) for g in want[1])
    for chunk in (N, 16):
        got = through(lambda *a: _share(*a, *held, body,
                                        chunk_tokens=chunk)[0])
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for g, w in zip(got[1], want[1]):
            np.testing.assert_allclose(g, w, atol=atol)
    if body == "relu2":
        assert float(jnp.abs(want[1][2]).max()) == 0.0   # no gate: no grad


# the ladder of the sorted copy's row bounds, forced rung by rung: chunks of
# 256 tokens x 3 choices = 768 assignments, 3 of 16 experts held (a fair
# load of 144), so a chunk takes 256, 512 or all 768 sorted rows
LN, LCHUNK, LE, LHELD = 512, 256, 16, (2, 3)
LRUNGS = (256, 512, 768)
HELD_IN_CHUNK_0 = {"none_held": 0, "under_the_first_rung": 100,
                   "at_the_first_rung": 256, "one_over_the_first_rung": 257,
                   "at_the_second_rung": 512, "one_over_the_second_rung": 513,
                   "all_to_one_held": 256, "every_choice_held": 768}


def _constructed_logits(case, seed):
    """(LN, LE) router logits: chunk 0 sends exactly HELD_IN_CHUNK_0[case]
    assignments to the held experts (all of them to the first held expert
    in ``all_to_one_held``), chunk 1 is whatever a random router sends."""
    rng = np.random.default_rng(seed)
    logits = rng.normal(size=(LN, LE)).astype(np.float32)
    held = np.arange(LHELD[0], LHELD[0] + LHELD[1])
    others = np.setdiff1d(np.arange(LE), held)
    per_token = np.clip(HELD_IN_CHUNK_0[case] - K * np.arange(LCHUNK), 0, K)
    if case == "all_to_one_held":
        per_token[:] = 1
    for t, c in enumerate(per_token):
        mine = held[:1] if case == "all_to_one_held" else np.roll(held, t)[:c]
        chosen = np.concatenate([mine, rng.permutation(others)[:K - c]])
        logits[t] = -9.0
        logits[t, chosen] = 4.0 + rng.uniform(0, 2, K)
    return jnp.asarray(logits)


def test_rungs_are_whole_tiles_at_most_twice_apart_and_end_at_the_chunk():
    assert expert.route_rungs(LCHUNK * K, LHELD[1], LE) == LRUNGS
    # both towers' chunks of 4,096 tokens x 6 choices: from the fair load
    # (a quarter) to the whole chunk; a sixteenth is under an eighth, the
    # least from which four rungs reach the chunk in steps of at most 2x
    small = expert.route_rungs(24576, 16, 64)
    hybrid = expert.route_rungs(24576, 8, 128)
    assert small == (6144, 9856, 15488, 24576)
    assert hybrid == (3072, 6144, 12288, 24576)
    for rows, rungs in ((24576, small), (24576, hybrid), (768, LRUNGS),
                        (144, expert.route_rungs(144, 2, 8)),
                        (24576, expert.route_rungs(24576, 1, 256))):
        assert rungs[-1] == rows and len(rungs) <= 4
        assert all(b <= 2 * a and a % 128 == 0
                   for a, b in zip(rungs, rungs[1:]))
    # every expert held: the whole chunk is the fair load
    assert expert.route_rungs(144, 8, 8) == (144,)


@pytest.mark.parametrize("rule", RULES)
@pytest.mark.parametrize("body", BODIES)
@pytest.mark.parametrize("case", list(HELD_IN_CHUNK_0))
def test_every_rung_gives_the_whole_chunk_path(case, body, rule, monkeypatch):
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    x = jax.random.normal(ks[0], (LN, D))
    wg, wu, wd = (jax.random.normal(ks[1], (LE, D, F)) * D ** -0.5,
                  jax.random.normal(ks[2], (LE, D, F)) * D ** -0.5,
                  jax.random.normal(ks[3], (LE, F, D)) * F ** -0.5)
    probs, experts = _route(_constructed_logits(case, 12), rule)

    def through(fn):
        def loss(x, probs, wg, wu, wd):
            out, *rest = fn(x, probs, experts, wg, wu, wd, *LHELD, body)
            return jnp.sum(out ** 2), rest
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                      has_aux=True)(x, probs, wg, wu, wd)

    laddered = functools.partial(_share_and_route, chunk_tokens=LCHUNK)
    (got, (sizes, took)), got_grads = through(laddered)
    # what the constructed routing implies
    held = (np.asarray(experts) >= LHELD[0]) & (
        np.asarray(experts) < LHELD[0] + LHELD[1])
    totals = held.reshape(LN // LCHUNK, -1).sum(axis=1)
    assert totals[0] == HELD_IN_CHUNK_0[case]
    rungs = [min(r for r in LRUNGS if r >= t) for t in totals]
    np.testing.assert_array_equal(
        took, [sum(rungs), sum(r == LRUNGS[-1] for r in rungs)])
    assert int(jnp.sum(sizes)) == totals.sum()
    if case == "all_to_one_held":
        assert int(sizes[0]) >= LCHUNK
    # the same chunks through the whole-chunk path alone, and the dense
    # masked computation
    monkeypatch.setattr(expert, "route_rungs", lambda rows, *_: (rows,))
    (whole, (_, whole_took)), whole_grads = through(laddered)
    np.testing.assert_array_equal(whole_took, [LN * K, LN // LCHUNK])
    (dense, _), dense_grads = through(
        lambda *a: (_dense(*a),))
    scale = max(float(jnp.abs(g).max()) for g in dense_grads)
    atol = max(3e-4 if body != "relu2" else 0.0, 1e-6 * scale)
    for want, want_grads in ((whole, whole_grads), (dense, dense_grads)):
        np.testing.assert_allclose(got, want, rtol=1e-5)
        for g, w in zip(got_grads, want_grads):
            np.testing.assert_allclose(g, w, atol=atol)


def test_wrong_stack_names_the_expert_body():
    x, router, wg, wu, wd = _weights()
    probs, experts = route_top_k(x @ router, K)
    with pytest.raises(ValueError, match="ReGLU experts: 3 expert weights"):
        held_expert_ffn(x, probs, experts, wg[:3], wu[:2], wd[:2], (0, 2), E,
                        body="reglu")
    with pytest.raises(ValueError, match="relu squared experts: 3 expert"):
        held_expert_ffn(x, probs, experts, None, wu[:2], wd[:3], (0, 2), E,
                        body="relu2")
    with pytest.raises(ValueError, match="SwiGLU experts: 3 expert"):
        held_expert_ffn(x, probs, experts, wg[:2], wu[:3], wd[:2], (0, 2), E,
                        body="swiglu")
    # the body is the call's to name: a gate says nothing by being there
    with pytest.raises(ValueError, match="SwiGLU experts take a w_gate"):
        held_expert_ffn(x, probs, experts, None, wu[:2], wd[:2], (0, 2), E,
                        body="swiglu")
    with pytest.raises(ValueError, match="relu squared experts take no"):
        held_expert_ffn(x, probs, experts, wg[:2], wu[:2], wd[:2], (0, 2), E,
                        body="relu2")
    with pytest.raises(ValueError, match="expert body 'gelu'"):
        held_expert_ffn(x, probs, experts, wg[:2], wu[:2], wd[:2], (0, 2), E,
                        body="gelu")
    with pytest.raises(TypeError, match="body"):
        held_expert_ffn(x, probs, experts, wg[:2], wu[:2], wd[:2], (0, 2), E)


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


def test_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """One 'E' block cut over 16 chips (2 of 32 routed experts each): the
    sixteen held parts, plus the shared expert every chip computes alike
    counted once, add up to what the plain reference gives for the whole
    block with every expert held."""
    ref = importlib.import_module("benchmark.reference.nemotron_h")
    args = dict(hidden_size=32, block_pattern="E", mamba_num_heads=2,
                mamba_head_dim=8, n_groups=1, ssm_state_size=8,
                conv_kernel=4, chunk_size=8, num_attention_heads=4,
                num_key_value_heads=2, head_dim=8, moe_intermediate_size=16,
                moe_shared_expert_intermediate_size=24, router_experts=32,
                experts_per_token=6, experts_held=32, first_expert=0,
                routed_scaling_factor=2.5, layer_norm_epsilon=1e-5,
                vocab_size=64, seq_len=16)
    block = ref.init_params(jax.random.PRNGKey(6), {"model_args": args}
                            )["blocks"][0]
    # a correction bias that moves choices, as a trained one would
    block["b_corr"] = 0.2 * jax.random.normal(jax.random.PRNGKey(7), (32,))
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 16, 32)) * 0.5
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([ref._block(block, h[b], "E", args)
                           for b in range(2)])
        parts, shared_alone, loads = [], None, []
        for first in range(0, 32, 2):
            model = NemotronHModel(**{**args, "experts_held": 2,
                                      "first_expert": first})
            mine = {**block, **{k: block[k][first:first + 2]
                                for k in ("w_up", "w_down")}}
            out, (load, _) = model._block(mine, h, "E")
            none = {**mine, "w_down": jnp.zeros_like(mine["w_down"])}
            shared_alone, _ = model._block(none, h, "E")  # h + shared(u)
            parts.append(out - shared_alone)
            loads.append(load)
    np.testing.assert_allclose(shared_alone + sum(parts), uncut, atol=5e-5)
    assert float(jnp.abs(sum(parts)).max()) > 1e-2
    assert float(jnp.abs(shared_alone - h).max()) > 1e-2
    # nothing dropped: every (token, choice) fell on exactly one share
    assert int(sum(jnp.sum(l) for l in loads)) == 2 * 16 * 6


def test_eight_shares_add_up_to_the_uncut_lfm2_layer():
    """One expert layer cut over 8 chips (8 of 64 routed experts each,
    ``first_expert`` 0, 8, ..., 56): SwiGLU bodies, sigmoid scores
    renormalised by their sum + 1e-6, no shared expert — the mixer counted
    once, the eight held parts add up to what the plain reference gives
    for the whole layer with every expert held."""
    ref = importlib.import_module("benchmark.reference.lfm2_moe")
    args = dict(hidden_size=32, layer_types=["conv"], dense_layers=0,
                conv_L_cache=3, num_attention_heads=4, num_key_value_heads=2,
                head_dim=8, intermediate_size=48, moe_intermediate_size=16,
                router_experts=64, experts_per_token=4, experts_held=64,
                first_expert=0, routed_scaling_factor=1, rope_theta=1000000,
                norm_eps=1e-5, vocab_size=64, seq_len=16)
    layer = ref.init_params(jax.random.PRNGKey(9), {"model_args": args}
                            )["layers"][0]
    # an expert bias that moves choices, as a balanced one would
    layer["expert_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(10),
                                                   (64,))
    h = jax.random.normal(jax.random.PRNGKey(11), (2, 16, 32)) * 0.5
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([ref._layer(layer, h[b], "conv", False, args)
                           for b in range(2)])
        parts, mixer_alone, loads = [], None, []
        for first in range(0, 64, 8):
            model = Lfm2MoeModel(**{**args, "experts_held": 8,
                                    "first_expert": first})
            mine = {**layer, **{k: layer[k][first:first + 8]
                                for k in ("w_gate", "w_up", "w_down")}}
            out, (load, _) = model._layer(mine, h, "conv", False)
            none = {**mine, "w_down": jnp.zeros_like(mine["w_down"])}
            mixer_alone, _ = model._layer(none, h, "conv", False)
            parts.append(out - mixer_alone)
            loads.append(load)
    np.testing.assert_allclose(mixer_alone + sum(parts), uncut, atol=3e-5)
    assert float(jnp.abs(sum(parts)).max()) > 1e-2
    assert float(jnp.abs(mixer_alone - h).max()) > 1e-2
    # nothing dropped: every (token, choice) fell on exactly one share
    assert int(sum(jnp.sum(l) for l in loads)) == 2 * 16 * 4
