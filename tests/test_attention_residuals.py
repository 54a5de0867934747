"""What the attention op keeps for its backward pass, and that a tower's
recomputed layer keeps it too (``models/nn.recomputed``): the forward
kernel runs once a layer and step, the gradients are those of a layer
recomputed whole, and the row statistics are kept one value a row. Tiny
shapes, the Pallas interpreter, outside shard_map."""

import collections
import os
import sys

import numpy as np
import pytest

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddlebox_tpu.ops import flash_attention as fa       # noqa: E402

from token_tower_common import tower                      # noqa: E402

# cell -> attention layers of its rehearsal tower
CELLS = {"smallthinker_21b_ep4.seq8k": 4, "nemotron3_nano_ep16.seq4k": 1,
         "lfm2_24b_a2b_ep8.seq8k": 1, "kanana2_30b_a3b_ep8.seq16k": 5}
KERNELS = ("pbtpu_attention_fwd", "pbtpu_attention_dq",
           "pbtpu_attention_dkv")


def kernel_calls(jaxpr) -> dict:
    """How many calls of each attention kernel a jaxpr holds, at any
    depth."""
    counts = dict.fromkeys(KERNELS, 0)

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                if name in counts:
                    counts[name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return counts


def saved_residuals(capsys, f, *args) -> list:
    """[(shape, where it comes from)] of what ``f``'s backward pass keeps,
    read off ``jax.ad_checkpoint.print_saved_residuals``' lines
    (``f32[2,4,32] named 'x' from file:line``)."""
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(f, *args)
    out = []
    for line in capsys.readouterr().out.splitlines():
        aval, _, why = line.partition(" ")
        dims = aval[aval.index("[") + 1:-1]
        out.append((tuple(int(d) for d in dims.split(",") if d), why))
    return out


def _loss_of(model, ids):
    mask = jnp.ones(ids.shape, bool)
    labels = jnp.zeros((ids.shape[0],))
    return lambda p, x: model.loss(p, x, mask, None, labels, ids)[0]


def _qkv(D=16, B=2, H=4, KV=2, T=32):
    ks = jax.random.split(jax.random.PRNGKey(37), 4)
    return (jax.random.normal(ks[0], (B, H, T, D)),
            jax.random.normal(ks[1], (B, KV, T, D)),
            jax.random.normal(ks[2], (B, KV, T, D)),
            jax.random.normal(ks[3], (B, H, T, D)))


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_recomputed_layer_runs_its_forward_kernel_once(cell, monkeypatch):
    _, _, model, params, pulled, ids = tower(cell)
    # a new function each time: a trace is cached by the function it traced
    grad = lambda: jax.value_and_grad(_loss_of(model, ids), argnums=(0, 1))
    kept = kernel_calls(jax.make_jaxpr(grad())(params, pulled))
    got = jax.jit(grad())(params, pulled)
    # the same tower, each layer under a plain jax.checkpoint: it runs the
    # forward kernel again to rebuild what the first call wrote
    monkeypatch.setattr(
        sys.modules[type(model).__module__], "recomputed",
        lambda fn, static_argnums=(), keep=None: jax.checkpoint(
            fn, static_argnums=static_argnums))
    whole = kernel_calls(jax.make_jaxpr(grad())(params, pulled))
    want = jax.jit(grad())(params, pulled)
    n = CELLS[cell]
    assert kept == dict(zip(KERNELS, (n, n, n)))
    assert whole == dict(zip(KERNELS, (2 * n, n, n)))
    flat = lambda g: jax.tree_util.tree_flatten_with_path(g)[0]
    for (path, x), (_, y) in zip(flat(got), flat(want)):
        np.testing.assert_array_equal(x, y,
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_kept_row_statistics_are_one_value_a_row(cell, capsys):
    """What a layer's recomputation saves of each attention op: q, k, v as
    they enter the kernel (q alone in multi-head latent attention), ``o``
    (as the op's output it is listed by the operation that hands it on,
    not by its name) and ``lse`` as (B, H, T), never the kernel's
    lane-replicated (B, H, T, 128)."""
    cfg, _, model, params, pulled, ids = tower(cell)
    B, T = ids.shape
    H, KV, D, Dv = _heads(cfg["model_args"])
    saved = saved_residuals(capsys, _loss_of(model, ids), params, pulled)
    named = {name: [shape for shape, why in saved if f"'{name}'" in why]
             for name in fa.RESIDUAL_NAMES}
    shapes = [shape for shape, _ in saved]
    n = CELLS[cell]
    # multi-head latent attention keeps q, o and lse and makes its keys and
    # values again from the latent (models/deepseek_v3.py::KEPT)
    kv_kept = "kv_lora_rank" not in cfg["model_args"]
    assert named["pbtpu_attention_lse"] == [(B, H, T)] * n
    assert named["pbtpu_attention_q"] == [(B, H, T, D)] * n
    assert named["pbtpu_attention_k"] == [(B, KV, T, D)] * n * kv_kept
    assert named["pbtpu_attention_v"] == [(B, KV, T, Dv)] * n * kv_kept
    # q and o; and k and v where kept and every query head has its own
    want = collections.Counter([(B, H, T, D), (B, H, T, Dv)] + [
        (B, KV, T, D), (B, KV, T, Dv)] * (KV == H and kv_kept))
    for shape, times in want.items():
        assert shapes.count(shape) == times * n, shape
    assert (B, H, T, fa.LANES) not in shapes


def _heads(a):
    """(query heads, key-value heads, the query and key head size, the
    value head size) of a tower's model_args."""
    if "kv_lora_rank" in a:          # multi-head latent attention
        return (a["num_attention_heads"], a["num_attention_heads"],
                a["qk_nope_head_dim"] + a["qk_rope_head_dim"],
                a["v_head_dim"])
    return (a["num_attention_heads"], a["num_key_value_heads"],
            a["head_dim"], a["head_dim"])


def test_the_op_alone_keeps_five_residuals_and_gives_the_gradients_it_gave(
        capsys):
    """No checkpoint around it: a name is the identity. One call of each
    kernel, the gradients those of the kernels called by hand on the
    forward's own outputs, and the residuals q, k, v, o and a compact lse."""
    q, k, v, do = _qkv()
    B, H, T, D = q.shape
    op = lambda *a: fa.attention(*a, window=12, block=8)
    f = lambda *a: jnp.sum(op(*a) * do)
    grad = jax.grad(f, argnums=(0, 1, 2))
    assert kernel_calls(jax.make_jaxpr(grad)(q, k, v)) == dict(
        zip(KERNELS, (1, 1, 1)))
    args = (12, float(D ** -0.5), (8, 8), True)
    o, lse = fa._forward(q, k, v, *args)
    np.testing.assert_array_equal(lse, jnp.broadcast_to(lse[..., :1],
                                                        lse.shape))
    by_hand = fa._backward(q, k, v, o, lse[..., 0], do, *args)
    np.testing.assert_array_equal(op(q, k, v), o)
    for x, y in zip(grad(q, k, v), by_hand):
        np.testing.assert_array_equal(x, y)
    with jax.default_matmul_precision("highest"):
        whole = jax.grad(lambda *a: jnp.sum(fa.attention_reference(
            *a, window=12) * do), argnums=(0, 1, 2))(q, k, v)
        for x, y in zip(grad(q, k, v), whole):
            np.testing.assert_allclose(x, y, atol=5e-6)
    shapes = [shape for shape, _ in saved_residuals(capsys, f, q, k, v)]
    assert (B, H, T) in shapes and (B, H, T, fa.LANES) not in shapes
