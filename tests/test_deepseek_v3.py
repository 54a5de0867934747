"""The DeepSeek-V3 tower (multi-head latent attention, a leading dense
layer, held and shared experts) on the normal path, at a small size on the
CPU that keeps a query/key head unlike the value head (24 against 16, a
rotary slice of 8): the program's loss and gradients against the plain
reference (``benchmark/reference/deepseek_v3.py``) on seeded random
weights, outside a trainer so the attention kernels run in the Pallas
interpreter; the interleaved rotation against a literal one, pair by pair;
the attention half's kernel operands, made in bfloat16 and head-major,
against the same half built in float32 and cast at the kernels' door;
an expert layer cut over eight chips adding up to the uncut reference
layer; through ``Trainer.train_pass`` for two passes with the reference
followed step by step; and what the model declares."""

import importlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddlebox_tpu.models import MODEL_REGISTRY, base     # noqa: E402
from paddlebox_tpu.models import deepseek_v3, nn          # noqa: E402
from paddlebox_tpu.models.deepseek_v3 import (KEPT,       # noqa: E402
                                              DeepseekV3Model)
from paddlebox_tpu.models.nn import recomputed, rope      # noqa: E402
from paddlebox_tpu.monitor import names                   # noqa: E402
from paddlebox_tpu.ops import flash_attention             # noqa: E402

from token_tower_common import follow_two_passes, tower   # noqa: E402

CELL = "kanana2_30b_a3b_ep8.seq16k"
# (num_layers, dense_layers, model_args over the cell's rehearsal ones)
CASES = {"dense": (1, 1, {}), "experts": (1, 0, {}),
         "half_split_rope": (1, 0, {"rope_interleave": False}),
         "tower": (5, 1, {})}


def _model_and_reference(num_layers, dense_layers, seed=0, **over):
    return tower(CELL, seed, num_layers=num_layers,
                 dense_layers=dense_layers, **over)


@pytest.mark.parametrize("case", list(CASES))
def test_model_loss_and_gradients_equal_the_reference(case):
    n, dense, over = CASES[case]
    cfg, ref, model, params, pulled, ids = _model_and_reference(n, dense,
                                                                **over)
    a = cfg["model_args"]
    assert a["qk_nope_head_dim"] + a["qk_rope_head_dim"] != a["v_head_dim"]
    mask = jnp.ones(ids.shape, bool)
    labels = jnp.zeros((ids.shape[0],))
    # the program's own initial state has the reference's names and shapes
    mine0 = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda x: x.shape, mine0) \
        == jax.tree.map(lambda x: x.shape, params)

    def mine(p, x):
        return model.loss(p, x, mask, None, labels, ids)[0]

    def theirs(p, x):
        return jnp.mean(ref.example_losses(p, x, mask, None, labels, ids,
                                           cfg))

    with jax.default_matmul_precision("highest"):
        a_, ga = jax.jit(jax.value_and_grad(mine, argnums=(0, 1)))(
            params, pulled)
        b_, gb = jax.jit(jax.value_and_grad(theirs, argnums=(0, 1)))(
            params, pulled)
    # float32 throughout, sums in another order: blocked attention against
    # whole rows, sorted grouped products against a masked scan over
    # experts, the MLP in chunks of tokens against one
    np.testing.assert_allclose(a_, b_, rtol=2e-6)
    flat = lambda g: jax.tree_util.tree_flatten_with_path(g)[0]
    for (path, x), (_, y) in zip(flat(ga), flat(gb)):
        np.testing.assert_allclose(
            x, y, atol=3e-5 * max(float(jnp.abs(y).max()), 1.0),
            err_msg=jax.tree_util.keystr(path))
    # dense: every leaf gets a gradient but the correction bias; rows: w,
    # show and clk are not read, the embedding is
    for path, g in flat(ga[0]):
        name = jax.tree_util.keystr(path)
        assert (float(jnp.abs(g).max()) == 0.0) \
            == ("e_score_correction_bias" in name), name
    assert float(jnp.abs(ga[1][..., :3]).max()) == 0.0
    assert float(jnp.abs(ga[1][..., 3:]).max()) > 0.0


@pytest.mark.parametrize("dim", [8, 64])
@pytest.mark.parametrize("interleave", [True, False])
def test_rope_turns_each_pair_by_its_angle(dim, interleave):
    """Against a literal rotation, pair by pair: with ``interleave`` the
    pair is (x[2i], x[2i + 1]), else (x[i], x[i + dim / 2]); either turns
    by t * theta^(-2i / dim)."""
    B, T, H, theta = 2, 11, 3, 1e6
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (B, T, H, dim)))
    got = np.asarray(rope(jnp.asarray(x), theta, interleave))
    want = np.empty_like(x)
    for t in range(T):
        for i in range(dim // 2):
            a, b = (2 * i, 2 * i + 1) if interleave else (i, i + dim // 2)
            ang = t * theta ** (-2.0 * i / dim)
            c, s = np.cos(ang), np.sin(ang)
            want[:, t, :, a] = x[:, t, :, a] * c - x[:, t, :, b] * s
            want[:, t, :, b] = x[:, t, :, b] * c + x[:, t, :, a] * s
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the two forms are the same rotation of channels in another order
    if interleave:
        order = np.concatenate([np.arange(0, dim, 2), np.arange(1, dim, 2)])
        half = np.asarray(rope(jnp.asarray(x[..., order]), theta))
        np.testing.assert_allclose(half, got[..., order], atol=2e-5)


def _rolled_rope(x, theta, interleave):
    """The interleaved rotation as it was first written: each channel's
    partner by a roll along the channels."""
    if not interleave:
        return rope(x, theta)
    T, dim = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = (jnp.repeat(f(ang), 2, axis=-1)[None, :, None, :]
                for f in (jnp.cos, jnp.sin))
    even = jnp.arange(dim) % 2 == 0
    partner = jnp.where(even, -jnp.roll(x, -1, axis=-1),
                        jnp.roll(x, 1, axis=-1))
    return x * cos + partner * sin


def _float32_operands(model, p, u):
    """The attention half with its operands built in float32, token-major
    — q concatenated at n + r channels, the rotary key broadcast to every
    head — and handed to ``nn.causal_attention`` to transpose and cast."""
    B, T, _ = u.shape
    H, n, r, c = model.heads, model.nope, model.rope_dim, model.latent
    rot = lambda x: _rolled_rope(x, model.theta, model.interleave)
    q = (u @ p["wq"]).reshape(B, T, H, n + r)
    lk = u @ p["wkv_a"]
    kv = (nn.rms_norm(lk[..., :c], p["kv_norm"], model.eps)
          @ p["wkv_b"]).reshape(B, T, H, n + model.v_dim)
    q = jnp.concatenate([q[..., :n], rot(q[..., n:])], axis=-1)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(rot(lk[..., None, c:]),
                                       (B, T, H, r))], axis=-1)
    return nn.causal_attention(q, k, kv[..., n:]) @ p["wo"]


@pytest.mark.parametrize("interleave", [True, False])
def test_the_kernels_operands_are_made_once_in_their_dtype(monkeypatch,
                                                          interleave):
    """The attention half as the chip runs it — the kernels' dtype
    bfloat16 (``jax.default_backend`` says "tpu"), the kernels in the
    Pallas interpreter — at the cell's head (queries and keys 128 + 64,
    values 128) against the same half with float32 operands made
    token-major and cast by ``nn.causal_attention``, forward,
    recomputation and backward, operation by operation (no jit: what is
    compared is the program's arithmetic, not how XLA:CPU fuses it). The
    kernels receive the same bits, so the output and the gradients of
    W_q, W_kv_b and W_o are the same bits. The gradients of the input, of
    W_kv_a and of the latent's norm differ in the last float32 places:
    each of W_q and W_kv_b is now two products, so the input's and the
    latent's cotangents sum their 192 and 256 channels a head in two
    parts (within 2e-6 of each leaf's largest value)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = []

    def recording(q, k, v, **kw):
        seen.append((q, k, v))
        return flash_attention.attention(q, k, v, interpret=True, **kw)

    monkeypatch.setattr(deepseek_v3, "attention", recording)
    monkeypatch.setattr(nn, "attention", recording)
    model = DeepseekV3Model(
        hidden_size=64, num_layers=1, dense_layers=1, num_attention_heads=2,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kv_lora_rank=32, intermediate_size=8, moe_intermediate_size=8,
        n_shared_experts=1, router_experts=4, experts_per_token=2,
        experts_held=4, routed_scaling_factor=1.0, rope_theta=1e4,
        rope_interleave=interleave, rms_norm_eps=1e-6, vocab_size=8,
        seq_len=128)
    p = {k: v for k, v in model.init(jax.random.PRNGKey(1))["layers"][0]
         .items() if k in ("wq", "wkv_a", "wkv_b", "wo", "kv_norm")}
    p["kv_norm"] = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), (32,))
    u, g = (jax.random.normal(jax.random.PRNGKey(s), (1, 128, 64))
            for s in (2, 3))

    def run(half):
        seen.clear()
        half(p, u)
        out, back = jax.vjp(recomputed(half, keep=KEPT), p, u)
        return seen[0], out, back(g)

    with jax.disable_jit():
        ops_a, out_a, grads_a = run(model._attention)
        ops_b, out_b, grads_b = run(
            lambda p, u: _float32_operands(model, p, u))
    for a, b in zip(ops_a, ops_b):
        assert a.dtype == jnp.bfloat16 and a.shape[:3] == (1, 2, 128)
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    np.testing.assert_array_equal(out_a, out_b)
    for name in ("wq", "wkv_b", "wo"):
        np.testing.assert_array_equal(grads_a[0][name], grads_b[0][name])
    for x, y in ((grads_a[1], grads_b[1]),
                 (grads_a[0]["wkv_a"], grads_b[0]["wkv_a"]),
                 (grads_a[0]["kv_norm"], grads_b[0]["kv_norm"])):
        np.testing.assert_allclose(x, y, rtol=0,
                                   atol=2e-6 * float(jnp.abs(y).max()))


def test_eight_shares_and_the_shared_experts_once_add_up_to_the_uncut_layer():
    """One expert layer cut over 8 chips (4 of 32 routed experts each,
    ``first_expert`` 0, 4, ..., 28): SwiGLU bodies, sigmoid scores
    renormalised by their sum + 1e-20 and scaled, a correction bias that
    moves choices — attention and the shared experts, which every chip
    computes alike, counted once, the eight held parts add up to what the
    plain reference gives for the whole layer with every expert held."""
    ref = importlib.import_module("benchmark.reference.deepseek_v3")
    args = dict(hidden_size=32, num_layers=1, dense_layers=0,
                num_attention_heads=4, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24,
                intermediate_size=48, moe_intermediate_size=16,
                n_shared_experts=2, router_experts=32, experts_per_token=6,
                experts_held=32, first_expert=0, routed_scaling_factor=2.448,
                rope_theta=1000000, rope_interleave=True, rms_norm_eps=1e-6,
                vocab_size=64, seq_len=16)
    layer = ref.init_params(jax.random.PRNGKey(12), {"model_args": args}
                            )["layers"][0]
    layer["e_score_correction_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(13), (32,))
    h = jax.random.normal(jax.random.PRNGKey(14), (2, 16, 32)) * 0.5
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([ref._layer(layer, h[b], False, args)
                           for b in range(2)])
        parts, rest_alone, loads = [], None, []
        for first in range(0, 32, 4):
            model = DeepseekV3Model(**{**args, "experts_held": 4,
                                       "first_expert": first})
            mine = {**layer, **{k: layer[k][first:first + 4]
                                for k in ("w_gate", "w_up", "w_down")}}
            out, (load, _) = model._layer(mine, h, False)
            none = {**mine, "w_down": jnp.zeros_like(mine["w_down"])}
            # h + attention + the shared experts, the held experts' part 0
            rest_alone, _ = model._layer(none, h, False)
            parts.append(out - rest_alone)
            loads.append(load)
    np.testing.assert_allclose(rest_alone + sum(parts), uncut, atol=5e-5)
    assert float(jnp.abs(sum(parts)).max()) > 1e-2
    assert float(jnp.abs(rest_alone - h).max()) > 1e-2
    # nothing dropped: every (token, choice) fell on exactly one share
    assert int(sum(jnp.sum(l) for l in loads)) == 2 * 16 * 6


def test_order_matters_and_the_declaration():
    cfg, _, model, params, pulled, ids = _model_and_reference(2, 1, seed=1)
    mask = jnp.ones(ids.shape, bool)
    labels = jnp.zeros((ids.shape[0],))
    loss, preds, stats = model.loss(params, pulled, mask, None, labels, ids)
    perm = np.arange(ids.shape[1])
    perm[[3, 11]] = perm[[11, 3]]
    swapped = model.loss(params, pulled[:, perm], mask, None, labels,
                         ids[:, perm])[0]
    assert abs(float(swapped) - float(loss)) > 1e-4
    assert preds is None and stats.shape == (len(model.stat_names),)
    assert not base.predicts(model)
    assert model.stat_names == names.MODEL_STAT_NAMES[:5]
    got = dict(zip(model.stat_names, np.asarray(stats)))
    a = cfg["model_args"]
    # one expert layer (the second; the first is dense)
    assert got["moe.assignments"] == ids.size * a["experts_per_token"]
    assert 0 < got["moe.held_assignments"] <= got["moe.route_rows"] \
        <= got["moe.assignments"]
    assert got["moe.expert_load_max"] <= got["moe.held_assignments"]
    with pytest.raises(ValueError, match="dense layers"):
        MODEL_REGISTRY["deepseek_v3"](**{**a, "dense_layers": 9})
    with pytest.raises(ValueError, match="past the router"):
        MODEL_REGISTRY["deepseek_v3"](**{**a, "first_expert": 15})


@pytest.fixture(scope="module")
def followed():
    """Two passes (files A, then B) through ``Trainer.train_pass``; the
    first pass's three first steps followed by the reference, as run.py
    follows them (rehearsal sizes: T 32, 512 ids, 16 experts with 4 held,
    4 heads of 24 / 16, the MLPs' tokens in two chunks)."""
    return follow_two_passes(CELL, 41001)


def test_program_follows_the_reference_through_train_pass(followed):
    n = followed["numbers"]
    assert n["ingest_mismatch"] == 0          # order kept, parser to packer
    assert n["counter_mismatch"] == 0         # the rows' show and clk
    # float32 on both sides (the trainer's attention on a CPU mesh is the
    # plain twin): round-off of sums in another order
    assert n["loss_gap_1"] < 1e-5 and n["loss_gap_3"] < 1e-4
    assert n["grad_gap"] < 1e-4               # first gradient, every leaf
    assert n["change_gap"] < 1e-3             # three steps' change
    left_out = followed["notes"]["leaves_left_out_of_change"]
    assert "table.w" in left_out              # w is not read by the tower
    assert sum("e_score_correction_bias" in leaf for leaf in left_out) == 4
    tr = followed["trainer"]
    assert followed["engines"]["pull_engine"] == "gather_seqpool"
    assert tr.schema.has_sequence and not tr._feeds_auc


def test_two_passes_train_and_their_statistics_reach_the_flight_record(
        followed):
    cfg, recs = followed["cfg"], followed["recs"]
    a = cfg["model_args"]
    assert [r["steps"] for r in recs] == [6, 6]
    assert all(np.isfinite(r["losses"]).all() for r in recs)
    steps_run = sum(r["steps"] for r in recs)
    tokens = cfg["trainer"]["global_batch_size"] * a["seq_len"]
    n_e = a["num_layers"] - a["dense_layers"]
    st = followed["stats"]
    assert st["moe.assignments"] == (steps_run * tokens
                                     * a["experts_per_token"] * n_e)
    assert 0 < st["moe.held_assignments"] < st["moe.assignments"]
    assert st["moe.held_assignments"] <= st["moe.route_rows"] \
        <= st["moe.assignments"]
    assert recs[1]["timers"]["extras"] > 0
