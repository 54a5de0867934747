"""LFM2-MoE — an ordered-token tower over a vocabulary table whose layers
differ in both halves: the mixer is a gated short convolution (``conv``)
or causal attention with per-head q/k norms and RoPE (``full_attention``),
by a list of kinds; the feed-forward is a dense SwiGLU MLP in the leading
``dense_layers`` layers and this chip's share of sigmoid-routed SwiGLU
experts after (LiquidAI/LFM2-24B-A2B's ``lfm2_moe`` layer).

The vocabulary is the sparse table: one sequence slot (``Slot.sequence``)
of ``seq_len`` ordered ids, whose pulled rows ``[show, clk, w, embedx]``
reach ``loss`` unpooled and in file order; ``h_0[t]`` is the row's embedx.
For each layer ``i``

    h <- h + op_i(RMSNorm(h; operator_norm_i))                 eps norm_eps
    h <- h + ffn_i(RMSNorm(h; ffn_norm_i))

then ``RMSNorm_f``, an untied head over the vocabulary slice and the
next-token cross entropy in chunks of positions (``models/nn.py``). With
``u`` (``m``) the half's normed input, ``d`` the hidden size:

``conv``, the gated short convolution: ``[B | C | x] = u W_in`` (three
widths of ``d``, no bias); ``y[t] = C[t] * sum_{j<K} w[j] (B x)[t - (K - 1)
+ j]``, depthwise over time, ``K = conv_L_cache`` taps, zero before the
sequence, no bias and no activation (``ops/short_conv.py``);
``out = y W_out``.

``full_attention``: ``q, k, v = u W_q, u W_k, u W_v`` (no bias);
``q <- RMSNorm(q; g_q)``, ``k <- RMSNorm(k; g_k)`` over each head's
channels (one weight of ``head_dim`` each, shared by the heads), then RoPE
on all channels (``models/nn.py::rope``), causal full attention
over grouped-query heads (``ops/flash_attention.py``), ``out = o W_o``.

dense feed-forward: ``(silu(m W_1) * (m W_3)) W_2``.

experts: ``s = sigmoid(m W_r)`` over ALL router_experts (the product in
float32); the experts_per_token largest of ``s + expert_bias`` are chosen;
their weights are ``s`` over the chosen, divided by their sum + 1e-6, times
``routed_scaling_factor`` (``parallel/expert.py::route_sigmoid_top_k``);

    out = sum over choices whose expert is held here of
          w_e (silu(m W_gate_e) * (m W_up_e)) W_down_e

The chip holds experts ``first_expert .. first_expert + experts_held - 1``
(the share layer: routed over all, nothing dropped, nothing standing in
for the experts other chips hold) and a slice of the vocabulary (table and
head alike). ``expert_bias`` is a parameter at zero that receives no
gradient. Each layer is recomputed in the backward pass
(``nn.recomputed``: all but what an attention kernel read and wrote); the
dense MLP and the experts take their tokens in chunks of
``expert_chunk_tokens``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.models.nn import (causal_attention, chunked_swiglu,
                                     next_token_loss, recomputed, rms_norm,
                                     rope, vocabulary_ids)
from paddlebox_tpu.monitor import device_scope
from paddlebox_tpu.ops.short_conv import short_conv
from paddlebox_tpu.parallel.expert import (held_expert_ffn,
                                           route_sigmoid_top_k)

MIXERS = ("conv", "full_attention")


class Lfm2MoeModel:
    name = "lfm2_moe"
    predicts = False            # a language-model loss has no CTR prediction
    num_extras = 1              # local_ids, staged per batch (batch_extras)
    stat_names = ("moe.assignments", "moe.held_assignments",
                  "moe.expert_load_max", "moe.route_rows",
                  "moe.whole_chunk_routes")

    def __init__(self, hidden_size: int, layer_types: tuple[str, ...],
                 dense_layers: int, conv_L_cache: int,
                 num_attention_heads: int, num_key_value_heads: int,
                 head_dim: int, intermediate_size: int,
                 moe_intermediate_size: int, router_experts: int,
                 experts_per_token: int, experts_held: int,
                 routed_scaling_factor: float, rope_theta: float,
                 norm_eps: float, vocab_size: int, seq_len: int,
                 first_expert: int = 0, key_index_bits: int = 27,
                 head_chunk: int = 2048, expert_chunk_tokens: int = 4096):
        self.emb_dim = self.d = int(hidden_size)
        self.mixers = tuple(str(k) for k in layer_types)
        if not self.mixers or set(self.mixers) - set(MIXERS):
            raise ValueError(f"layer_types {layer_types!r}: mixers are of "
                             f"the kinds {MIXERS!r}")
        self.dense_layers = int(dense_layers)
        if not 0 <= self.dense_layers <= len(self.mixers):
            raise ValueError(f"{dense_layers} dense layers of "
                             f"{len(self.mixers)}")
        self.taps = int(conv_L_cache)
        self.heads, self.kv_heads = int(num_attention_heads), \
            int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.dense_ffn = int(intermediate_size)
        self.ffn = int(moe_intermediate_size)
        self.router_experts = int(router_experts)
        self.top_k = int(experts_per_token)
        self.held = (int(first_expert), int(experts_held))
        self.scale = float(routed_scaling_factor)
        self.theta, self.eps = float(rope_theta), float(norm_eps)
        self.vocab, self.seq_len = int(vocab_size), int(seq_len)
        self.key_index_bits = int(key_index_bits)
        self.head_chunk = int(head_chunk)
        self.expert_chunk_tokens = int(expert_chunk_tokens)
        if self.held[0] + self.held[1] > self.router_experts:
            raise ValueError(f"held experts {self.held} past the router's "
                             f"{self.router_experts}")

    # -- parameters --------------------------------------------------------

    def _shapes(self, mixer: str, dense: bool) -> dict:
        d, hd = self.d, self.head_dim
        if mixer == "conv":
            shapes = {"in_proj": (d, 3 * d), "out_proj": (d, d)}
        else:
            shapes = {"wq": (d, self.heads * hd), "wk": (d, self.kv_heads * hd),
                      "wv": (d, self.kv_heads * hd), "wo": (self.heads * hd, d)}
        if dense:
            return {**shapes, "w1": (d, self.dense_ffn),
                    "w3": (d, self.dense_ffn), "w2": (self.dense_ffn, d)}
        return {**shapes, "router": (d, self.router_experts),
                "w_gate": (self.held[1], d, self.ffn),
                "w_up": (self.held[1], d, self.ffn),
                "w_down": (self.held[1], self.ffn, d)}

    def init(self, key):
        """Matrices normal with a deviation of fan_in ** -0.5; norms one;
        the convolution's taps uniform in +-K ** -0.5; ``expert_bias``
        zero."""
        d = self.d
        keys = jax.random.split(key, len(self.mixers) + 1)
        layers = []
        for i, (lk, mixer) in enumerate(zip(keys[:-1], self.mixers)):
            dense = i < self.dense_layers
            shapes = self._shapes(mixer, dense)
            ks = jax.random.split(lk, len(shapes) + 1)
            layer = {name: jax.random.normal(k, shape, jnp.float32)
                     * shape[-2] ** -0.5
                     for k, (name, shape) in zip(ks, sorted(shapes.items()))}
            layer["operator_norm"] = jnp.ones((d,), jnp.float32)
            layer["ffn_norm"] = jnp.ones((d,), jnp.float32)
            if mixer == "conv":
                bound = self.taps ** -0.5
                layer["conv_w"] = jax.random.uniform(
                    ks[-1], (self.taps, d), jnp.float32, -bound, bound)
            else:
                layer["q_norm"] = jnp.ones((self.head_dim,), jnp.float32)
                layer["k_norm"] = jnp.ones((self.head_dim,), jnp.float32)
            if not dense:
                layer["expert_bias"] = jnp.zeros((self.router_experts,),
                                                 jnp.float32)
            layers.append(layer)
        return {"layers": layers, "norm_f": jnp.ones((d,), jnp.float32),
                "head": jax.random.normal(keys[-1], (d, self.vocab),
                                          jnp.float32) * d ** -0.5}

    # -- the host stage ----------------------------------------------------

    def batch_extras(self, pb, n_shards: int = 1) -> tuple[np.ndarray]:
        return (vocabulary_ids(pb, self.key_index_bits),)

    # -- the tower ---------------------------------------------------------

    @device_scope("mixer")
    def _conv(self, p, u):
        gate_in, gate_out, x = jnp.split(u @ p["in_proj"], 3, axis=-1)
        return short_conv(gate_in, gate_out, x, p["conv_w"]) @ p["out_proj"]

    @device_scope("attention")
    def _attention(self, p, u):
        B, T, _ = u.shape
        heads = lambda y, n: y.reshape(B, T, n, self.head_dim)
        q = rms_norm(heads(u @ p["wq"], self.heads), p["q_norm"], self.eps)
        k = rms_norm(heads(u @ p["wk"], self.kv_heads), p["k_norm"], self.eps)
        v = heads(u @ p["wv"], self.kv_heads)
        return causal_attention(rope(q, self.theta), rope(k, self.theta),
                                v) @ p["wo"]

    @device_scope("dense_mlp")
    def _dense(self, p, m):
        """The dense SwiGLU MLP over m (N, d), ``expert_chunk_tokens`` at a
        time (one chunk size for both feed-forward kinds, the experts' and
        this: a bound on memory, not mathematics), a chunk recomputed in
        the backward pass."""
        return chunked_swiglu(m, p["w1"], p["w3"], p["w2"],
                              self.expert_chunk_tokens)

    def _experts(self, p, m):
        """(the held experts' part of the layer's output (N, d),
        (assignments per held expert, how the chunks were routed))."""
        with device_scope("route"):
            logits = jnp.dot(m, p["router"],
                             precision=jax.lax.Precision.HIGHEST)
        weights, experts = route_sigmoid_top_k(
            logits, p["expert_bias"], self.top_k, self.scale, 1e-6)
        y, load, took = held_expert_ffn(
            m, weights, experts, p["w_gate"], p["w_up"], p["w_down"],
            self.held, self.router_experts,
            chunk_tokens=self.expert_chunk_tokens, body="swiglu")
        return y, (load, took)

    def _layer(self, p, h, mixer: str, dense: bool):
        """One layer over h (B, T, d): (h_next, its held experts' load and
        how its chunks were routed; None for a dense layer)."""
        B, T, d = h.shape
        u = rms_norm(h, p["operator_norm"], self.eps)
        h = h + (self._conv(p, u) if mixer == "conv"
                 else self._attention(p, u))
        m = rms_norm(h, p["ffn_norm"], self.eps).reshape(B * T, d)
        y, route = (self._dense(p, m), None) if dense \
            else self._experts(p, m)
        return h + y.reshape(B, T, d), route

    def example_losses(self, params, pulled, mask, local_ids):
        """(one loss an example (B,), the assignments each held expert
        received in each expert layer (layers, experts_held), each expert
        layer's sorted rows and whole-chunk routes (layers, 2))."""
        h = pulled[..., 3:]
        routed = []
        for i, (p, mixer) in enumerate(zip(params["layers"], self.mixers)):
            h, route = recomputed(self._layer, static_argnums=(2, 3))(
                p, h, mixer, i < self.dense_layers)
            if route is not None:
                routed.append(route)
        loads, took = (jnp.stack(v) for v in zip(*routed)) if routed else (
            jnp.zeros((0, self.held[1]), jnp.int32),
            jnp.zeros((0, 2), jnp.int32))
        return next_token_loss(params, h, local_ids, mask, self.eps,
                               self.head_chunk), loads, took

    def loss(self, params, pulled, mask, dense, labels, local_ids):
        """The declared loss (models/base.py): the batch's mean, no
        prediction, and the step's routing statistics."""
        per_example, loads, took = self.example_losses(params, pulled, mask,
                                                       local_ids)
        n_tok = pulled.shape[0] * pulled.shape[1]
        loads = jax.lax.stop_gradient(loads).astype(jnp.float32)
        stats = jnp.stack([
            jnp.float32(n_tok * self.top_k
                        * (len(self.mixers) - self.dense_layers)),
            jnp.sum(loads), jnp.max(loads, initial=0.0),
            *jnp.sum(took, axis=0).astype(jnp.float32)])
        return jnp.mean(per_example), None, stats
