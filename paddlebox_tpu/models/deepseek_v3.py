"""DeepSeek-V3's layer — an ordered-token tower over a vocabulary table
whose attention is multi-head latent attention (MLA) without a query
low-rank path, and whose feed-forward is a dense SwiGLU MLP in the leading
``dense_layers`` layers and, after them, this chip's share of
sigmoid-routed SwiGLU experts beside a shared expert
(kakaocorp/kanana-2-30b-a3b-instruct-2601's ``deepseek_v3`` layer).

The vocabulary is the sparse table: one sequence slot (``Slot.sequence``)
of ``seq_len`` ordered ids, whose pulled rows ``[show, clk, w, embedx]``
reach ``loss`` unpooled and in file order; ``h_0[t]`` is the row's embedx.
For each layer

    h <- h + mla(RMSNorm(h; attn_norm))                        eps rms_norm_eps
    h <- h + ffn(RMSNorm(h; ffn_norm))

then ``RMSNorm_f``, an untied head over the vocabulary slice and the
next-token cross entropy in chunks of positions (``models/nn.py``). With
``u`` the attention half's normed input, H heads, ``n`` = qk_nope_head_dim,
``r`` = qk_rope_head_dim, ``dv`` = v_head_dim, ``c`` = kv_lora_rank:

    q            = u W_q                        (T, H, n + r) -> q_nope | q_pe
    [l | k_pe]   = u W_kv_a                     (T, c + r)
    l            = RMSNorm(l; kv_norm)
    [k_nope | v] = l W_kv_b                     (T, H, n + dv)
    q_pe, k_pe   = RoPE(q_pe), RoPE(k_pe)       (``nn.rope``;
                                                 adjacent pairs where
                                                 ``rope_interleave``; left
                                                 as they are where
                                                 ``mla_use_nope``)
    k            = [k_nope | k_pe, the one rotary key of every head]
    o            = softmax(q k^T (n + r)^-0.5, causal) v      (T, H, dv)
    out          = o W_o

by ``ops/flash_attention.py``, whose values have a head size of their own.
The tower hands the kernels q, k and v head-major in their dtype (bfloat16
on the chip, float32 elsewhere) and makes each once: ``W_q`` and ``W_kv_b``
as two products each, whose no-position part and values are cast in the
write that makes them; the rotary parts turned in float32 and cast; the
rotary key broadcast to every head only inside the bfloat16 write of
``k``. The casts, broadcasts and concatenations commute, so the kernels
receive the bits a float32 build cast at their door would give (for values
of ordinary size: ``nn.rope``), and the backward pass sums the rotary
key's cotangent over the heads in float32.

dense feed-forward: ``(silu(m W_1) * (m W_3)) W_2``.

experts: ``s = sigmoid(m W_r)`` over ALL router_experts (the product in
float32); the experts_per_token largest of ``s + e_score_correction_bias``
are chosen; their weights are ``s`` over the chosen, divided by their sum +
1e-20, times ``routed_scaling_factor`` (``parallel/expert.py::
route_sigmoid_top_k``; one expert group, so the group step chooses all);

    out = shared(m) + sum over choices whose expert is held here of
          w_e (silu(m W_gate_e) * (m W_up_e)) W_down_e

``shared`` the SwiGLU of the ``n_shared_experts`` shared experts as one,
``n_shared_experts x moe_intermediate_size`` wide.

The chip holds experts ``first_expert .. first_expert + experts_held - 1``
(the share layer: routed over all, nothing dropped, nothing standing in
for the experts other chips hold), the whole attention, router, shared
expert and dense MLP, and a slice of the vocabulary (table and head
alike). ``e_score_correction_bias`` is a parameter at zero that receives
no gradient. Each layer is recomputed in the backward pass
(``nn.recomputed``: all but the queries its attention kernel read and the
output and statistics it wrote; keys and values come again from the
latent); the dense MLP and the experts take their tokens in chunks of
``expert_chunk_tokens``. Device scopes: the attention half under
``attention`` (with the query products and the cast of their
no-position part), its latent path — ``W_kv_a``, the latent's norm,
``W_kv_b`` and the casts of its products, both rotations, the key's
broadcast, q's and k's bfloat16 writes — under ``latent`` inside it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.models.nn import (chunked_swiglu, next_token_loss,
                                     recomputed, rms_norm, rope,
                                     vocabulary_ids)
from paddlebox_tpu.monitor import device_scope
from paddlebox_tpu.ops.flash_attention import attention
from paddlebox_tpu.parallel.expert import (held_expert_ffn,
                                           route_sigmoid_top_k)

# what a layer's recomputation keeps of its attention op: the queries, the
# output and the row statistics. Keys and values are rebuilt from the
# latent (5.4 M multiply-adds a token against the query projection's
# 12.6 M); all five left 36 MB of the chip's memory free, these three
# 1.97 GB, at the same step time (PERF.md, section 6)
KEPT = ("pbtpu_attention_q", "pbtpu_attention_o", "pbtpu_attention_lse")


class DeepseekV3Model:
    name = "deepseek_v3"
    predicts = False            # a language-model loss has no CTR prediction
    num_extras = 1              # local_ids, staged per batch (batch_extras)
    stat_names = ("moe.assignments", "moe.held_assignments",
                  "moe.expert_load_max", "moe.route_rows",
                  "moe.whole_chunk_routes")

    def __init__(self, hidden_size: int, num_layers: int, dense_layers: int,
                 num_attention_heads: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int, kv_lora_rank: int,
                 intermediate_size: int, moe_intermediate_size: int,
                 n_shared_experts: int, router_experts: int,
                 experts_per_token: int, experts_held: int,
                 routed_scaling_factor: float, rope_theta: float,
                 rope_interleave: bool, rms_norm_eps: float, vocab_size: int,
                 seq_len: int, first_expert: int = 0,
                 key_index_bits: int = 27, head_chunk: int = 2048,
                 expert_chunk_tokens: int = 4096, mla_use_nope: bool = False):
        self.emb_dim = self.d = int(hidden_size)
        self.layers, self.dense_layers = int(num_layers), int(dense_layers)
        if not 0 <= self.dense_layers <= self.layers:
            raise ValueError(f"{dense_layers} dense layers of {num_layers}")
        self.heads = int(num_attention_heads)
        self.nope, self.rope_dim = int(qk_nope_head_dim), int(qk_rope_head_dim)
        self.v_dim, self.latent = int(v_head_dim), int(kv_lora_rank)
        self.dense_ffn = int(intermediate_size)
        self.ffn = int(moe_intermediate_size)
        self.shared_ffn = int(n_shared_experts) * self.ffn
        self.router_experts = int(router_experts)
        self.top_k = int(experts_per_token)
        self.held = (int(first_expert), int(experts_held))
        self.scale = float(routed_scaling_factor)
        self.theta, self.interleave = float(rope_theta), bool(rope_interleave)
        self.rotates = not mla_use_nope
        self.eps = float(rms_norm_eps)
        self.vocab, self.seq_len = int(vocab_size), int(seq_len)
        self.key_index_bits = int(key_index_bits)
        self.head_chunk = int(head_chunk)
        self.expert_chunk_tokens = int(expert_chunk_tokens)
        if self.held[0] + self.held[1] > self.router_experts:
            raise ValueError(f"held experts {self.held} past the router's "
                             f"{self.router_experts}")

    # -- parameters --------------------------------------------------------

    def _shapes(self, dense: bool) -> dict:
        d, H, r = self.d, self.heads, self.rope_dim
        shapes = {"wq": (d, H * (self.nope + r)),
                  "wkv_a": (d, self.latent + r),
                  "wkv_b": (self.latent, H * (self.nope + self.v_dim)),
                  "wo": (H * self.v_dim, d)}
        if dense:
            return {**shapes, "w1": (d, self.dense_ffn),
                    "w3": (d, self.dense_ffn), "w2": (self.dense_ffn, d)}
        return {**shapes, "router": (d, self.router_experts),
                "w_gate": (self.held[1], d, self.ffn),
                "w_up": (self.held[1], d, self.ffn),
                "w_down": (self.held[1], self.ffn, d),
                "shared_gate": (d, self.shared_ffn),
                "shared_up": (d, self.shared_ffn),
                "shared_down": (self.shared_ffn, d)}

    def init(self, key):
        """Matrices normal with a deviation of fan_in ** -0.5; norms one;
        ``e_score_correction_bias`` zero."""
        d = self.d
        keys = jax.random.split(key, self.layers + 1)
        layers = []
        for i, lk in enumerate(keys[:-1]):
            dense = i < self.dense_layers
            shapes = self._shapes(dense)
            ks = jax.random.split(lk, len(shapes))
            layer = {name: jax.random.normal(k, shape, jnp.float32)
                     * shape[-2] ** -0.5
                     for k, (name, shape) in zip(ks, sorted(shapes.items()))}
            layer["attn_norm"] = jnp.ones((d,), jnp.float32)
            layer["ffn_norm"] = jnp.ones((d,), jnp.float32)
            layer["kv_norm"] = jnp.ones((self.latent,), jnp.float32)
            if not dense:
                layer["e_score_correction_bias"] = jnp.zeros(
                    (self.router_experts,), jnp.float32)
            layers.append(layer)
        return {"layers": layers, "norm_f": jnp.ones((d,), jnp.float32),
                "head": jax.random.normal(keys[-1], (d, self.vocab),
                                          jnp.float32) * d ** -0.5}

    # -- the host stage ----------------------------------------------------

    def batch_extras(self, pb, n_shards: int = 1) -> tuple[np.ndarray]:
        return (vocabulary_ids(pb, self.key_index_bits),)

    # -- the tower ---------------------------------------------------------

    def _turn(self, x):
        """The rotary part's rotation (``nn.rope``), or none where the
        configuration says ``mla_use_nope``."""
        return rope(x, self.theta, self.interleave) if self.rotates else x

    @device_scope("attention")
    def _attention(self, p, u):
        B, T, d = u.shape
        H, n, r, c = self.heads, self.nope, self.rope_dim, self.latent
        cd = jnp.bfloat16 if jax.default_backend() == "tpu" else u.dtype
        wq = p["wq"].reshape(d, H, n + r)
        q_nope = jnp.einsum("btd,dhn->bhtn", u, wq[..., :n]).astype(cd)
        # head-major in float32 too: made token-major, XLA copied it into
        # a second layout for the turn
        q_pe = jnp.einsum("btd,dhr->bhtr", u, wq[..., n:])
        with device_scope("latent"):
            lk = u @ p["wkv_a"]
            lat = rms_norm(lk[..., :c], p["kv_norm"], self.eps)
            wkv = p["wkv_b"].reshape(c, H, n + self.v_dim)
            k_nope = jnp.einsum("btc,chn->bhtn", lat, wkv[..., :n]).astype(cd)
            v = jnp.einsum("btc,chv->bhtv", lat, wkv[..., n:]).astype(cd)
            k_pe = jnp.swapaxes(self._turn(lk[..., None, c:]), 1, 2)
            q_pe = jnp.swapaxes(self._turn(jnp.swapaxes(q_pe, 1, 2)), 1,
                                2).astype(cd)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            # k_nope beside the one rotary key, as a sum with zeros where
            # the other is: the compiler fuses the key's broadcast into
            # that one write, where a concatenation writes it out first
            k = jnp.pad(k_nope, ((0, 0),) * 3 + ((0, r),)) + jnp.broadcast_to(
                jnp.pad(k_pe, ((0, 0),) * 3 + ((n, 0),)),
                (B, H, T, n + r)).astype(cd)
        o = attention(q, k, v)
        return jnp.swapaxes(o, 1, 2).reshape(B, T, -1).astype(u.dtype) \
            @ p["wo"]

    @device_scope("dense_mlp")
    def _dense(self, p, m):
        return chunked_swiglu(m, p["w1"], p["w3"], p["w2"],
                              self.expert_chunk_tokens)

    def _experts(self, p, m):
        """(the held and shared experts' output (N, d), (assignments per
        held expert, how the chunks were routed))."""
        with device_scope("route"):
            logits = jnp.dot(m, p["router"],
                             precision=jax.lax.Precision.HIGHEST)
        weights, experts = route_sigmoid_top_k(
            logits, p["e_score_correction_bias"], self.top_k, self.scale,
            1e-20)
        y, load, took = held_expert_ffn(
            m, weights, experts, p["w_gate"], p["w_up"], p["w_down"],
            self.held, self.router_experts,
            chunk_tokens=self.expert_chunk_tokens, body="swiglu")
        with device_scope("dense_mlp"):
            shared = (jax.nn.silu(m @ p["shared_gate"])
                      * (m @ p["shared_up"])) @ p["shared_down"]
        return y + shared, (load, took)

    def _layer(self, p, h, dense: bool):
        """One layer over h (B, T, d): (h_next, its held experts' load and
        how its chunks were routed; None for a dense layer)."""
        B, T, d = h.shape
        h = h + self._attention(p, rms_norm(h, p["attn_norm"], self.eps))
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
        for i, p in enumerate(params["layers"]):
            h, route = recomputed(self._layer, static_argnums=(2,),
                                  keep=KEPT)(p, h, i < self.dense_layers)
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
                        * (self.layers - self.dense_layers)),
            jnp.sum(loads), jnp.max(loads, initial=0.0),
            *jnp.sum(took, axis=0).astype(jnp.float32)])
        return jnp.mean(per_example), None, stats
