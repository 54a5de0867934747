"""SmallThinker — an ordered-token tower over a vocabulary table: causal
attention in two kinds (full without positions, sliding-window with RoPE),
a router ahead of attention over all experts, and this chip's share of the
ReGLU experts (PowerInfer/SmallThinker-21BA3B-Instruct's block).

The vocabulary is the sparse table: one sequence slot (``Slot.sequence``)
of ``seq_len`` ordered ids, whose pulled rows ``[show, clk, w, embedx]``
reach ``loss`` unpooled and in file order; ``h_0[t]`` is the row's embedx
(``w`` and the counters are not read). The model declares its own loss —
the next-token cross entropy over the vocabulary slice, taken in chunks of
positions — and no prediction (``predicts = False``).

One layer, ``kind`` 0 = full attention without positions, 1 = window of
``sliding_window_size`` with RoPE (``ops/flash_attention.py``):

    r      = h W_router                    over ALL router_experts
    p, e   = softmax over the experts_per_token largest of r
    a      = RMSNorm_1(h);  q, k, v = a W_q, a W_k, a W_v  (+ RoPE, kind 1)
    h'     = h + attention(q, k, v) W_o
    m      = RMSNorm_2(h')
    h_next = h' + sum over choices whose expert is held here of
             p (relu(m W_gate_e) * (m W_up_e)) W_down_e

The chip holds experts ``first_expert .. first_expert + experts_held - 1``
(``parallel/expert.py``'s share layer: routed over all, nothing dropped,
nothing standing in for the experts other chips hold) and a slice of the
vocabulary (table and output head alike). Each layer is recomputed in the
backward pass (``nn.recomputed``: all but what its attention kernel read
and wrote).

Host stage: ``batch_extras`` turns the batch's keys into ids within the
vocabulary — the key's low ``key_index_bits`` bits less one, the format of
a slot file whose key is ``(slot + 1) << bits | (id + 1)`` — which are the
loss's targets (``local_ids``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.models.nn import (causal_attention, next_token_loss,
                                     recomputed, rms_norm, rope,
                                     vocabulary_ids)
from paddlebox_tpu.monitor import device_scope
from paddlebox_tpu.parallel.expert import held_expert_ffn, route_top_k


class SmallThinkerModel:
    name = "smallthinker"
    predicts = False            # a language-model loss has no CTR prediction
    num_extras = 1              # local_ids, staged per batch (batch_extras)
    stat_names = ("moe.assignments", "moe.held_assignments",
                  "moe.expert_load_max", "moe.route_rows",
                  "moe.whole_chunk_routes")

    def __init__(self, hidden_size: int, num_attention_heads: int,
                 num_key_value_heads: int, head_dim: int,
                 moe_ffn_hidden_size: int, router_experts: int,
                 experts_per_token: int, experts_held: int,
                 layer_kinds: tuple[int, ...], sliding_window_size: int,
                 rope_theta: float, rms_norm_eps: float, vocab_size: int,
                 seq_len: int, first_expert: int = 0,
                 key_index_bits: int = 27, head_chunk: int = 2048,
                 expert_chunk_tokens: int = 4096):
        self.emb_dim = self.d = int(hidden_size)
        self.heads, self.kv_heads = int(num_attention_heads), \
            int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.ffn = int(moe_ffn_hidden_size)
        self.router_experts = int(router_experts)
        self.top_k = int(experts_per_token)
        self.held = (int(first_expert), int(experts_held))
        self.kinds = tuple(int(k) for k in layer_kinds)
        self.window = int(sliding_window_size)
        self.theta, self.eps = float(rope_theta), float(rms_norm_eps)
        self.vocab, self.seq_len = int(vocab_size), int(seq_len)
        self.key_index_bits = int(key_index_bits)
        self.head_chunk = int(head_chunk)
        self.expert_chunk_tokens = int(expert_chunk_tokens)
        if self.held[0] + self.held[1] > self.router_experts:
            raise ValueError(f"held experts {self.held} past the router's "
                             f"{self.router_experts}")

    # -- parameters --------------------------------------------------------

    def init(self, key):
        d, hd, f = self.d, self.head_dim, self.ffn
        shapes = {"wq": (d, self.heads * hd), "wk": (d, self.kv_heads * hd),
                  "wv": (d, self.kv_heads * hd), "wo": (self.heads * hd, d),
                  "router": (d, self.router_experts),
                  "w_gate": (self.held[1], d, f),
                  "w_up": (self.held[1], d, f),
                  "w_down": (self.held[1], f, d)}
        keys = jax.random.split(key, len(self.kinds) + 1)
        layers = []
        for lk in keys[:-1]:
            ks = jax.random.split(lk, len(shapes))
            layer = {name: jax.random.normal(k, shape, jnp.float32)
                     * shape[-2] ** -0.5
                     for k, (name, shape) in zip(ks, sorted(shapes.items()))}
            layer["norm1"] = jnp.ones((d,), jnp.float32)
            layer["norm2"] = jnp.ones((d,), jnp.float32)
            layers.append(layer)
        return {"layers": layers, "norm_f": jnp.ones((d,), jnp.float32),
                "head": jax.random.normal(keys[-1], (d, self.vocab),
                                          jnp.float32) * d ** -0.5}

    # -- the host stage ----------------------------------------------------

    def batch_extras(self, pb, n_shards: int = 1) -> tuple[np.ndarray]:
        return (vocabulary_ids(pb, self.key_index_bits),)

    # -- the tower ---------------------------------------------------------

    def _layer(self, p, h, kind: int):
        """One layer over h (B, T, d): (h_next, (assignments per held
        expert, how the chunks were routed))."""
        B, T, d = h.shape
        with device_scope("route"):
            logits = h.reshape(B * T, d) @ p["router"]
        probs, experts = route_top_k(logits, self.top_k)
        a = rms_norm(h, p["norm1"], self.eps)
        with device_scope("attention"):
            heads = lambda y, n: y.reshape(B, T, n, self.head_dim)
            q, k, v = (heads(a @ p["wq"], self.heads),
                       heads(a @ p["wk"], self.kv_heads),
                       heads(a @ p["wv"], self.kv_heads))
            if kind:
                q, k = rope(q, self.theta), rope(k, self.theta)
            o = causal_attention(q, k, v, self.window if kind else None) \
                @ p["wo"]
        h = h + o
        m = rms_norm(h, p["norm2"], self.eps).reshape(B * T, d)
        y, load, took = held_expert_ffn(
            m, probs, experts, p["w_gate"], p["w_up"], p["w_down"],
            self.held, self.router_experts,
            chunk_tokens=self.expert_chunk_tokens, body="reglu")
        return h + y.reshape(B, T, d), (load, took)

    def example_losses(self, params, pulled, mask, local_ids):
        """(one loss an example (B,), the assignments each held expert
        received in each layer (layers, experts_held), each layer's sorted
        rows and whole-chunk routes (layers, 2))."""
        h = pulled[..., 3:]
        routed = []
        for p, kind in zip(params["layers"], self.kinds):
            h, route = recomputed(self._layer, static_argnums=(2,))(
                p, h, kind)
            routed.append(route)
        loads, took = (jnp.stack(v) for v in zip(*routed))
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
            jnp.float32(n_tok * self.top_k * len(self.kinds)),
            jnp.sum(loads), jnp.max(loads),
            *jnp.sum(took, axis=0).astype(jnp.float32)])
        return jnp.mean(per_example), None, stats
