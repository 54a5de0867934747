"""Nemotron-H — an ordered-token tower over a vocabulary table whose
blocks are of three kinds with one mixer each: Mamba-2 state-space mixers
(``M``), sigmoid-routed relu-squared experts with a shared expert (``E``)
and causal attention without positions (``*``), in the order of a pattern
string (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B's ``nemotron_h`` block).

The vocabulary is the sparse table: one sequence slot (``Slot.sequence``)
of ``seq_len`` ordered ids, whose pulled rows ``[show, clk, w, embedx]``
reach ``loss`` unpooled and in file order; ``h_0[t]`` is the row's embedx.
For each block ``i`` of the pattern

    h <- h + mixer_i(RMSNorm_i(h))                         (eps 1e-5)

then ``RMSNorm_f``, an untied head over the vocabulary slice and the
next-token cross entropy in chunks of positions (``models/nn.py``). With
``u`` the block's normed input:

``M``, Mamba-2 (``H`` heads of ``P`` channels, inner width ``H P``, ``G``
groups, state ``N``, convolution width ``K``):

    [z | xBC | dt] = u W_in          widths H P | H P + 2 G N | H, no bias
    xBC  = silu(conv(xBC) + b)       causal, depthwise, over time:
                                     conv(v)[t] = sum_j w[j] v[t - K + 1 + j]
    [x | B | C] = xBC                H P | G N | G N; head j reads group
                                     j // (H / G)
    Delta_t = softplus(dt_t + dt_bias),  a_t = exp(Delta_t A),
    A = -exp(A_log)                  a head
    S_t  = a_t S_{t-1} + Delta_t x_t B_t^T;   y_t = S_t C_t + D x_t
                                     (``ops/ssm_scan.py``, chunks of
                                     ``chunk_size``)
    y    = RMSNorm_g(y * silu(z))    the norm over each of the G groups of
                                     H P / G channels, one weight of H P
    out  = y W_out

``*``, attention: ``q, k, v = u W_q, u W_k, u W_v`` (no bias, no
positions), causal full attention over grouped-query heads
(``ops/flash_attention.py``), ``out = o W_o``.

``E``, experts: ``s = sigmoid(u W_r)`` over ALL router_experts (the
product in float32); the experts_per_token largest of ``s + b_corr`` are
chosen; their weights are ``s`` renormalised over the chosen and times
``routed_scaling_factor`` (``parallel/expert.py::route_sigmoid_top_k``);

    out = shared(u) + sum over choices whose expert is held here of
          w_e relu(u W_up_e)^2 W_down_e,   shared(u) = relu(u W_up_s)^2 W_down_s

The chip holds experts ``first_expert .. first_expert + experts_held - 1``
(the share layer: routed over all, nothing dropped, nothing standing in
for the experts other chips hold), the whole shared expert, and a slice of
the vocabulary (table and head alike). ``b_corr`` is a parameter at zero
that receives no gradient. Each block is recomputed in the backward pass
(``nn.recomputed``: all but what an attention kernel read and wrote).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.models.nn import (causal_attention, next_token_loss,
                                     recomputed, rms_norm, vocabulary_ids)
from paddlebox_tpu.monitor import device_scope
from paddlebox_tpu.ops.ssm_scan import ssm_scan
from paddlebox_tpu.parallel.expert import (held_expert_ffn,
                                           route_sigmoid_top_k)

KINDS = "ME*"


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


class NemotronHModel:
    name = "nemotron_h"
    predicts = False            # a language-model loss has no CTR prediction
    num_extras = 1              # local_ids, staged per batch (batch_extras)
    stat_names = ("moe.assignments", "moe.held_assignments",
                  "moe.expert_load_max", "moe.route_rows",
                  "moe.whole_chunk_routes", "ssm.tokens", "ssm.chunks",
                  "ssm.decay_log_min")

    def __init__(self, hidden_size: int, block_pattern: str,
                 mamba_num_heads: int, mamba_head_dim: int, n_groups: int,
                 ssm_state_size: int, conv_kernel: int, chunk_size: int,
                 num_attention_heads: int, num_key_value_heads: int,
                 head_dim: int, moe_intermediate_size: int,
                 moe_shared_expert_intermediate_size: int,
                 router_experts: int, experts_per_token: int,
                 experts_held: int, routed_scaling_factor: float,
                 layer_norm_epsilon: float, vocab_size: int, seq_len: int,
                 first_expert: int = 0, time_step_min: float = 0.001,
                 time_step_max: float = 0.1, time_step_floor: float = 1e-4,
                 key_index_bits: int = 27, head_chunk: int = 2048,
                 expert_chunk_tokens: int = 4096):
        self.emb_dim = self.d = int(hidden_size)
        self.pattern = str(block_pattern)
        if not self.pattern or set(self.pattern) - set(KINDS):
            raise ValueError(f"block_pattern {block_pattern!r}: blocks are "
                             f"of the kinds {KINDS!r}")
        self.m_heads, self.m_dim = int(mamba_num_heads), int(mamba_head_dim)
        self.groups, self.state = int(n_groups), int(ssm_state_size)
        self.conv, self.chunk = int(conv_kernel), int(chunk_size)
        self.heads, self.kv_heads = int(num_attention_heads), \
            int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.ffn = int(moe_intermediate_size)
        self.shared_ffn = int(moe_shared_expert_intermediate_size)
        self.router_experts = int(router_experts)
        self.top_k = int(experts_per_token)
        self.held = (int(first_expert), int(experts_held))
        self.scale = float(routed_scaling_factor)
        self.eps = float(layer_norm_epsilon)
        self.vocab, self.seq_len = int(vocab_size), int(seq_len)
        self.dt_range = (float(time_step_min), float(time_step_max),
                         float(time_step_floor))
        self.key_index_bits = int(key_index_bits)
        self.head_chunk = int(head_chunk)
        self.expert_chunk_tokens = int(expert_chunk_tokens)
        if self.held[0] + self.held[1] > self.router_experts:
            raise ValueError(f"held experts {self.held} past the router's "
                             f"{self.router_experts}")
        if self.m_heads % self.groups:
            raise ValueError(f"{self.m_heads} Mamba heads do not divide "
                             f"into {self.groups} groups")

    # -- parameters --------------------------------------------------------

    def _shapes(self, kind: str) -> dict:
        d, inner = self.d, self.m_heads * self.m_dim
        if kind == "M":
            bc = 2 * self.groups * self.state
            return {"w_in": (d, 2 * inner + bc + self.m_heads),
                    "w_out": (inner, d)}
        if kind == "*":
            q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
            return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
        return {"router": (d, self.router_experts),
                "w_up": (self.held[1], d, self.ffn),
                "w_down": (self.held[1], self.ffn, d),
                "shared_up": (d, self.shared_ffn),
                "shared_down": (self.shared_ffn, d)}

    def init(self, key):
        """Matrices normal with a deviation of fan_in ** -0.5; norms one;
        the Mamba mixer's own as its family initialises them: ``dt_bias``
        the inverse softplus of a step log-uniform in [time_step_min,
        time_step_max] (floored), ``A_log = log(uniform(1, 16))``,
        ``D = 1``, the convolution uniform in +-K ** -0.5."""
        d, inner = self.d, self.m_heads * self.m_dim
        keys = jax.random.split(key, len(self.pattern) + 1)
        blocks = []
        for bk, kind in zip(keys[:-1], self.pattern):
            shapes = self._shapes(kind)
            ks = jax.random.split(bk, len(shapes) + 4)
            block = {name: jax.random.normal(k, shape, jnp.float32)
                     * shape[-2] ** -0.5
                     for k, (name, shape) in zip(ks, sorted(shapes.items()))}
            block["norm"] = jnp.ones((d,), jnp.float32)
            if kind == "M":
                conv_dim = inner + 2 * self.groups * self.state
                lo, hi, floor = self.dt_range
                bound = self.conv ** -0.5
                block["conv_w"] = jax.random.uniform(
                    ks[-4], (self.conv, conv_dim), jnp.float32, -bound, bound)
                block["conv_b"] = jax.random.uniform(
                    ks[-3], (conv_dim,), jnp.float32, -bound, bound)
                dt = jnp.maximum(jnp.exp(
                    jax.random.uniform(ks[-2], (self.m_heads,), jnp.float32)
                    * (math.log(hi) - math.log(lo)) + math.log(lo)), floor)
                block["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
                block["A_log"] = jnp.log(jax.random.uniform(
                    ks[-1], (self.m_heads,), jnp.float32, 1.0, 16.0))
                block["D"] = jnp.ones((self.m_heads,), jnp.float32)
                block["norm_g"] = jnp.ones((inner,), jnp.float32)
            elif kind == "E":
                block["b_corr"] = jnp.zeros((self.router_experts,),
                                            jnp.float32)
            blocks.append(block)
        return {"blocks": blocks, "norm_f": jnp.ones((d,), jnp.float32),
                "head": jax.random.normal(keys[-1], (d, self.vocab),
                                          jnp.float32) * d ** -0.5}

    # -- the host stage ----------------------------------------------------

    def batch_extras(self, pb, n_shards: int = 1) -> tuple[np.ndarray]:
        return (vocabulary_ids(pb, self.key_index_bits),)

    # -- the tower ---------------------------------------------------------

    @device_scope("mixer")
    def _mamba(self, p, u):
        """(the mixer's output (B, T, d), the most negative cumulative
        ``Delta A`` within a chunk)."""
        B, T, _ = u.shape
        H, P, G, N, K = (self.m_heads, self.m_dim, self.groups, self.state,
                         self.conv)
        inner, gn = H * P, G * N
        proj = u @ p["w_in"]
        z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * gn],
                      proj[..., 2 * inner + 2 * gn:])
        padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(p["conv_w"][j] * padded[:, j:j + T]
                              for j in range(K)) + p["conv_b"])
        dt = jax.nn.softplus(dt + p["dt_bias"])
        # on the chip the scan's products take bfloat16 operands (the
        # device's default precision for a float32 product), float32 sums;
        # its decays stay float32
        cd = jnp.bfloat16 if jax.default_backend() == "tpu" else u.dtype
        L = min(self.chunk, T)
        y = ssm_scan(xbc[..., :inner].reshape(B, T, H, P).astype(cd), dt,
                     p["A_log"],
                     xbc[..., inner:inner + gn].reshape(B, T, G, N).astype(cd),
                     xbc[..., inner + gn:].reshape(B, T, G, N).astype(cd),
                     p["D"], chunk=L)
        y = y.reshape(B, T, inner).astype(u.dtype) * jax.nn.silu(z)
        y = rms_norm(y.reshape(B, T, G, inner // G), 1.0, self.eps
                     ).reshape(B, T, inner) * p["norm_g"]
        log_decay = jnp.sum((dt * -jnp.exp(p["A_log"])
                             ).reshape(B, T // L, L, H), axis=2)
        return y @ p["w_out"], jnp.min(jax.lax.stop_gradient(log_decay))

    @device_scope("attention")
    def _attention(self, p, u):
        B, T, _ = u.shape
        heads = lambda y, n: y.reshape(B, T, n, self.head_dim)
        q, k, v = (heads(u @ p["wq"], self.heads),
                   heads(u @ p["wk"], self.kv_heads),
                   heads(u @ p["wv"], self.kv_heads))
        return causal_attention(q, k, v) @ p["wo"]

    def _experts(self, p, u):
        """(the layer's output (B, T, d), (assignments per held expert,
        how the chunks were routed))."""
        B, T, d = u.shape
        m = u.reshape(B * T, d)
        with device_scope("route"):
            logits = jnp.dot(m, p["router"],
                             precision=jax.lax.Precision.HIGHEST)
        weights, experts = route_sigmoid_top_k(logits, p["b_corr"],
                                               self.top_k, self.scale, 1e-20)
        y, load, took = held_expert_ffn(
            m, weights, experts, None, p["w_up"], p["w_down"], self.held,
            self.router_experts, chunk_tokens=self.expert_chunk_tokens,
            body="relu2")
        with device_scope("dense_mlp"):
            shared = _relu2(m @ p["shared_up"]) @ p["shared_down"]
        return (y + shared).reshape(B, T, d), (load, took)

    def _block(self, p, h, kind: str):
        """One block over h (B, T, d): (h_next, what the kind counts —
        ``M`` its decay gauge, ``E`` its held experts' load and how its
        chunks were routed, ``*`` nothing)."""
        u = rms_norm(h, p["norm"], self.eps)
        if kind == "M":
            out, aux = self._mamba(p, u)
        elif kind == "E":
            out, aux = self._experts(p, u)
        else:
            out, aux = self._attention(p, u), None
        return h + out, aux

    def example_losses(self, params, pulled, mask, local_ids):
        """(one loss an example (B,), the assignments each held expert
        received in each ``E`` block (blocks, experts_held), each ``E``
        block's sorted rows and whole-chunk routes (blocks, 2), the ``M``
        blocks' decay gauges (blocks,))."""
        h = pulled[..., 3:]
        aux = {kind: [] for kind in KINDS}
        for p, kind in zip(params["blocks"], self.pattern):
            h, a = recomputed(self._block, static_argnums=(2,))(
                p, h, kind)
            aux[kind].append(a)
        stack = lambda v, width: jnp.stack(v) if v else jnp.zeros(
            (0,) + width, jnp.float32)
        loads, took = zip(*aux["E"]) if aux["E"] else ((), ())
        return (next_token_loss(params, h, local_ids, mask, self.eps,
                                self.head_chunk),
                stack(loads, (self.held[1],)), stack(took, (2,)),
                stack(aux["M"], ()))

    def loss(self, params, pulled, mask, dense, labels, local_ids):
        """The declared loss (models/base.py): the batch's mean, no
        prediction, and the step's routing and scan statistics."""
        per_example, loads, took, decays = self.example_losses(
            params, pulled, mask, local_ids)
        B, T = pulled.shape[:2]
        n_m, n_e = self.pattern.count("M"), self.pattern.count("E")
        loads = jax.lax.stop_gradient(loads).astype(jnp.float32)
        stats = jnp.stack([
            jnp.float32(B * T * self.top_k * n_e),
            jnp.sum(loads), jnp.max(loads, initial=0.0),
            *jnp.sum(took, axis=0).astype(jnp.float32),
            jnp.float32(B * T * n_m),
            jnp.float32(B * (T // min(self.chunk, T)) * n_m),
            jnp.min(decays, initial=0.0)])
        return jnp.mean(per_example), None, stats
