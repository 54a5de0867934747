"""Kimi Linear — an ordered-token tower over a vocabulary table whose
mixers are Kimi Delta Attention (KDA: a gated delta rule with a decay per
channel) in most layers and multi-head latent attention without positions
in the others, and whose feed-forward is a dense SwiGLU MLP in the leading
``dense_layers`` and, after them, this chip's share of sigmoid-routed
SwiGLU experts beside a shared expert (moonshotai/Kimi-Linear-48B-A3B's
``kimi_linear`` layer).

The vocabulary is the sparse table: one sequence slot (``Slot.sequence``)
of ``seq_len`` ordered ids, whose pulled rows ``[show, clk, w, embedx]``
reach ``loss`` unpooled and in file order; ``h_0[t]`` is the row's embedx.
Layer ``i`` (numbered from 1, as the configuration's lists are) mixes by
KDA where ``i`` is in ``kda_layers`` and by latent attention where it is
in ``full_attn_layers``:

    h <- h + mixer_i(RMSNorm(h; attn_norm))                 eps rms_norm_eps
    h <- h + ffn_i(RMSNorm(h; ffn_norm))

then ``RMSNorm_f``, an untied head over the vocabulary slice and the
next-token cross entropy in chunks of positions (``models/nn.py``). With
``u`` the mixer's normed input, H = kda_num_heads heads of K =
kda_head_dim channels:

    q, k, v = conv4(u W_q), conv4(u W_k), conv4(u W_v)   depthwise, causal,
              short_conv_kernel_size taps, no bias, then silu
    q       = l2norm(q) K^-0.5,  k = l2norm(k)           over a head's
                                                          channels, eps 1e-6
    log a   = -exp(A_log[h]) softplus((u W_fa) W_fb + dt_bias)   (T, H, K)
    beta    = sigmoid(u W_b)                                     (T, H)
    S_t     = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t     = S_t^T q_t                                  (``ops/kda.py``)
    out     = (RMSNorm_head(o; o_norm) * sigmoid((u W_ga) W_gb)) W_o

``W_fa`` and ``W_ga`` are K wide (the gates' low rank is the head size),
``o_norm`` one weight of K shared by the heads. The latent-attention layers
and the feed-forward halves are ``models/deepseek_v3.py``'s (this class is
its subclass): the attention half under ``mla_use_nope`` (no rotation of
the 64-channel positional part, kept at its published width), the dense
SwiGLU MLP, and the experts — sigmoid scores over all router_experts, the
experts_per_token largest of score + correction bias, their scores over
their sum + 1e-20, times routed_scaling_factor, beside the shared expert.

The chip holds experts ``first_expert .. first_expert + experts_held - 1``
(the share layer: routed over all, nothing dropped, nothing standing in for
the experts other chips hold), every mixer, the router, the shared expert
and the dense MLP whole, and a slice of the vocabulary (table and head
alike). Each layer is recomputed in the backward pass (``nn.recomputed``;
the latent-attention layer keeps what its kernels read,
``deepseek_v3.KEPT``; a KDA layer keeps nothing, and its op keeps the
state each chunk entered with). Device scopes: a KDA mixer whole —
projections, convolutions, norms, gates, the kernels and the gated norm —
under ``mixer``; the latent-attention half under ``attention`` ⊃
``latent``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddlebox_tpu.models.deepseek_v3 import KEPT, DeepseekV3Model
from paddlebox_tpu.models.nn import next_token_loss, recomputed, rms_norm
from paddlebox_tpu.monitor import device_scope
from paddlebox_tpu.ops.kda import kda


# the decay gate's initial step: log-uniform in [0.001, 0.1], floored at
# 1e-4 (the family's init, as Mamba's)
DT_RANGE = (0.001, 0.1, 1e-4)


def _l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class KimiLinearModel(DeepseekV3Model):
    name = "kimi_linear"
    stat_names = DeepseekV3Model.stat_names + ("kda.chunk_decay_log_min",
                                               "kda.pair_subchunks")

    def __init__(self, hidden_size: int, num_layers: int, dense_layers: int,
                 kda_layers: tuple, full_attn_layers: tuple,
                 kda_num_heads: int, kda_head_dim: int,
                 short_conv_kernel_size: int, num_attention_heads: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, kv_lora_rank: int, intermediate_size: int,
                 moe_intermediate_size: int, n_shared_experts: int,
                 router_experts: int, experts_per_token: int,
                 experts_held: int, routed_scaling_factor: float,
                 rope_theta: float, rms_norm_eps: float, vocab_size: int,
                 seq_len: int, first_expert: int = 0, kda_chunk: int = 128,
                 key_index_bits: int = 27, head_chunk: int = 2048,
                 expert_chunk_tokens: int = 4096):
        super().__init__(
            hidden_size, num_layers, dense_layers, num_attention_heads,
            qk_nope_head_dim, qk_rope_head_dim, v_head_dim, kv_lora_rank,
            intermediate_size, moe_intermediate_size, n_shared_experts,
            router_experts, experts_per_token, experts_held,
            routed_scaling_factor, rope_theta, False, rms_norm_eps,
            vocab_size, seq_len, first_expert, key_index_bits, head_chunk,
            expert_chunk_tokens, mla_use_nope=True)
        kda_set, mla_set = set(kda_layers), set(full_attn_layers)
        mine = range(1, self.layers + 1)
        if any((i in kda_set) == (i in mla_set) for i in mine):
            raise ValueError(
                f"layers 1..{num_layers} must each be in exactly one of "
                f"kda_layers {kda_layers} and full_attn_layers "
                f"{full_attn_layers}")
        self.kinds = tuple("kda" if i in kda_set else "mla" for i in mine)
        self.kda_heads, self.kda_dim = int(kda_num_heads), int(kda_head_dim)
        self.conv = int(short_conv_kernel_size)
        self.kda_chunk = int(kda_chunk)

    # -- parameters --------------------------------------------------------

    def _kda_shapes(self) -> dict:
        d, H, K = self.d, self.kda_heads, self.kda_dim
        return {"wq": (d, H * K), "wk": (d, H * K), "wv": (d, H * K),
                "w_fa": (d, K), "w_fb": (K, H * K), "w_b": (d, H),
                "w_ga": (d, K), "w_gb": (K, H * K), "wo": (H * K, d)}

    def _kda_init(self, key) -> dict:
        """The mixer's own as its family initialises them: the
        convolutions uniform in +-taps ** -0.5, ``A_log = log(uniform(1,
        16))``, ``dt_bias`` the inverse softplus of a step log-uniform in
        ``DT_RANGE`` (floored), ``o_norm`` one."""
        H, K = self.kda_heads, self.kda_dim
        ks = jax.random.split(key, 5)
        bound = self.conv ** -0.5
        conv = lambda k: jax.random.uniform(k, (self.conv, H * K),
                                            jnp.float32, -bound, bound)
        lo, hi, floor = DT_RANGE
        dt = jnp.maximum(jnp.exp(
            jax.random.uniform(ks[3], (H * K,), jnp.float32)
            * (math.log(hi) - math.log(lo)) + math.log(lo)), floor)
        return {"conv_q": conv(ks[0]), "conv_k": conv(ks[1]),
                "conv_v": conv(ks[2]),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    ks[4], (H,), jnp.float32, 1.0, 16.0)),
                "o_norm": jnp.ones((K,), jnp.float32)}

    def init(self, key):
        """Matrices normal with a deviation of fan_in ** -0.5; norms one;
        ``e_score_correction_bias`` zero; a KDA mixer's own as
        ``_kda_init`` says."""
        d = self.d
        keys = jax.random.split(key, self.layers + 1)
        layers = []
        for i, (lk, kind) in enumerate(zip(keys[:-1], self.kinds)):
            dense = i < self.dense_layers
            # the latent-attention half's and the feed-forward's, or the
            # feed-forward's beside a KDA mixer's
            shapes = self._shapes(dense)
            if kind == "kda":
                shapes = {**{n: s for n, s in shapes.items() if n not in (
                    "wq", "wkv_a", "wkv_b", "wo")}, **self._kda_shapes()}
            ks = jax.random.split(lk, len(shapes) + 1)
            layer = {name: jax.random.normal(k, shape, jnp.float32)
                     * shape[-2] ** -0.5
                     for k, (name, shape) in zip(ks, sorted(shapes.items()))}
            layer["attn_norm"] = jnp.ones((d,), jnp.float32)
            layer["ffn_norm"] = jnp.ones((d,), jnp.float32)
            if kind == "kda":
                layer.update(self._kda_init(ks[-1]))
            else:
                layer["kv_norm"] = jnp.ones((self.latent,), jnp.float32)
            if not dense:
                layer["e_score_correction_bias"] = jnp.zeros(
                    (self.router_experts,), jnp.float32)
            layers.append(layer)
        return {"layers": layers, "norm_f": jnp.ones((d,), jnp.float32),
                "head": jax.random.normal(keys[-1], (d, self.vocab),
                                          jnp.float32) * d ** -0.5}

    # -- the tower ---------------------------------------------------------

    def _short_conv(self, x, w):
        """silu of the causal depthwise convolution over time:
        conv(x)[t] = sum_j w[j] x[t - taps + 1 + j]."""
        T = x.shape[1]
        padded = jnp.pad(x, ((0, 0), (self.conv - 1, 0), (0, 0)))
        return jax.nn.silu(sum(w[j] * padded[:, j:j + T]
                               for j in range(self.conv)))

    @device_scope("mixer")
    def _kda_mixer(self, p, u):
        """(the mixer's output (B, T, d), the least cumulative log decay
        of any channel over one chunk, the sub-chunk visits the kernels'
        forward call took pair by pair: ``ops/kda.py::pair_subchunks``)."""
        B, T, _ = u.shape
        H, K = self.kda_heads, self.kda_dim
        L = min(self.kda_chunk, T)
        # on the chip the kernels take bfloat16 q, k, v (the device's
        # default precision for a float32 product); decays and beta stay
        # float32. Head-major, made in the write that casts them.
        cd = jnp.bfloat16 if jax.default_backend() == "tpu" else u.dtype
        heads = lambda x: jnp.swapaxes(x.reshape(B, T, H, -1), 1, 2)
        q = _l2norm(heads(self._short_conv(u @ p["wq"], p["conv_q"]))) \
            * K ** -0.5
        k = _l2norm(heads(self._short_conv(u @ p["wk"], p["conv_k"])))
        v = heads(self._short_conv(u @ p["wv"], p["conv_v"]))
        f = (u @ p["w_fa"]) @ p["w_fb"] + p["dt_bias"]
        log_a = heads(jax.nn.softplus(f)) \
            * -jnp.exp(p["A_log"])[None, :, None, None]
        beta = jnp.swapaxes(jax.nn.sigmoid(u @ p["w_b"]), 1, 2)
        o, pairs = kda(q.astype(cd), k.astype(cd), v.astype(cd), log_a,
                       beta, chunk=L)
        o = jnp.swapaxes(o, 1, 2).astype(u.dtype)              # (B, T, H, K)
        gate = jax.nn.sigmoid((u @ p["w_ga"]) @ p["w_gb"])
        y = rms_norm(o, p["o_norm"], self.eps).reshape(B, T, H * K) * gate
        decay = jnp.sum(log_a.reshape(B, H, T // L, L, K), axis=3)
        return y @ p["wo"], (jnp.min(jax.lax.stop_gradient(decay)), pairs)

    def _mixed_layer(self, p, h, kind: str, dense: bool):
        """One layer over h (B, T, d): (h_next, its held experts' load and
        how its chunks were routed — None for a dense layer — and a KDA
        mixer's decay gauge and pair-by-pair visits — 0 and 0 for latent
        attention)."""
        B, T, d = h.shape
        u = rms_norm(h, p["attn_norm"], self.eps)
        if kind == "kda":
            out, decay = self._kda_mixer(p, u)
        else:
            out, decay = self._attention(p, u), (jnp.float32(0.0),
                                                 jnp.float32(0.0))
        h = h + out
        m = rms_norm(h, p["ffn_norm"], self.eps).reshape(B * T, d)
        y, route = (self._dense(p, m), None) if dense \
            else self._experts(p, m)
        return h + y.reshape(B, T, d), route, decay

    def example_losses(self, params, pulled, mask, local_ids):
        """(one loss an example (B,), the assignments each held expert
        received in each expert layer (layers, experts_held), each expert
        layer's sorted rows and whole-chunk routes (layers, 2), the least
        chunk decay over the KDA layers, their pair-by-pair visits)."""
        h = pulled[..., 3:]
        routed, decays = [], [(jnp.float32(0.0), jnp.float32(0.0))]
        for i, (p, kind) in enumerate(zip(params["layers"], self.kinds)):
            h, route, decay = recomputed(
                self._mixed_layer, static_argnums=(2, 3), keep=KEPT)(
                    p, h, kind, i < self.dense_layers)
            if route is not None:
                routed.append(route)
            decays.append(decay)
        loads, took = (jnp.stack(v) for v in zip(*routed)) if routed else (
            jnp.zeros((0, self.held[1]), jnp.int32),
            jnp.zeros((0, 2), jnp.int32))
        decays, pairs = zip(*decays)
        return (next_token_loss(params, h, local_ids, mask, self.eps,
                                self.head_chunk), loads, took,
                jnp.min(jnp.stack(decays)), sum(pairs))

    def loss(self, params, pulled, mask, dense, labels, local_ids):
        """The declared loss (models/base.py): the batch's mean, no
        prediction, the step's routing statistics, its least chunk decay
        and its pair-by-pair sub-chunk visits."""
        per_example, loads, took, decay, pairs = self.example_losses(
            params, pulled, mask, local_ids)
        n_tok = pulled.shape[0] * pulled.shape[1]
        loads = jax.lax.stop_gradient(loads).astype(jnp.float32)
        stats = jnp.stack([
            jnp.float32(n_tok * self.top_k
                        * (self.layers - self.dense_layers)),
            jnp.sum(loads), jnp.max(loads, initial=0.0),
            *jnp.sum(took, axis=0).astype(jnp.float32), decay, pairs])
        return jnp.mean(per_example), None, stats
