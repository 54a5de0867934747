"""Plain reference of the DeepSeek-V3 layer as Kanana-2-30B-A3B configures
it (kakaocorp/kanana-2-30b-a3b-instruct-2601, config.json, ``model_type:
deepseek_v3``): one chip's share of five of its layers, trained on a
next-token loss over the chip's slice of the vocabulary.

For a sequence of T tokens whose pulled rows are ``[show, clk, w, e_t]``:

  h_0[t] = e_t          (w, show and clk are not read; w gets no gradient)
  layer i, dense where i < dense_layers:
    h <- h + mla(RMSNorm(h; attn_norm_i))
    h <- h + ffn_i(RMSNorm(h; ffn_norm_i))
      RMSNorm(x; g) = x / sqrt(mean x^2 + eps) * g,  eps = rms_norm_eps

  mla, with H heads, n = qk_nope_head_dim, r = qk_rope_head_dim,
  dv = v_head_dim, c = kv_lora_rank (no query low-rank path, no bias):
    q            = u W_q                        (T, H, n + r): q_nope | q_pe
    [l | k_pe]   = u W_kv_a                     (T, c + r)
    l            = RMSNorm(l; kv_norm)
    [k_nope | v] = l W_kv_b                     (T, H, n + dv)
    q_pe, k_pe   = RoPE(q_pe), RoPE(k_pe)       theta rope_theta; with
                   rope_interleave the pair (x[2i], x[2i+1]) turns by
                   t * theta^(-2i / r), else (x[i], x[i + r/2])
    k_h          = [k_nope_h | k_pe]            the one rotary key, every head
    s            = q_h k_h^T (n + r)^-0.5, kept where s_pos <= t
    out          = concat_heads(softmax(s) v_h) W_o

  dense feed-forward:  (silu(m W_1) * (m W_3)) W_2

  experts:  s = sigmoid(m W_r) over ALL router_experts;  top = the
       experts_per_token largest of s + e_score_correction_bias;  w =
       s[top] / (sum of s[top] + 1e-20) * routed_scaling_factor;
    out = shared(m) + sum over e in top, e held here, of
          w_e (silu(m W_gate_e) * (m W_up_e)) W_down_e
    shared(m) = (silu(m W_sg) * (m W_su)) W_sd, n_shared_experts x
          moe_intermediate_size wide

  logits[t] = RMSNorm_f(h[t]) W_head         over the vocabulary slice
  loss      = mean over t = 0 .. T-2 of CE(logits[t], id[t+1])

The chip holds experts first_expert .. first_expert + experts_held - 1 of
router_experts; what the others would add is left out here as in the
program, and w is normalised over all chosen experts, never over the held.

Departures from the published model, each also under ``assumed`` in the
configuration's file:
  * e_score_correction_bias is a parameter at zero: no gradient reaches it
    (it only moves the choice) and no rule updates it; no auxiliary loss;
    with n_group = topk_group = 1 the group step keeps every expert;
  * the embedding is the system's sparse table, trained by its in-table
    adagrad, the tower and its untied head by dense Adam
    (``reference/steps.py``);
  * rope_interleave is read as adjacent pairs turned in place (the family's
    code moves them to the two halves first; queries and keys alike, so the
    scores are the same);
  * sequences have one fixed length, no document boundaries.

Written from those equations in plain ``jax.numpy``; imports nothing of
``paddlebox_tpu``. MLA in its expanded form (every head's key and value
made whole); no kernel, no sort, no chunked form: attention takes blocks
of queries against all keys under the mask; the experts are a scan over
the held ones with a mask over tokens; the head takes chunks of positions;
each layer is recomputed in the backward pass so that one sequence fits
beside the state. Everything runs in the dtype it is given (``steps.py``:
float32 at ``highest`` precision, bfloat16 in the control).
"""

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128         # queries a block of the reference's attention
HEAD_CHUNK = 512      # positions a chunk of the head and loss


def _a(cfg):
    return cfg["model_args"]


def _dense_flags(a):
    return [i < a["dense_layers"] for i in range(a["num_layers"])]


def _attention_shapes(a):
    d, H = a["hidden_size"], a["num_attention_heads"]
    n, r = a["qk_nope_head_dim"], a["qk_rope_head_dim"]
    dv, c = a["v_head_dim"], a["kv_lora_rank"]
    return {"wq": (d, H * (n + r)), "wkv_a": (d, c + r),
            "wkv_b": (c, H * (n + dv)), "wo": (H * dv, d)}


def _shapes(a, dense):
    d = a["hidden_size"]
    shapes = _attention_shapes(a)
    if dense:
        f = a["intermediate_size"]
        return {**shapes, "w1": (d, f), "w3": (d, f), "w2": (f, d)}
    held, f = a["experts_held"], a["moe_intermediate_size"]
    fs = a["n_shared_experts"] * f
    return {**shapes, "router": (d, a["router_experts"]),
            "w_gate": (held, d, f), "w_up": (held, d, f),
            "w_down": (held, f, d), "shared_gate": (d, fs),
            "shared_up": (d, fs), "shared_down": (fs, d)}


def init_params(key, cfg):
    """The names the program's model gives its own (models/deepseek_v3).
    Matrices normal with a deviation of fan_in ** -0.5, norms one,
    e_score_correction_bias zero."""
    a = _a(cfg)
    d = a["hidden_size"]
    flags = _dense_flags(a)
    keys = jax.random.split(key, len(flags) + 1)
    layers = []
    for lk, dense in zip(keys[:-1], flags):
        shapes = _shapes(a, dense)
        ks = jax.random.split(lk, len(shapes))
        layer = {name: jax.random.normal(k, shape, jnp.float32)
                 * shape[-2] ** -0.5
                 for k, (name, shape) in zip(ks, sorted(shapes.items()))}
        layer["attn_norm"] = jnp.ones((d,), jnp.float32)
        layer["ffn_norm"] = jnp.ones((d,), jnp.float32)
        layer["kv_norm"] = jnp.ones((a["kv_lora_rank"],), jnp.float32)
        if not dense:
            layer["e_score_correction_bias"] = jnp.zeros(
                (a["router_experts"],), jnp.float32)
        layers.append(layer)
    return {"layers": layers, "norm_f": jnp.ones((d,), jnp.float32),
            "head": jax.random.normal(keys[-1], (d, a["vocab_size"]),
                                      jnp.float32) * d ** -0.5}


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * g


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rope(x, theta, interleave):
    """x (T, heads, r): the pair (x[2i], x[2i+1]) — with ``interleave`` —
    or (x[i], x[i + r/2]) turns by t * theta^(-2i / r)."""
    T, r = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    if interleave:
        even, odd = x[..., 0::2], x[..., 1::2]
        return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                         -1).reshape(x.shape)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, u, a):
    """MLA expanded: every head's key and value made whole; blocks of
    queries against all keys, masked; the block is recomputed in the
    backward pass."""
    T, H = u.shape[0], a["num_attention_heads"]
    n, r = a["qk_nope_head_dim"], a["qk_rope_head_dim"]
    dv, c = a["v_head_dim"], a["kv_lora_rank"]
    theta, inter = a["rope_theta"], a["rope_interleave"]
    q = (u @ p["wq"]).reshape(T, H, n + r)
    lk = u @ p["wkv_a"]
    latent = _norm(lk[:, :c], p["kv_norm"], a["rms_norm_eps"])
    kv = (latent @ p["wkv_b"]).reshape(T, H, n + dv)
    k_pe = _rope(lk[:, None, c:], theta, inter)
    q = jnp.concatenate([q[..., :n], _rope(q[..., n:], theta, inter)], -1)
    k = jnp.concatenate([kv[..., :n], jnp.repeat(k_pe, H, axis=1)], -1)
    v = kv[..., n:]
    block = min(Q_BLOCK, T)
    cols = jnp.arange(T)[None, :]

    @jax.checkpoint
    def one(args):
        qb, first = args
        keep = cols <= first + jnp.arange(block)[:, None]
        s = jnp.einsum("thd,shd->hts", qb, k) * (n + r) ** -0.5
        pr = jax.nn.softmax(jnp.where(keep[None], s, -1e30), axis=-1)
        return jnp.einsum("hts,shd->thd", pr, v)

    o = jax.lax.map(one, (q.reshape(T // block, block, H, n + r),
                          jnp.arange(0, T, block)))
    return o.reshape(T, H * dv) @ p["wo"]


def _dense(p, m, a):
    return (_silu(m @ p["w1"]) * (m @ p["w3"])) @ p["w2"]


def _experts(p, m, a):
    s = jax.nn.sigmoid(m @ p["router"])
    _, top = jax.lax.top_k(s + p["e_score_correction_bias"],
                           a["experts_per_token"])
    w = jnp.take_along_axis(s, top, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True)
             + jnp.asarray(1e-20, w.dtype)) \
        * jnp.asarray(a["routed_scaling_factor"], w.dtype)

    # a scan over the held experts, each over every token under a mask
    # (recomputed in the backward pass, so only its weights are kept)
    @jax.checkpoint
    def one(m, e, wg, wu, wd):
        weight = jnp.sum(jnp.where(top == e, w, 0), axis=-1).astype(m.dtype)
        return weight[:, None] * ((_silu(m @ wg) * (m @ wu)) @ wd)

    first = a.get("first_expert", 0)
    y, _ = jax.lax.scan(
        lambda y, xs: (y + one(m, *xs), None), jnp.zeros_like(m),
        (first + jnp.arange(p["w_up"].shape[0]), p["w_gate"], p["w_up"],
         p["w_down"]))
    shared = (_silu(m @ p["shared_gate"]) * (m @ p["shared_up"])) \
        @ p["shared_down"]
    return y + shared


def _layer(p, h, dense, a):
    eps = a["rms_norm_eps"]
    h = h + _attention(p, _norm(h, p["attn_norm"], eps), a)
    ffn = _dense if dense else _experts
    return h + ffn(p, _norm(h, p["ffn_norm"], eps), a)


def _sequence_loss(params, e, mask, ids, a):
    """One sequence: e (T, d) embeddings, ids (T,) within the slice."""
    h = e
    for p, dense in zip(params["layers"], _dense_flags(a)):
        h = jax.checkpoint(_layer, static_argnums=(2, 3))(
            p, h, dense, _Frozen(a))
    x = _norm(h, params["norm_f"], a["rms_norm_eps"])
    T = x.shape[0]
    chunk = min(HEAD_CHUNK, T)

    @jax.checkpoint
    def nll_of(args):
        xc, tc = args
        logp = jax.nn.log_softmax(xc @ params["head"], axis=-1)
        return -jnp.take_along_axis(logp, tc[:, None], axis=-1)[:, 0]

    targets = jnp.concatenate([ids[1:], ids[:1] * 0])
    nll = jax.lax.map(nll_of, (x.reshape(T // chunk, chunk, -1),
                               targets.reshape(T // chunk, chunk)))
    nll = nll.reshape(T)[:-1]
    counted = (mask[1:] & mask[:-1]).astype(nll.dtype)
    return jnp.sum(nll * counted) / jnp.maximum(jnp.sum(counted), 1)


class _Frozen(dict):
    """model_args as a hashable static argument of ``jax.checkpoint``."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def example_losses(params, pulled, mask, dense, labels, local_ids, cfg):
    a = _a(cfg)
    return jnp.stack([_sequence_loss(params, pulled[b, :, 3:], mask[b],
                                     local_ids[b], a)
                      for b in range(pulled.shape[0])])


# -- the work, counted from the shapes ---------------------------------------

def _expert_layers(a):
    return a["num_layers"] - a["dense_layers"]


def _tokens(cfg):
    return cfg["trainer"]["global_batch_size"] * _a(cfg)["seq_len"]


def _held_share(a):
    return a["experts_per_token"] * a["experts_held"] / a["router_experts"]


def attention_macs(cfg):
    """Multiply-adds of one example's scores (n + r channels) and values
    (dv) in every layer, all heads, forward; the masked part is not
    counted."""
    a = _a(cfg)
    T = a["seq_len"]
    return a["num_layers"] * T * (T + 1) / 2 * a["num_attention_heads"] \
        * (a["qk_nope_head_dim"] + a["qk_rope_head_dim"] + a["v_head_dim"])


def expert_gmm_macs(cfg):
    """Multiply-adds of one example's held routed experts in all the expert
    layers (forward): the expected held share of the experts_per_token
    choices, gate, up and down."""
    a = _a(cfg)
    return _expert_layers(a) * a["seq_len"] * _held_share(a) \
        * 3 * a["hidden_size"] * a["moe_intermediate_size"]


def route_rows(cfg):
    """(rows, held, experts): the (token, choice) assignments of one chunk
    the expert layers route at a time, the experts this chip holds and the
    experts the router chooses among."""
    a = _a(cfg)
    return (min(a["expert_chunk_tokens"], _tokens(cfg))
            * a["experts_per_token"], a["experts_held"], a["router_experts"])


def macs_per_example(cfg):
    a = _a(cfg)
    d = a["hidden_size"]
    mla = sum(math.prod(s) for s in _attention_shapes(a).values())
    shared = 3 * d * a["n_shared_experts"] * a["moe_intermediate_size"]
    per_token = (a["num_layers"] * mla
                 + a["dense_layers"] * 3 * d * a["intermediate_size"]
                 + _expert_layers(a) * (d * a["router_experts"] + shared)
                 + d * a["vocab_size"])
    return (a["seq_len"] * per_token + attention_macs(cfg)
            + expert_gmm_macs(cfg))


def tower_sizes(cfg):
    """(dense parameters, activation floats per example): each layer's two
    residuals and normed inputs; the attention half's q, the latent and
    rotary key, the expanded keys and values, k, the output; the dense
    MLP's two hidden products; an expert layer's scores, the held experts'
    hidden values and the shared expert's — once; the head's logits."""
    a = _a(cfg)
    d, H = a["hidden_size"], a["num_attention_heads"]
    n, r = a["qk_nope_head_dim"], a["qk_rope_head_dim"]
    dv, c = a["v_head_dim"], a["kv_lora_rank"]
    n_params = d + d * a["vocab_size"]
    per_token = a["vocab_size"]
    for dense in _dense_flags(a):
        n_params += 2 * d + c + sum(math.prod(s)
                                    for s in _shapes(a, dense).values())
        per_token += 4 * d + 2 * H * (n + r) + (c + r) + H * (n + dv) \
            + H * dv
        if dense:
            per_token += 2 * a["intermediate_size"]
        else:
            n_params += a["router_experts"]
            per_token += a["router_experts"] \
                + (_held_share(a) * 3 + 2 * a["n_shared_experts"]) \
                * a["moe_intermediate_size"]
    return n_params, a["seq_len"] * per_token
