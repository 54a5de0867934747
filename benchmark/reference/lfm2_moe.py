"""Plain reference of the LFM2-MoE configuration (LiquidAI/LFM2-24B-A2B,
config.json, ``model_type: lfm2_moe``): one chip's share of five of its
layers, trained on a next-token loss over the chip's slice of the
vocabulary.

For a sequence of T tokens whose pulled rows are ``[show, clk, w, e_t]``:

  h_0[t] = e_t          (w, show and clk are not read; w gets no gradient)
  layer i, mixer layer_types[i], dense where i < dense_layers:
    h <- h + op_i(RMSNorm(h; operator_norm_i))
    h <- h + ffn_i(RMSNorm(h; ffn_norm_i))
      RMSNorm(x; g) = x / sqrt(mean x^2 + eps) * g,  eps = norm_eps

  'conv'  the gated short convolution, d = hidden_size, K = conv_L_cache:
    [B | C | x] = u W_in              three widths of d, in that order, no bias
    y[t] = C[t] * sum_{j<K} w[j] * (B x)[t - (K - 1) + j]
                                      a channel; (B x) before the sequence
                                      is 0; no bias, no activation
    out  = y W_out

  'full_attention':  q,k,v = u W_q, u W_k, u W_v  (heads of head_dim, no
       bias);  q <- RMSNorm(q; g_q), k <- RMSNorm(k; g_k) over each head's
       channels (one weight of head_dim each, shared by the heads);  RoPE
       (rope_theta) on q and k, all channels, half-split pairs;
       s = q k^T / sqrt(head_dim) kept where s_pos <= t;  each key-value
       head serves num_attention_heads / num_key_value_heads query heads;
       out = concat_heads(softmax(s) v) W_o

  dense feed-forward:  (silu(m W_1) * (m W_3)) W_2

  experts:  s = sigmoid(m W_r) over ALL router_experts;  top = the
       experts_per_token largest of s + expert_bias;  w = s[top] / (sum of
       s[top] + 1e-6) * routed_scaling_factor;
    out = sum over e in top, e held here, of
          w_e (silu(m W_gate_e) * (m W_up_e)) W_down_e

  logits[t] = RMSNorm_f(h[t]) W_head         over the vocabulary slice
  loss      = mean over t = 0 .. T-2 of CE(logits[t], id[t+1])

The chip holds experts first_expert .. first_expert + experts_held - 1 of
router_experts; what the others would add is left out here as in the
program, and w is normalised over all chosen experts, never over the held.

Departures from the published model, each also under ``assumed`` in the
configuration's file:
  * head_dim is hidden_size / num_attention_heads (config.json gives none);
  * expert_bias is a parameter at zero: no gradient reaches it (it only
    moves the choice) and no balance rule updates it; no auxiliary loss;
  * the embedding is the system's sparse table, trained by its in-table
    adagrad, the tower and its untied head by dense Adam
    (``reference/steps.py``);
  * sequences have one fixed length, no document boundaries: the
    convolution starts from zero at position 0 and nowhere else.

Written from those equations in plain ``jax.numpy``; imports nothing of
``paddlebox_tpu``. No kernel, no sort, no chunked form: the convolution is
K shifted products; attention takes blocks of queries against all keys
under the mask; the experts are a scan over the held ones with a mask over
tokens; the head takes chunks of positions; each layer is recomputed in
the backward pass so that one sequence fits beside the state. Everything
runs in the dtype it is given (``steps.py``: float32 at ``highest``
precision, bfloat16 in the control).
"""

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128         # queries a block of the reference's attention
HEAD_CHUNK = 512      # positions a chunk of the head and loss


def _a(cfg):
    return cfg["model_args"]


def _layers(a):
    """[(mixer, dense)] a layer."""
    return [(m, i < a["dense_layers"]) for i, m in enumerate(a["layer_types"])]


def _shapes(a, mixer, dense):
    d, hd = a["hidden_size"], a["head_dim"]
    if mixer == "conv":
        shapes = {"in_proj": (d, 3 * d), "out_proj": (d, d)}
    else:
        q, kv = a["num_attention_heads"] * hd, a["num_key_value_heads"] * hd
        shapes = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
    if dense:
        f = a["intermediate_size"]
        return {**shapes, "w1": (d, f), "w3": (d, f), "w2": (f, d)}
    held, f = a["experts_held"], a["moe_intermediate_size"]
    return {**shapes, "router": (d, a["router_experts"]),
            "w_gate": (held, d, f), "w_up": (held, d, f),
            "w_down": (held, f, d)}


def init_params(key, cfg):
    """The names the program's model gives its own (models/lfm2_moe).
    Matrices normal with a deviation of fan_in ** -0.5, norms one, the
    convolution's taps uniform in +-K ** -0.5, expert_bias zero."""
    a = _a(cfg)
    d, K = a["hidden_size"], a["conv_L_cache"]
    kinds = _layers(a)
    keys = jax.random.split(key, len(kinds) + 1)
    layers = []
    for lk, (mixer, dense) in zip(keys[:-1], kinds):
        shapes = _shapes(a, mixer, dense)
        ks = jax.random.split(lk, len(shapes) + 1)
        layer = {name: jax.random.normal(k, shape, jnp.float32)
                 * shape[-2] ** -0.5
                 for k, (name, shape) in zip(ks, sorted(shapes.items()))}
        layer["operator_norm"] = jnp.ones((d,), jnp.float32)
        layer["ffn_norm"] = jnp.ones((d,), jnp.float32)
        if mixer == "conv":
            layer["conv_w"] = jax.random.uniform(
                ks[-1], (K, d), jnp.float32, -K ** -0.5, K ** -0.5)
        else:
            layer["q_norm"] = jnp.ones((a["head_dim"],), jnp.float32)
            layer["k_norm"] = jnp.ones((a["head_dim"],), jnp.float32)
        if not dense:
            layer["expert_bias"] = jnp.zeros((a["router_experts"],),
                                             jnp.float32)
        layers.append(layer)
    return {"layers": layers, "norm_f": jnp.ones((d,), jnp.float32),
            "head": jax.random.normal(keys[-1], (d, a["vocab_size"]),
                                      jnp.float32) * d ** -0.5}


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * g


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _short_conv(p, u, a):
    T, d, K = u.shape[0], a["hidden_size"], a["conv_L_cache"]
    proj = u @ p["in_proj"]
    gate_in, gate_out, x = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    gated = gate_in * x
    # the causal depthwise convolution as K shifted products
    conv = p["conv_w"][K - 1] * gated
    for back in range(1, K):
        shifted = jnp.concatenate(
            [jnp.zeros((back, d), gated.dtype), gated[:T - back]])
        conv = conv + p["conv_w"][K - 1 - back] * shifted
    return (gate_out * conv) @ p["out_proj"]


def _rope(x, theta):
    """x (T, heads, head_dim): the pair (x[i], x[i + head_dim / 2]) turns
    by t * theta^(-2 i / head_dim)."""
    T, dim = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos.astype(x.dtype) \
        + jnp.concatenate([-x2, x1], -1) * sin.astype(x.dtype)


def _attention(p, u, a):
    """Blocks of queries against all keys, masked; the block is recomputed
    in the backward pass."""
    T, hd, eps = u.shape[0], a["head_dim"], a["norm_eps"]
    q = _norm((u @ p["wq"]).reshape(T, -1, hd), p["q_norm"], eps)
    k = _norm((u @ p["wk"]).reshape(T, -1, hd), p["k_norm"], eps)
    v = (u @ p["wv"]).reshape(T, -1, hd)
    q, k = _rope(q, a["rope_theta"]), _rope(k, a["rope_theta"])
    H = q.shape[1]
    k, v = (jnp.repeat(t, H // t.shape[1], axis=1) for t in (k, v))
    block = min(Q_BLOCK, T)
    cols = jnp.arange(T)[None, :]

    @jax.checkpoint
    def one(args):
        qb, first = args
        keep = cols <= first + jnp.arange(block)[:, None]
        s = jnp.einsum("thd,shd->hts", qb, k) * hd ** -0.5
        pr = jax.nn.softmax(jnp.where(keep[None], s, -1e30), axis=-1)
        return jnp.einsum("hts,shd->thd", pr, v)

    o = jax.lax.map(one, (q.reshape(T // block, block, H, hd),
                          jnp.arange(0, T, block)))
    return o.reshape(T, H * hd) @ p["wo"]


def _dense(p, m, a):
    return (_silu(m @ p["w1"]) * (m @ p["w3"])) @ p["w2"]


def _experts(p, m, a):
    s = jax.nn.sigmoid(m @ p["router"])
    _, top = jax.lax.top_k(s + p["expert_bias"], a["experts_per_token"])
    w = jnp.take_along_axis(s, top, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True)
             + jnp.asarray(1e-6, w.dtype)) \
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
    return y


def _layer(p, h, mixer, dense, a):
    eps = a["norm_eps"]
    op = _short_conv if mixer == "conv" else _attention
    h = h + op(p, _norm(h, p["operator_norm"], eps), a)
    ffn = _dense if dense else _experts
    return h + ffn(p, _norm(h, p["ffn_norm"], eps), a)


def _sequence_loss(params, e, mask, ids, a):
    """One sequence: e (T, d) embeddings, ids (T,) within the slice."""
    h = e
    for p, (mixer, dense) in zip(params["layers"], _layers(a)):
        h = jax.checkpoint(_layer, static_argnums=(2, 3, 4))(
            p, h, mixer, dense, _Frozen(a))
    x = _norm(h, params["norm_f"], a["norm_eps"])
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

def _count(cfg, mixer):
    return _a(cfg)["layer_types"].count(mixer)


def _expert_layers(cfg):
    return len(_a(cfg)["layer_types"]) - _a(cfg)["dense_layers"]


def _tokens(cfg):
    return cfg["trainer"]["global_batch_size"] * _a(cfg)["seq_len"]


def short_conv_macs(cfg):
    """Multiply-adds of one example's gated short convolutions, all its
    'conv' layers, forward, the operator between the two projections: a
    token and channel cost the gate B x, K taps and the gate C."""
    a = _a(cfg)
    return _count(cfg, "conv") * a["seq_len"] * a["hidden_size"] \
        * (a["conv_L_cache"] + 2)


def short_conv_bytes(cfg):
    """The least bytes one step's gated short convolutions must move,
    forward and backward, all 'conv' layers, the whole batch, at the 4
    bytes the configuration's precision gives the operator: the forward
    reads B, C, x and writes y; the backward reads B, C, x, dy and writes
    dB, dC, dx — eleven arrays of tokens x hidden_size. The taps and their
    gradient (K x hidden_size) are left out, and so is the forward pass
    the layer's recomputation repeats."""
    a = _a(cfg)
    return _count(cfg, "conv") * _tokens(cfg) * a["hidden_size"] * 4 * 11


def attention_macs(cfg):
    """Multiply-adds of one example's scores and values in all the
    'full_attention' layers of the cut (forward; the masked part is not
    counted)."""
    a = _a(cfg)
    T = a["seq_len"]
    return _count(cfg, "full_attention") * T * 2 \
        * a["num_attention_heads"] * a["head_dim"] * (T + 1) / 2


def _held_share(a):
    return a["experts_per_token"] * a["experts_held"] / a["router_experts"]


def expert_gmm_macs(cfg):
    """Multiply-adds of one example's held experts in all the expert
    layers (forward): the expected held share of the experts_per_token
    choices, gate, up and down."""
    a = _a(cfg)
    return _expert_layers(cfg) * a["seq_len"] * _held_share(a) \
        * 3 * a["hidden_size"] * a["moe_intermediate_size"]


def route_rows(cfg):
    """(rows, held, experts): the (token, choice) assignments of one chunk
    the expert layers route at a time, the experts this chip holds and the
    experts the router chooses among — what names the route's operations
    in a trace."""
    a = _a(cfg)
    return (min(a["expert_chunk_tokens"], _tokens(cfg))
            * a["experts_per_token"], a["experts_held"], a["router_experts"])


def macs_per_example(cfg):
    a = _a(cfg)
    d, hd = a["hidden_size"], a["head_dim"]
    conv = 4 * d * d
    attn = 2 * d * a["num_attention_heads"] * hd \
        + 2 * d * a["num_key_value_heads"] * hd
    per_token = (_count(cfg, "conv") * conv
                 + _count(cfg, "full_attention") * attn
                 + a["dense_layers"] * 3 * d * a["intermediate_size"]
                 + _expert_layers(cfg) * d * a["router_experts"]
                 + d * a["vocab_size"])
    return (a["seq_len"] * per_token + short_conv_macs(cfg)
            + attention_macs(cfg) + expert_gmm_macs(cfg))


def tower_sizes(cfg):
    """(dense parameters, activation floats per example): each layer's two
    residuals and normed inputs; a 'conv' mixer's projection, gated
    product and output; an attention mixer's q, k, v and output; the dense
    MLP's two hidden products; an expert layer's scores and the held
    experts' hidden values — once; the head's logits."""
    a = _a(cfg)
    d, hd = a["hidden_size"], a["head_dim"]
    nh, nkv = a["num_attention_heads"], a["num_key_value_heads"]
    n_params = d + d * a["vocab_size"]
    per_token = a["vocab_size"]
    for mixer, dense in _layers(a):
        n_params += 2 * d + sum(math.prod(s)
                                for s in _shapes(a, mixer, dense).values())
        n_params += a["conv_L_cache"] * d if mixer == "conv" else 2 * hd
        per_token += 4 * d + (5 * d if mixer == "conv"
                              else 2 * nh * hd + 2 * nkv * hd)
        if dense:
            per_token += 2 * a["intermediate_size"]
        else:
            n_params += a["router_experts"]
            per_token += a["router_experts"] \
                + _held_share(a) * 3 * a["moe_intermediate_size"]
    return n_params, a["seq_len"] * per_token
