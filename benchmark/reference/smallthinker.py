"""Plain reference of the SmallThinker configuration
(PowerInfer/SmallThinker-21BA3B-Instruct, config.json): one chip's share of
a period of its layers, trained on a next-token loss over the chip's slice
of the vocabulary.

For a sequence of T tokens whose pulled rows are ``[show, clk, w, e_t]``:

  h_0[t] = e_t          (w, show and clk are not read; w gets no gradient)
  layer l, kind[l] from sliding_window_layout / rope_layout (0: full
  attention, no positions; 1: window of sliding_window_size, RoPE):
    r      = h W_router                       all router_experts logits
    top    = the experts_per_token largest of r;  p = softmax over those
             (= softmax over all, renormalised over the chosen)
    a      = RMSNorm_1(h)                     x / sqrt(mean x^2 + eps) * g
    q,k,v  = a W_q, a W_k, a W_v              heads of head_dim, no bias;
             kind 1: RoPE(theta) on q and k, all dims, half-split pairs
    s      = q k^T / sqrt(head_dim), kept where  s_pos <= t  and, kind 1,
             s_pos > t - window;  each key-value head serves
             num_attention_heads / num_key_value_heads query heads
    h'     = h + concat_heads(softmax(s) v) W_o
    m      = RMSNorm_2(h')
    h_next = h' + sum over e in top, e held here, of
             p_e (relu(m W_gate_e) * (m W_up_e)) W_down_e        (ReGLU)
  logits[t] = RMSNorm_f(h_L[t]) W_head        over the vocabulary slice
  loss      = mean over t = 0 .. T-2 of CE(logits[t], id[t+1])

The chip holds experts first_expert .. first_expert + experts_held - 1 of
router_experts; what the others would add is left out here as in the
program, and p is normalised over all chosen experts, never over the held.

Departures from the published model, each also under ``assumed`` in the
configuration's file:
  * the router reads the layer's input h itself (the residual stream before
    RMSNorm_1): the config says only "router placed before attention";
  * the embedding is the system's sparse table, trained by its in-table
    adagrad, the tower by dense Adam (``reference/steps.py``);
  * sequences have one fixed length and no document boundaries;
  * the family's secondary experts are not in this config and not built;
  * no auxiliary balance loss (the config names none).

Written from those equations in plain ``jax.numpy``; imports nothing of
``paddlebox_tpu``. No kernel, no sort, no cache: attention takes blocks of
queries against all keys under the mask, the experts are a scan over the
held ones with a mask over tokens, the head takes chunks of positions —
each recomputed in the backward pass so that one sequence fits beside the
state. Everything runs in the dtype it is given (``steps.py``: float32 at
``highest`` precision, bfloat16 in the control).
"""

import jax
import jax.numpy as jnp

Q_BLOCK = 128         # queries a block of the reference's attention
HEAD_CHUNK = 512      # positions a chunk of the head and loss


def _a(cfg):
    return cfg["model_args"]


def init_params(key, cfg):
    """The names the program's model gives its own (models/smallthinker)."""
    a = _a(cfg)
    d, hd, f = a["hidden_size"], a["head_dim"], a["moe_ffn_hidden_size"]
    nh, nkv, held = (a["num_attention_heads"], a["num_key_value_heads"],
                     a["experts_held"])
    shapes = {"wq": (d, nh * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd),
              "wo": (nh * hd, d), "router": (d, a["router_experts"]),
              "w_gate": (held, d, f), "w_up": (held, d, f),
              "w_down": (held, f, d)}
    keys = jax.random.split(key, len(a["layer_kinds"]) + 1)
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
            "head": jax.random.normal(keys[-1], (d, a["vocab_size"]),
                                      jnp.float32) * d ** -0.5}


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * g


def _rope(x, theta):
    """x (T, heads, head_dim)."""
    T, dim = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos.astype(x.dtype) \
        + jnp.concatenate([-x2, x1], -1) * sin.astype(x.dtype)


def _attention(q, k, v, window):
    """q (T, H, hd), k, v (T, KV, hd) of one sequence: blocks of queries
    against all keys, masked; the block is recomputed in the backward."""
    T, H, hd = q.shape
    G = H // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    block = min(Q_BLOCK, T)
    cols = jnp.arange(T)[None, :]

    @jax.checkpoint
    def one(args):
        qb, first = args
        rows = first + jnp.arange(block)[:, None]
        keep = cols <= rows
        if window:
            keep &= cols > rows - window
        s = jnp.einsum("thd,shd->hts", qb, k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(keep[None], s, -1e30), axis=-1)
        return jnp.einsum("hts,shd->thd", p, v)

    out = jax.lax.map(one, (q.reshape(T // block, block, H, hd),
                            jnp.arange(0, T, block)))
    return out.reshape(T, H * hd)


def _held_experts(m, p, top, layer, first):
    """sum over the held experts e of [weight of e for the token] * FFN_e:
    a scan over the held experts, each over every token under a mask (and
    recomputed in the backward pass, so only its weights are kept)."""
    @jax.checkpoint
    def one(m, e, wg, wu, wd):
        weight = jnp.sum(jnp.where(top == e, p, 0), axis=-1).astype(m.dtype)
        return weight[:, None] * ((jnp.maximum(m @ wg, 0) * (m @ wu)) @ wd)

    held = layer["w_gate"].shape[0]
    y, _ = jax.lax.scan(
        lambda y, xs: (y + one(m, *xs), None), jnp.zeros_like(m),
        (first + jnp.arange(held), layer["w_gate"], layer["w_up"],
         layer["w_down"]))
    return y


def _layer(layer, h, kind, a):
    T = h.shape[0]
    hd, eps = a["head_dim"], a["rms_norm_eps"]
    r = h @ layer["router"]
    vals, top = jax.lax.top_k(r, a["experts_per_token"])
    p = jax.nn.softmax(vals, axis=-1)
    x = _norm(h, layer["norm1"], eps)
    q = (x @ layer["wq"]).reshape(T, -1, hd)
    k = (x @ layer["wk"]).reshape(T, -1, hd)
    v = (x @ layer["wv"]).reshape(T, -1, hd)
    if kind:
        q, k = _rope(q, a["rope_theta"]), _rope(k, a["rope_theta"])
    h = h + _attention(q, k, v, a["sliding_window_size"] if kind else None) \
        @ layer["wo"]
    m = _norm(h, layer["norm2"], eps)
    return h + _held_experts(m, p, top, layer, a.get("first_expert", 0))


def _sequence_loss(params, e, mask, ids, a):
    """One sequence: e (T, d) embeddings, ids (T,) within the slice."""
    h = e
    for layer, kind in zip(params["layers"], a["layer_kinds"]):
        h = jax.checkpoint(_layer, static_argnums=(2, 3))(
            layer, h, kind, _Frozen(a))
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

def _mean_keys(T, window):
    """Keys a query reads, averaged over the T positions (the masked part
    is not counted)."""
    if not window or window >= T:
        return (T + 1) / 2
    return (window * (window + 1) / 2 + (T - window) * window) / T


def _layer_attention_macs(a, kind):
    T = a["seq_len"]
    keys = _mean_keys(T, a["sliding_window_size"] if kind else None)
    return T * 2 * a["num_attention_heads"] * a["head_dim"] * keys


def attention_macs(cfg):
    """Multiply-adds of one example's scores and values in all the
    attention layers of the cut, each of its own kind (forward; the masked
    part is not counted)."""
    a = _a(cfg)
    return sum(_layer_attention_macs(a, kind) for kind in a["layer_kinds"])


def _held_share(a):
    return a["experts_per_token"] * a["experts_held"] / a["router_experts"]


def expert_gmm_macs(cfg):
    """Multiply-adds of one example's held experts in all the layers
    (forward): the expected held share of the experts_per_token choices."""
    a = _a(cfg)
    return len(a["layer_kinds"]) * a["seq_len"] * _held_share(a) \
        * 3 * a["hidden_size"] * a["moe_ffn_hidden_size"]


def route_rows(cfg):
    """(rows, held, experts): the (token, choice) assignments of one chunk
    the expert layer routes at a time, the experts this chip holds and the
    experts the router chooses among — what names the route's operations
    in a trace."""
    a = _a(cfg)
    tokens = cfg["trainer"]["global_batch_size"] * a["seq_len"]
    return (min(a["expert_chunk_tokens"], tokens) * a["experts_per_token"],
            a["experts_held"], a["router_experts"])


def macs_per_example(cfg):
    a = _a(cfg)
    d, hd = a["hidden_size"], a["head_dim"]
    proj = 2 * d * a["num_attention_heads"] * hd \
        + 2 * d * a["num_key_value_heads"] * hd
    per_layer = a["seq_len"] * (proj + d * a["router_experts"])
    return (len(a["layer_kinds"]) * per_layer + attention_macs(cfg)
            + expert_gmm_macs(cfg) + a["seq_len"] * d * a["vocab_size"])


def tower_sizes(cfg):
    """(dense parameters, activation floats per example): each layer's
    residual, normed input, q, k, v, attention output, second residual and
    normed input, the held experts' hidden values and output, once; the
    head's logits."""
    a = _a(cfg)
    d, hd, f = a["hidden_size"], a["head_dim"], a["moe_ffn_hidden_size"]
    nh, nkv = a["num_attention_heads"], a["num_key_value_heads"]
    layer_params = (2 * d * nh * hd + 2 * d * nkv * hd
                    + d * a["router_experts"] + 2 * d
                    + a["experts_held"] * 3 * d * f)
    n_params = len(a["layer_kinds"]) * layer_params + d \
        + d * a["vocab_size"]
    per_token = len(a["layer_kinds"]) * (
        6 * d + 2 * nh * hd + 2 * nkv * hd + a["router_experts"]
        + _held_share(a) * 3 * f) + a["vocab_size"]
    return n_params, a["seq_len"] * per_token
