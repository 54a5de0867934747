"""Plain reference of the Kimi Linear layer as Kimi-Linear-48B-A3B
configures it (moonshotai/Kimi-Linear-48B-A3B-Instruct, config.json,
``model_type: kimi_linear``): one chip's share of its first five layers,
trained on a next-token loss over the chip's slice of the vocabulary.

For a sequence of T tokens whose pulled rows are ``[show, clk, w, e_t]``:

  h_0[t] = e_t          (w, show and clk are not read; w gets no gradient)
  layer i (numbered from 1), dense where i <= dense_layers:
    h <- h + mixer_i(RMSNorm(h; attn_norm_i))     KDA where i is in
                                                  kda_layers, latent
                                                  attention where it is in
                                                  full_attn_layers
    h <- h + ffn_i(RMSNorm(h; ffn_norm_i))
      RMSNorm(x; g) = x / sqrt(mean x^2 + eps) * g,  eps = rms_norm_eps

  KDA, H = kda_num_heads heads of K = kda_head_dim, taps =
  short_conv_kernel_size:
    q, k, v = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))
              conv(x)[t] = sum_{j<taps} w[j] x[t - taps + 1 + j], x before
              the sequence 0, a channel; no bias
    q = q / sqrt(sum_c q^2 + 1e-6) * K^-0.5,  k = k / sqrt(sum_c k^2 + 1e-6)
    log a = -exp(A_log[h]) softplus((u W_fa) W_fb + dt_bias)    (T, H, K)
    beta  = sigmoid(u W_b)                                       (T, H)
    per head, S_0 = 0 (K x K):
      S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t
    out = (RMSNorm(o_h; o_norm) * sigmoid((u W_ga) W_gb)) W_o   the norm
          over each head's K channels, one weight of K for all heads

  latent attention with no rotation (mla_use_nope), n = qk_nope_head_dim,
  r = qk_rope_head_dim, dv = v_head_dim, c = kv_lora_rank, no query
  low-rank path, no bias:
    q            = u W_q                        (T, H, n + r)
    [l | k_pe]   = u W_kv_a                     (T, c + r)
    [k_nope | v] = RMSNorm(l; kv_norm) W_kv_b   (T, H, n + dv)
    k_h          = [k_nope_h | k_pe]            the one positional key,
                                                every head, not turned
    out          = concat_heads(softmax(q_h k_h^T (n + r)^-0.5, causal) v_h)
                   W_o

  dense feed-forward and experts: ``reference/deepseek_v3.py``'s (sigmoid
  scores over all router_experts, the experts_per_token largest of score +
  correction bias, their scores over their sum + 1e-20, times
  routed_scaling_factor; SwiGLU bodies; the shared expert beside them).

  logits[t] = RMSNorm_f(h[t]) W_head         over the vocabulary slice
  loss      = mean over t = 0 .. T-2 of CE(logits[t], id[t+1])

Departures from the published model, each also under ``assumed`` in the
configuration's file: the correction bias is a parameter at zero with no
rule; the embedding is the system's sparse table (``reference/steps.py``);
sequences have one fixed length, no document boundaries: every state and
convolution starts from zero at position 0 and nowhere else.

Written from those equations in plain ``jax.numpy``; imports nothing of
``paddlebox_tpu``. No kernel and no chunked form: the delta rule is the
recurrence itself, one position at a time (a ``lax.scan`` over blocks of
``SCAN_BLOCK`` positions, each block recomputed in the backward pass: the
states kept are one a block — memory, not mathematics); attention takes
blocks of queries against all keys under the mask; the routed experts are
a scan over the held ones with a mask over tokens; the head takes chunks
of positions; each layer is recomputed in the backward pass so that one
sequence fits beside the state. Everything runs in the dtype it is given
(``steps.py``: float32 at ``highest`` precision, bfloat16 in the control).
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v3 import (HEAD_CHUNK, Q_BLOCK, _Frozen,
                                             _dense, _experts, _norm, _silu)

SCAN_BLOCK = 128      # positions a block of the delta rule's scan


def _a(cfg):
    return cfg["model_args"]


def _kinds(a):
    """(mixer, dense) of each layer of the cut."""
    kda = set(a["kda_layers"])
    return [("kda" if i + 1 in kda else "mla", i < a["dense_layers"])
            for i in range(a["num_layers"])]


def _kda_shapes(a):
    d, H, K = a["hidden_size"], a["kda_num_heads"], a["kda_head_dim"]
    return {"wq": (d, H * K), "wk": (d, H * K), "wv": (d, H * K),
            "w_fa": (d, K), "w_fb": (K, H * K), "w_b": (d, H),
            "w_ga": (d, K), "w_gb": (K, H * K), "wo": (H * K, d)}


def _mla_shapes(a):
    d, H = a["hidden_size"], a["num_attention_heads"]
    n, r = a["qk_nope_head_dim"], a["qk_rope_head_dim"]
    dv, c = a["v_head_dim"], a["kv_lora_rank"]
    return {"wq": (d, H * (n + r)), "wkv_a": (d, c + r),
            "wkv_b": (c, H * (n + dv)), "wo": (H * dv, d)}


def _ffn_shapes(a, dense):
    d = a["hidden_size"]
    if dense:
        f = a["intermediate_size"]
        return {"w1": (d, f), "w3": (d, f), "w2": (f, d)}
    held, f = a["experts_held"], a["moe_intermediate_size"]
    fs = a["n_shared_experts"] * f
    return {"router": (d, a["router_experts"]),
            "w_gate": (held, d, f), "w_up": (held, d, f),
            "w_down": (held, f, d), "shared_gate": (d, fs),
            "shared_up": (d, fs), "shared_down": (fs, d)}


def _shapes(a, kind, dense):
    mixer = _kda_shapes(a) if kind == "kda" else _mla_shapes(a)
    return {**mixer, **_ffn_shapes(a, dense)}


def init_params(key, cfg):
    """The names the program's model gives its own (models/kimi_linear).
    Matrices normal with a deviation of fan_in ** -0.5, norms one, the
    correction bias zero; a KDA mixer's convolutions uniform in +-taps **
    -0.5, A_log = log(uniform(1, 16)), dt_bias the inverse softplus of a
    step log-uniform in [0.001, 0.1] floored at 1e-4."""
    a = _a(cfg)
    d, H, K = a["hidden_size"], a["kda_num_heads"], a["kda_head_dim"]
    taps = a["short_conv_kernel_size"]
    lo, hi, floor = 0.001, 0.1, 1e-4
    kinds = _kinds(a)
    keys = jax.random.split(key, len(kinds) + 1)
    layers = []
    for lk, (kind, dense) in zip(keys[:-1], kinds):
        shapes = _shapes(a, kind, dense)
        ks = jax.random.split(lk, len(shapes) + 6)
        layer = {name: jax.random.normal(k, shape, jnp.float32)
                 * shape[-2] ** -0.5
                 for k, (name, shape) in zip(ks, sorted(shapes.items()))}
        layer["attn_norm"] = jnp.ones((d,), jnp.float32)
        layer["ffn_norm"] = jnp.ones((d,), jnp.float32)
        if kind == "kda":
            bound = taps ** -0.5
            for name, k in zip(("conv_q", "conv_k", "conv_v"), ks[-6:-3]):
                layer[name] = jax.random.uniform(
                    k, (taps, H * K), jnp.float32, -bound, bound)
            dt = jnp.maximum(jnp.exp(
                jax.random.uniform(ks[-3], (H * K,), jnp.float32)
                * (math.log(hi) - math.log(lo)) + math.log(lo)), floor)
            layer["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            layer["A_log"] = jnp.log(jax.random.uniform(
                ks[-2], (H,), jnp.float32, 1.0, 16.0))
            layer["o_norm"] = jnp.ones((K,), jnp.float32)
        else:
            layer["kv_norm"] = jnp.ones((a["kv_lora_rank"],), jnp.float32)
        if not dense:
            layer["e_score_correction_bias"] = jnp.zeros(
                (a["router_experts"],), jnp.float32)
        layers.append(layer)
    return {"layers": layers, "norm_f": jnp.ones((d,), jnp.float32),
            "head": jax.random.normal(keys[-1], (d, a["vocab_size"]),
                                      jnp.float32) * d ** -0.5}


def _delta_rule(q, k, v, log_a, beta):
    """The recurrence, a position at a time: q, k, log_a (T, H, K), v (T,
    H, V), beta (T, H) -> o (T, H, V)."""
    T, H, K = q.shape

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[:, :, None] * S
        corr = vt - jnp.sum(kt[:, :, None] * S, axis=1)
        S = S + (bt[:, None] * kt)[:, :, None] * corr[:, None, :]
        return S, jnp.sum(qt[:, :, None] * S, axis=1)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(step, S, xs)

    n = min(SCAN_BLOCK, T)
    xs = tuple(x.reshape(T // n, n, *x.shape[1:])
               for x in (q, k, v, log_a, beta))
    _, o = jax.lax.scan(block, jnp.zeros((H, K, v.shape[-1]), q.dtype), xs)
    return o.reshape(T, H, v.shape[-1])


def _kda_gates(p, u, a):
    """(log a (T, H, K), beta (T, H))."""
    T = u.shape[0]
    H, K = a["kda_num_heads"], a["kda_head_dim"]
    f = (u @ p["w_fa"]) @ p["w_fb"] + p["dt_bias"]
    log_a = -jnp.exp(p["A_log"])[:, None] \
        * jax.nn.softplus(f).reshape(T, H, K)
    return log_a, jax.nn.sigmoid(u @ p["w_b"])


def _kda(p, u, a):
    T = u.shape[0]
    H, K = a["kda_num_heads"], a["kda_head_dim"]
    taps = a["short_conv_kernel_size"]

    def conv(x, w):
        padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
        return _silu(sum(w[j] * padded[j:j + T] for j in range(taps)))

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                            + jnp.asarray(1e-6, x.dtype))

    q = l2(conv(u @ p["wq"], p["conv_q"]).reshape(T, H, K)) \
        * jnp.asarray(K ** -0.5, u.dtype)
    k = l2(conv(u @ p["wk"], p["conv_k"]).reshape(T, H, K))
    v = conv(u @ p["wv"], p["conv_v"]).reshape(T, H, K)
    log_a, beta = _kda_gates(p, u, a)
    o = _delta_rule(q, k, v, log_a, beta)
    gate = jax.nn.sigmoid((u @ p["w_ga"]) @ p["w_gb"]).reshape(T, H, K)
    o = _norm(o, p["o_norm"], a["rms_norm_eps"]) * gate
    return o.reshape(T, H * K) @ p["wo"]


def _mla(p, u, a):
    """Latent attention, no rotation, every head's key and value made
    whole; blocks of queries against all keys, masked; the block is
    recomputed in the backward pass."""
    T, H = u.shape[0], a["num_attention_heads"]
    n, r = a["qk_nope_head_dim"], a["qk_rope_head_dim"]
    dv, c = a["v_head_dim"], a["kv_lora_rank"]
    q = (u @ p["wq"]).reshape(T, H, n + r)
    lk = u @ p["wkv_a"]
    kv = (_norm(lk[:, :c], p["kv_norm"], a["rms_norm_eps"])
          @ p["wkv_b"]).reshape(T, H, n + dv)
    k = jnp.concatenate([kv[..., :n], jnp.repeat(lk[:, None, c:], H, axis=1)],
                        -1)
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


def _layer(p, h, kind, dense, a):
    eps = a["rms_norm_eps"]
    mixer = _kda if kind == "kda" else _mla
    h = h + mixer(p, _norm(h, p["attn_norm"], eps), a)
    ffn = _dense if dense else _experts
    return h + ffn(p, _norm(h, p["ffn_norm"], eps), a)


def _sequence_loss(params, e, mask, ids, a):
    """One sequence: e (T, d) embeddings, ids (T,) within the slice."""
    h = e
    for p, (kind, dense) in zip(params["layers"], _kinds(a)):
        h = jax.checkpoint(_layer, static_argnums=(2, 3, 4))(
            p, h, kind, dense, _Frozen(a))
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


def example_losses(params, pulled, mask, dense, labels, local_ids, cfg):
    a = _a(cfg)
    return jnp.stack([_sequence_loss(params, pulled[b, :, 3:], mask[b],
                                     local_ids[b], a)
                      for b in range(pulled.shape[0])])


def chunk_decay_log_min(params, pulled, cfg):
    """The least sum of log a over one chunk of ``kda_chunk`` positions,
    any channel, head, KDA layer and example of the batch (the program's
    gauge ``kda.chunk_decay_log_min``)."""
    a = _a(cfg)
    eps = a["rms_norm_eps"]
    least = jnp.float32(0.0)
    for e in pulled[..., 3:]:
        h = e
        for p, (kind, dense) in zip(params["layers"], _kinds(a)):
            if kind == "kda":
                log_a, _ = _kda_gates(p, _norm(h, p["attn_norm"], eps), a)
                T, H, K = log_a.shape
                L = min(a["kda_chunk"], T)
                least = jnp.minimum(least, jnp.min(
                    jnp.sum(log_a.reshape(T // L, L, H, K), axis=1)))
            h = _layer(p, h, kind, dense, a)
    return least


# -- the work, counted from the shapes ---------------------------------------

def _count(a, kind):
    return sum(k == kind for k, _ in _kinds(a))


def _expert_layers(a):
    return a["num_layers"] - a["dense_layers"]


def _tokens(cfg):
    return cfg["trainer"]["global_batch_size"] * _a(cfg)["seq_len"]


def _held_share(a):
    return a["experts_per_token"] * a["experts_held"] / a["router_experts"]


def _chunk(a):
    return min(a["kda_chunk"], a["seq_len"])


def kda_macs(cfg):
    """Multiply-adds of one example's delta rule in every KDA layer,
    forward, in the chunked form any blocked implementation takes (chunks
    of C = kda_chunk positions, a head at a time): the chunk's two grams
    (q against k, positions s <= t; beta k against k, s < t) over the K
    channels, the unit lower triangular inverse (C^3 / 6), its products
    with beta k and beta v, the entering state's three products (with the
    decayed beta k, with q, and carried on to the next chunk) and the
    grams' product with the corrected values; the masked half of every
    triangular product is not counted."""
    a = _a(cfg)
    H, K = a["kda_num_heads"], a["kda_head_dim"]
    T, C = a["seq_len"], _chunk(a)
    tri = C * (C + 1) / 2
    per_chunk = (tri + C * (C - 1) / 2) * K + C ** 3 / 6 \
        + tri * (K + 2 * K) + 3 * C * K * K
    return _count(a, "kda") * (T / C) * H * per_chunk


def kda_bytes(cfg):
    """Bytes one step's delta rules must move, forward and backward, all
    KDA layers, the whole batch: q, k, v and o and their cotangents at the
    2 bytes the configuration's precision gives the op's operands, log a
    and beta and their cotangents at 4. The forward reads q, k, v, log a,
    beta and writes o; the backward reads them and do and writes dq, dk,
    dv, d log a, d beta; and the state at every chunk's boundary (K x K
    floats a head), once."""
    a = _a(cfg)
    H, K = a["kda_num_heads"], a["kda_head_dim"]
    inputs = H * (3 * K * 2 + K * 4 + 4)
    per_token = (inputs + H * K * 2) + (inputs + H * K * 2) + inputs
    states = (a["seq_len"] / _chunk(a)) * H * K * K * 4
    batch = cfg["trainer"]["global_batch_size"]
    return _count(a, "kda") * batch * (a["seq_len"] * per_token + states)


def attention_macs(cfg):
    """Multiply-adds of one example's scores (n + r channels) and values
    (dv) in every latent-attention layer, all heads, forward; the masked
    part is not counted."""
    a = _a(cfg)
    T = a["seq_len"]
    return _count(a, "mla") * T * (T + 1) / 2 * a["num_attention_heads"] \
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
    d, H, K = a["hidden_size"], a["kda_num_heads"], a["kda_head_dim"]
    kda = sum(math.prod(s) for s in _kda_shapes(a).values()) \
        + 3 * a["short_conv_kernel_size"] * H * K
    mla = sum(math.prod(s) for s in _mla_shapes(a).values())
    shared = 3 * d * a["n_shared_experts"] * a["moe_intermediate_size"]
    per_token = (_count(a, "kda") * kda + _count(a, "mla") * mla
                 + a["dense_layers"] * 3 * d * a["intermediate_size"]
                 + _expert_layers(a) * (d * a["router_experts"] + shared)
                 + d * a["vocab_size"])
    return (a["seq_len"] * per_token + kda_macs(cfg) + attention_macs(cfg)
            + expert_gmm_macs(cfg))


def tower_sizes(cfg):
    """(dense parameters, activation floats per example): each layer's two
    residuals and normed inputs; a KDA mixer's three projections before
    and after their convolution, the decay's two products, beta, the
    gate's two products and the output; a latent-attention half's q, the
    latent and positional key, the expanded keys and values, k, the
    output; the dense MLP's two hidden products; an expert layer's scores,
    the held experts' hidden values and the shared expert's — once; the
    head's logits."""
    a = _a(cfg)
    d, H, K = a["hidden_size"], a["kda_num_heads"], a["kda_head_dim"]
    Ha = a["num_attention_heads"]
    n, r = a["qk_nope_head_dim"], a["qk_rope_head_dim"]
    dv, c = a["v_head_dim"], a["kv_lora_rank"]
    taps = a["short_conv_kernel_size"]
    n_params = d + d * a["vocab_size"]
    per_token = a["vocab_size"]
    for kind, dense in _kinds(a):
        n_params += 2 * d + sum(math.prod(s)
                                for s in _shapes(a, kind, dense).values())
        per_token += 4 * d
        if kind == "kda":
            n_params += 3 * taps * H * K + H + H * K + K
            per_token += 6 * H * K + (K + H * K) + H + (K + H * K) + H * K
        else:
            n_params += c
            per_token += 2 * Ha * (n + r) + (c + r) + Ha * (n + dv) + Ha * dv
        if dense:
            per_token += 2 * a["intermediate_size"]
        else:
            n_params += a["router_experts"]
            per_token += a["router_experts"] \
                + (_held_share(a) * 3 + 2 * a["n_shared_experts"]) \
                * a["moe_intermediate_size"]
    return n_params, a["seq_len"] * per_token
