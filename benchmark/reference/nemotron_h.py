"""Plain reference of the Nemotron-H configuration
(nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, config.json, ``model_type:
nemotron_h``): one chip's share of the first blocks of its pattern,
trained on a next-token loss over the chip's slice of the vocabulary.

For a sequence of T tokens whose pulled rows are ``[show, clk, w, e_t]``:

  h_0[t] = e_t          (w, show and clk are not read; w gets no gradient)
  block i, of the kind pattern[i]:   u = RMSNorm_i(h),  h <- h + mixer_i(u)
      RMSNorm(x) = x / sqrt(mean x^2 + eps) * g,  eps = layer_norm_epsilon

  'M'  Mamba-2: H = mamba_num_heads heads of P = mamba_head_dim channels,
       G = n_groups groups, N = ssm_state_size, K = conv_kernel
    [z | xBC | dt] = u W_in         widths H P | H P + 2 G N | H, no bias
    xBC = silu(conv(xBC) + b)       conv(v)[t] = sum_{j<K} w[j] v[t-K+1+j]
                                    (v before the sequence is 0), a channel
    [x | B | C] = xBC               H P | G N | G N; head j reads group
                                    j // (H / G)
    Delta_t = softplus(dt_t + dt_bias),  A = -exp(A_log)          a head
    S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T              (P, N)
    y_t = S_t C_t + D x_t
    y   = RMSNorm_g(y * silu(z))    the mean over each group of H P / G
                                    channels, one weight of H P
    out = y W_out

  '*'  attention, no positions:  q,k,v = u W_q, u W_k, u W_v  (heads of
       head_dim, no bias);  s = q k^T / sqrt(head_dim) kept where s_pos <=
       t;  each key-value head serves num_attention_heads /
       num_key_value_heads query heads;  out = concat_heads(softmax(s) v) W_o

  'E'  experts:  s = sigmoid(u W_r) over ALL router_experts;  top = the
       experts_per_token largest of s + b_corr;  w = s[top] / (sum of
       s[top] + 1e-20) * routed_scaling_factor;
    out = relu(u W_up_s)^2 W_down_s                    the shared expert
        + sum over e in top, e held here, of w_e relu(u W_up_e)^2 W_down_e

  logits[t] = RMSNorm_f(h[t]) W_head         over the vocabulary slice
  loss      = mean over t = 0 .. T-2 of CE(logits[t], id[t+1])

The chip holds experts first_expert .. first_expert + experts_held - 1 of
router_experts; what the others would add is left out here as in the
program, and w is normalised over all chosen experts, never over the held.

Departures from the published model, each also under ``assumed`` in the
configuration's file:
  * the inner width is heads x head size (the family's code; ``expand`` is
    not used); attention applies no rotary embedding (the family's code);
  * b_corr is a parameter at zero: no gradient reaches it (it only moves
    the choice) and no balance rule updates it; no auxiliary loss;
  * the embedding is the system's sparse table, trained by its in-table
    adagrad, the tower by dense Adam (``reference/steps.py``);
  * sequences have one fixed length, no document boundaries: the state
    and the convolution start from zero at position 0 and nowhere else.

Written from those equations in plain ``jax.numpy``; imports nothing of
``paddlebox_tpu``. No kernel, no sort, no chunked form: the scan is the
recurrence itself, one position at a time (in two levels of ``lax.scan``,
the inner one recomputed in the backward pass: memory, not mathematics);
the convolution is K shifted products; attention takes blocks of queries
against all keys under the mask; the routed experts are a scan over the
held ones with a mask over tokens; the head takes chunks of positions;
each block is recomputed in the backward pass so that one sequence fits
beside the state. Everything runs in the dtype it is given (``steps.py``:
float32 at ``highest`` precision, bfloat16 in the control).
"""

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128         # queries a block of the reference's attention
HEAD_CHUNK = 512      # positions a chunk of the head and loss
SCAN_BLOCK = 64       # positions the inner level of the recurrence takes


def _a(cfg):
    return cfg["model_args"]


def _inner(a):
    return a["mamba_num_heads"] * a["mamba_head_dim"]


def _shapes(a, kind):
    d = a["hidden_size"]
    if kind == "M":
        bc = 2 * a["n_groups"] * a["ssm_state_size"]
        return {"w_in": (d, 2 * _inner(a) + bc + a["mamba_num_heads"]),
                "w_out": (_inner(a), d)}
    if kind == "*":
        q = a["num_attention_heads"] * a["head_dim"]
        kv = a["num_key_value_heads"] * a["head_dim"]
        return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
    held, f = a["experts_held"], a["moe_intermediate_size"]
    fs = a["moe_shared_expert_intermediate_size"]
    return {"router": (d, a["router_experts"]), "w_up": (held, d, f),
            "w_down": (held, f, d), "shared_up": (d, fs),
            "shared_down": (fs, d)}


def init_params(key, cfg):
    """The names the program's model gives its own (models/nemotron_h).
    Matrices normal with a deviation of fan_in ** -0.5, norms one; the
    mixer's own as the family initialises them: dt_bias the inverse
    softplus of a step log-uniform in [time_step_min, time_step_max]
    floored at time_step_floor, A_log = log(uniform(1, 16)), D = 1, the
    convolution uniform in +-K ** -0.5; b_corr zero."""
    a = _a(cfg)
    d, pattern = a["hidden_size"], a["block_pattern"]
    keys = jax.random.split(key, len(pattern) + 1)
    blocks = []
    for bk, kind in zip(keys[:-1], pattern):
        shapes = _shapes(a, kind)
        ks = jax.random.split(bk, len(shapes) + 4)
        block = {name: jax.random.normal(k, shape, jnp.float32)
                 * shape[-2] ** -0.5
                 for k, (name, shape) in zip(ks, sorted(shapes.items()))}
        block["norm"] = jnp.ones((d,), jnp.float32)
        if kind == "M":
            H, K = a["mamba_num_heads"], a["conv_kernel"]
            conv_dim = _inner(a) + 2 * a["n_groups"] * a["ssm_state_size"]
            lo, hi = a.get("time_step_min", 0.001), a.get("time_step_max", 0.1)
            bound = K ** -0.5
            block["conv_w"] = jax.random.uniform(
                ks[-4], (K, conv_dim), jnp.float32, -bound, bound)
            block["conv_b"] = jax.random.uniform(
                ks[-3], (conv_dim,), jnp.float32, -bound, bound)
            dt = jnp.maximum(jnp.exp(
                jax.random.uniform(ks[-2], (H,), jnp.float32)
                * (math.log(hi) - math.log(lo)) + math.log(lo)),
                a.get("time_step_floor", 1e-4))
            block["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            block["A_log"] = jnp.log(jax.random.uniform(
                ks[-1], (H,), jnp.float32, 1.0, 16.0))
            block["D"] = jnp.ones((H,), jnp.float32)
            block["norm_g"] = jnp.ones((_inner(a),), jnp.float32)
        elif kind == "E":
            block["b_corr"] = jnp.zeros((a["router_experts"],), jnp.float32)
        blocks.append(block)
    return {"blocks": blocks, "norm_f": jnp.ones((d,), jnp.float32),
            "head": jax.random.normal(keys[-1], (d, a["vocab_size"]),
                                      jnp.float32) * d ** -0.5}


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * g


def _relu2(x):
    r = jnp.maximum(x, 0)
    return r * r


def _recurrence(x, dt, A, Bm, Cm):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t, position
    by position. x (T, H, P), dt (T, H), A (H,), Bm, Cm (T, H, N) (each
    head's group already repeated)."""
    T, H, P = x.shape

    def step(S, inp):
        xt, dtt, bt, ct = inp
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return S, jnp.sum(S * ct[:, None, :], axis=-1)

    block = SCAN_BLOCK if T % SCAN_BLOCK == 0 else T
    inner = jax.checkpoint(lambda S, inps: jax.lax.scan(step, S, inps))
    S0 = jnp.zeros((H, P, Bm.shape[-1]), x.dtype)
    _, y = jax.lax.scan(inner, S0, tuple(
        v.reshape(T // block, block, *v.shape[1:])
        for v in (x, dt, Bm, Cm)))
    return y.reshape(T, H, P)


def _mamba(p, u, a):
    T = u.shape[0]
    H, P, G, N, K = (a["mamba_num_heads"], a["mamba_head_dim"],
                     a["n_groups"], a["ssm_state_size"], a["conv_kernel"])
    inner, gn = H * P, G * N
    proj = u @ p["w_in"]
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * gn],
                  proj[:, 2 * inner + 2 * gn:])
    # the causal depthwise convolution as K shifted products
    conv = p["conv_b"] + p["conv_w"][K - 1] * xbc
    for back in range(1, K):
        shifted = jnp.concatenate(
            [jnp.zeros((back, xbc.shape[1]), xbc.dtype), xbc[:T - back]])
        conv = conv + p["conv_w"][K - 1 - back] * shifted
    xbc = conv * jax.nn.sigmoid(conv)                               # silu
    x = xbc[:, :inner].reshape(T, H, P)
    per_head = lambda v: jnp.repeat(v.reshape(T, G, N), H // G, axis=1)
    Bm, Cm = per_head(xbc[:, inner:inner + gn]), per_head(xbc[:, inner + gn:])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = _recurrence(x, dt, -jnp.exp(p["A_log"]), Bm, Cm) \
        + p["D"][:, None] * x
    y = y.reshape(T, inner) * (z * jax.nn.sigmoid(z))
    y = _norm(y.reshape(T, G, inner // G), 1, a["layer_norm_epsilon"]
              ).reshape(T, inner) * p["norm_g"]
    return y @ p["w_out"]


def _attention(p, u, a):
    """Blocks of queries against all keys, masked; the block is recomputed
    in the backward pass."""
    T, hd = u.shape[0], a["head_dim"]
    q = (u @ p["wq"]).reshape(T, -1, hd)
    k = (u @ p["wk"]).reshape(T, -1, hd)
    v = (u @ p["wv"]).reshape(T, -1, hd)
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


def _experts(p, u, a):
    s = jax.nn.sigmoid(u @ p["router"])
    _, top = jax.lax.top_k(s + p["b_corr"], a["experts_per_token"])
    w = jnp.take_along_axis(s, top, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True)
             + jnp.asarray(1e-20, w.dtype)) \
        * jnp.asarray(a["routed_scaling_factor"], w.dtype)

    # a scan over the held experts, each over every token under a mask
    # (recomputed in the backward pass, so only its weights are kept)
    @jax.checkpoint
    def one(u, e, wu, wd):
        weight = jnp.sum(jnp.where(top == e, w, 0), axis=-1).astype(u.dtype)
        return weight[:, None] * (_relu2(u @ wu) @ wd)

    first = a.get("first_expert", 0)
    y, _ = jax.lax.scan(
        lambda y, xs: (y + one(u, *xs), None),
        _relu2(u @ p["shared_up"]) @ p["shared_down"],
        (first + jnp.arange(p["w_up"].shape[0]), p["w_up"], p["w_down"]))
    return y


_MIXERS = {"M": _mamba, "*": _attention, "E": _experts}


def _block(p, h, kind, a):
    return h + _MIXERS[kind](p, _norm(h, p["norm"], a["layer_norm_epsilon"]),
                             a)


def _sequence_loss(params, e, mask, ids, a):
    """One sequence: e (T, d) embeddings, ids (T,) within the slice."""
    h = e
    for p, kind in zip(params["blocks"], a["block_pattern"]):
        h = jax.checkpoint(_block, static_argnums=(2, 3))(
            p, h, kind, _Frozen(a))
    x = _norm(h, params["norm_f"], a["layer_norm_epsilon"])
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

def _count(cfg, kind):
    return _a(cfg)["block_pattern"].count(kind)


def ssm_scan_macs(cfg):
    """Multiply-adds of one example's scans, all its 'M' blocks, forward,
    in the chunked form any blocked scan takes: a chunk of L positions and
    a head cost C B^T under the causal mask (shared by a group's heads),
    its product with Delta x under the mask, the chunk's end state and the
    carried state's part of y."""
    a = _a(cfg)
    H, P, N = a["mamba_num_heads"], a["mamba_head_dim"], a["ssm_state_size"]
    T = a["seq_len"]
    L = min(a["chunk_size"], T)
    causal = L * (L + 1) / 2
    per_head_chunk = causal * N * a["n_groups"] / H + causal * P \
        + 2 * L * P * N
    return _count(cfg, "M") * (T / L) * H * per_head_chunk


def ssm_scan_bytes(cfg):
    """Bytes one step's scans must move, forward and backward, all 'M'
    blocks, the whole batch: x, B, C and y and their cotangents at the 2
    bytes the configuration's precision gives the scan's operands, Delta
    and Delta A and their cotangents at 4. The forward reads x, B, C,
    Delta, Delta A and writes y; the backward reads them and dy and
    writes dx, dB, dC, dDelta, d(Delta A). The chunk states the backward
    pass is recomputed from are a kernel's choice and are not counted."""
    a = _a(cfg)
    H = a["mamba_num_heads"]
    x = H * a["mamba_head_dim"]
    bc = 2 * a["n_groups"] * a["ssm_state_size"]
    forward = 2 * (x + bc + x) + 4 * 2 * H
    backward = 2 * (x + bc + x) + 4 * 2 * H + 2 * (x + bc) + 4 * 2 * H
    tokens = cfg["trainer"]["global_batch_size"] * a["seq_len"]
    return _count(cfg, "M") * tokens * (forward + backward)


def _block_attention_macs(a):
    T = a["seq_len"]
    return T * 2 * a["num_attention_heads"] * a["head_dim"] * (T + 1) / 2


def attention_macs(cfg):
    """Multiply-adds of one example's scores and values in all the '*'
    blocks of the cut (forward; the masked part is not counted)."""
    return _count(cfg, "*") * _block_attention_macs(_a(cfg))


def _held_share(a):
    return a["experts_per_token"] * a["experts_held"] / a["router_experts"]


def expert_gmm_macs(cfg):
    """Multiply-adds of one example's held routed experts in all the 'E'
    blocks (forward): the expected held share of the experts_per_token
    choices, up and down; the shared expert is a dense product."""
    a = _a(cfg)
    return _count(cfg, "E") * a["seq_len"] * _held_share(a) \
        * 2 * a["hidden_size"] * a["moe_intermediate_size"]


def route_rows(cfg):
    """(rows, held, experts): the (token, choice) assignments of one chunk
    the expert blocks route at a time, the experts this chip holds and the
    experts the router chooses among — what names the route's operations
    in a trace."""
    a = _a(cfg)
    tokens = cfg["trainer"]["global_batch_size"] * a["seq_len"]
    return (min(a["expert_chunk_tokens"], tokens) * a["experts_per_token"],
            a["experts_held"], a["router_experts"])


def macs_per_example(cfg):
    a = _a(cfg)
    d, hd, T = a["hidden_size"], a["head_dim"], a["seq_len"]
    conv_dim = _inner(a) + 2 * a["n_groups"] * a["ssm_state_size"]
    mamba = d * (_inner(a) + conv_dim + a["mamba_num_heads"]) \
        + _inner(a) * d + a["conv_kernel"] * conv_dim
    attn = 2 * d * a["num_attention_heads"] * hd \
        + 2 * d * a["num_key_value_heads"] * hd
    experts = d * a["router_experts"] \
        + 2 * d * a["moe_shared_expert_intermediate_size"]
    return (T * (_count(cfg, "M") * mamba + _count(cfg, "*") * attn
                 + _count(cfg, "E") * experts + d * a["vocab_size"])
            + ssm_scan_macs(cfg) + attention_macs(cfg)
            + expert_gmm_macs(cfg))


def tower_sizes(cfg):
    """(dense parameters, activation floats per example): each block's
    residual and normed input; an 'M' block's projection, convolved xBC,
    scan output and gated output; a '*' block's q, k, v and attention
    output; an 'E' block's scores, the held experts' hidden values, the
    shared expert's and the output — once; the head's logits."""
    a = _a(cfg)
    d, hd = a["hidden_size"], a["head_dim"]
    nh, nkv = a["num_attention_heads"], a["num_key_value_heads"]
    conv_dim = _inner(a) + 2 * a["n_groups"] * a["ssm_state_size"]
    n_params = d + d * a["vocab_size"]
    for kind in a["block_pattern"]:
        shapes = _shapes(a, kind)
        n_params += d + sum(math.prod(s) for s in shapes.values())
        if kind == "M":
            n_params += (a["conv_kernel"] + 1) * conv_dim \
                + 3 * a["mamba_num_heads"] + _inner(a)
        elif kind == "E":
            n_params += a["router_experts"]
    per_token = {
        "M": 2 * d + (_inner(a) + conv_dim + a["mamba_num_heads"])
        + conv_dim + 2 * _inner(a),
        "*": 2 * d + 2 * nh * hd + 2 * nkv * hd,
        "E": 2 * d + a["router_experts"]
        + _held_share(a) * a["moe_intermediate_size"]
        + a["moe_shared_expert_intermediate_size"] + d}
    return n_params, a["seq_len"] * (
        sum(_count(cfg, k) * v for k, v in per_token.items())
        + a["vocab_size"])
