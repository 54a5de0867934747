"""Expert parallelism — MoE with all_to_all token dispatch over an ep axis.

Absent in the reference (SURVEY.md §2.3: "Expert parallelism — NO"; its
MMoE runs every expert densely on every device). Here experts shard across
an ``ep`` mesh axis and tokens travel to their experts through the same
fixed-capacity ``all_to_all`` pattern the embedding engine uses for keys
(embedding/sharded.py) — the TPU-native shape of MoE dispatch:

    gate (top-k softmax) → route token features into per-(device, expert)
    capacity lanes → all_to_all over ep → batched expert MLPs
    (one einsum over stacked local experts) → all_to_all back →
    weighted combine.

Tokens beyond a lane's capacity are dropped (standard MoE capacity-factor
semantics; monitor with `dropped_tokens`). Numerics match `moe_reference`
for all surviving tokens.

The share layer (a routing rule + ``held_expert_ffn``) is the other shape
of expert parallelism: a chip is told which experts of a layer it holds
(``held = (first, count)`` of ``n_experts``), every token is routed over
ALL experts, and the chip computes the part of the layer's output that its
held experts contribute — grouped matrix products over the (token, choice)
assignments sorted by held expert, no capacity and no drop at any
imbalance. What the absent experts would add is another chip's part and is
not computed, approximated or stood in for; on a mesh of one chip the layer
runs without an exchange. Two routing rules (``route_top_k``: a softmax
over the chosen logits; ``route_sigmoid_top_k``: sigmoid scores chosen
with a correction bias, renormalised and scaled) and two expert bodies
(gated ReGLU; non-gated relu squared) share the one layer. An expert that
every chip computes alike (a shared expert) is the model's to add: the sum
of the shares counts it once.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

EP_AXIS = "ep"


def make_ep_mesh(n_ep: int,
                 devices: Sequence[jax.Device] | None = None) -> Mesh:
    devs = list(devices) if devices is not None else list(jax.devices())
    return Mesh(np.array(devs[:n_ep]), (EP_AXIS,))


def init_moe(key, num_experts: int, d_model: int, d_hidden: int) -> dict:
    """Gate + stacked expert FFNs (unsharded; shard with shard_moe_params)."""
    kg, k1, k2 = jax.random.split(key, 3)
    s1 = 1.0 / jnp.sqrt(d_model)
    s2 = 1.0 / jnp.sqrt(d_hidden)
    return {
        "gate": jax.random.normal(kg, (d_model, num_experts),
                                  jnp.float32) * s1,
        "w1": jax.random.normal(k1, (num_experts, d_model, d_hidden),
                                jnp.float32) * s1,
        "b1": jnp.zeros((num_experts, d_hidden), jnp.float32),
        "w2": jax.random.normal(k2, (num_experts, d_hidden, d_model),
                                jnp.float32) * s2,
        "b2": jnp.zeros((num_experts, d_model), jnp.float32),
    }


def _expert_ffn(w1, b1, w2, b2, x):
    """x (E, n, D) through per-expert FFNs — one batched einsum pair."""
    h = jax.nn.relu(jnp.einsum("end,edh->enh", x, w1) + b1[:, None, :])
    return jnp.einsum("enh,ehd->end", h, w2) + b2[:, None, :]


def moe_reference(params: dict, x: jnp.ndarray, top_k: int = 2
                  ) -> jnp.ndarray:
    """Dense ground truth: every expert computes every token."""
    logits = x @ params["gate"]
    weights, experts = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    all_out = _expert_ffn(params["w1"], params["b1"], params["w2"],
                          params["b2"],
                          jnp.broadcast_to(x, (params["w1"].shape[0],
                                               *x.shape)))
    out = jnp.zeros_like(x)
    for k in range(top_k):
        out = out + weights[:, k:k + 1] * all_out[experts[:, k],
                                                  jnp.arange(x.shape[0])]
    return out


def shard_moe_params(mesh: Mesh, params: dict) -> dict:
    """Experts shard over ep (leading axis); the gate is replicated."""
    ex = NamedSharding(mesh, P(EP_AXIS))
    rep = NamedSharding(mesh, P())
    return {
        "gate": jax.device_put(params["gate"], rep),
        "w1": jax.device_put(params["w1"], ex),
        "b1": jax.device_put(params["b1"], ex),
        "w2": jax.device_put(params["w2"], ex),
        "b2": jax.device_put(params["b2"], ex),
    }


def dropped_tokens(params: dict, x: jnp.ndarray, n_ep: int,
                   top_k: int = 2, capacity_factor: float = 2.0) -> int:
    """How many (token, choice) assignments the dispatch will drop.

    Mirrors make_moe exactly: each top-k round has its OWN capacity lanes
    (a separate all_to_all per k), so counts are per (source device,
    expert, k)."""
    logits = x @ params["gate"]
    _, experts = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    E = params["w1"].shape[0]
    n_local = x.shape[0] // n_ep
    cap = _capacity(n_local, E, capacity_factor)
    dropped = 0
    for k in range(top_k):
        for dev in range(n_ep):
            loc = np.asarray(experts[dev * n_local:(dev + 1) * n_local, k])
            counts = np.bincount(loc, minlength=E)
            dropped += int(np.maximum(counts - cap, 0).sum())
    return dropped


def _capacity(n_local: int, n_experts: int, factor: float) -> int:
    avg = n_local * 1.0 / n_experts  # per (local batch, expert) average
    return max(1, int(np.ceil(avg * factor)))


def make_moe(mesh: Mesh, num_experts: int, top_k: int = 2,
             capacity_factor: float = 2.0) -> Callable:
    """→ fn(sharded_params, x) with x batch-sharded over ep.

    Requires num_experts % n_ep == 0."""
    n_ep = mesh.shape[EP_AXIS]
    if num_experts % n_ep:
        raise ValueError(f"{num_experts} experts not divisible by "
                         f"ep={n_ep}")
    e_local = num_experts // n_ep

    def body(params: dict, x: jnp.ndarray) -> jnp.ndarray:
        n, d = x.shape  # local batch
        cap = _capacity(n, num_experts, capacity_factor)
        logits = x @ params["gate"]
        weights, experts = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        out = jnp.zeros_like(x)
        for k in range(top_k):
            # destination = global expert id; device dev = id // e_local
            # owns local expert id % e_local
            dest = experts[:, k]
            # lane position within each destination: stable rank
            order = jnp.argsort(dest)
            sdest = dest[order]
            counts = jnp.bincount(dest, length=num_experts)
            starts = jnp.cumsum(counts) - counts
            pos = jnp.arange(n, dtype=jnp.int32) - starts[sdest]
            valid = pos < cap
            # send buffers: features + originating row (for the return trip)
            send_x = jnp.zeros((n_ep, e_local, cap, d), x.dtype)
            send_row = jnp.full((n_ep, e_local, cap), -1, jnp.int32)
            sdev, sloc = sdest // e_local, sdest % e_local
            rows = order.astype(jnp.int32)
            send_x = send_x.at[sdev, sloc, pos].set(
                jnp.where(valid[:, None], x[order], 0.0), mode="drop")
            send_row = send_row.at[sdev, sloc, pos].set(
                jnp.where(valid, rows, -1), mode="drop")
            # dispatch / compute / return. After the tiled all_to_all,
            # axis 0 indexes the SOURCE device, so fold (src, cap) into the
            # expert token axis with an explicit transpose — and undo it
            # symmetrically on the way back.
            recv_x = lax.all_to_all(send_x, EP_AXIS, 0, 0, tiled=True)
            recv_x = recv_x.transpose(1, 0, 2, 3).reshape(
                e_local, n_ep * cap, d)
            y = _expert_ffn(params["w1"], params["b1"], params["w2"],
                            params["b2"], recv_x)
            y = y.reshape(e_local, n_ep, cap, d).transpose(1, 0, 2, 3)
            back = lax.all_to_all(y, EP_AXIS, 0, 0, tiled=True)
            # scatter outputs to their originating rows
            flat_row = send_row.reshape(-1)
            flat_y = back.reshape(-1, d)
            safe = jnp.where(flat_row >= 0, flat_row, n)
            gathered = jnp.zeros((n + 1, d), x.dtype).at[safe].add(
                flat_y, mode="drop")[:n]
            out = out + weights[:, k:k + 1] * gathered
        return out

    spec_p = {"gate": P(), "w1": P(EP_AXIS), "b1": P(EP_AXIS),
              "w2": P(EP_AXIS), "b2": P(EP_AXIS)}

    # jitted once — rebuilding per call would retrace every step
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec_p, P(EP_AXIS)),
        out_specs=P(EP_AXIS)))


# ---------------------------------------------------------------------------
# the share layer: one chip's held experts of a layer routed over all
# ---------------------------------------------------------------------------

def route_top_k(router_logits: jnp.ndarray, top_k: int
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(probs, experts), both (N, top_k): the top_k largest of each
    token's logits over ALL experts and the softmax over those top_k
    logits — equal to a softmax over all experts renormalised over the
    chosen ones. Normalised over every choice, held here or not."""
    vals, experts = lax.top_k(router_logits, top_k)
    return jax.nn.softmax(vals, axis=-1), experts


def route_sigmoid_top_k(router_logits: jnp.ndarray, bias: jnp.ndarray,
                        top_k: int, scale: float
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(weights, experts), both (N, top_k): scores ``s = sigmoid(logits)``
    over ALL experts; the top_k largest of ``s + bias`` are chosen (the
    correction bias moves the choice and nothing else); their weights are
    ``s`` renormalised over the chosen, held here or not, times ``scale``.
    No gradient reaches ``bias``."""
    scores = jax.nn.sigmoid(router_logits)
    _, experts = lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    return picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) \
        * scale, experts


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _tokens_by_expert(x, order, inv, k: int):
    """x[order // k]: every (token, choice) assignment's token row, in the
    sorted order. `inv` is the inverse permutation of `order`, so the
    cotangent is a gather too (back to (token, choice) order, summed over
    a token's k choices) and never a scatter-add over repeated rows."""
    return jnp.take(x, order // k, axis=0)


def _tokens_by_expert_fwd(x, order, inv, k):
    return jnp.take(x, order // k, axis=0), inv


def _tokens_by_expert_bwd(k, inv, g):
    by_token = jnp.take(g, inv, axis=0).reshape(g.shape[0] // k, k, -1)
    return (jnp.sum(by_token.astype(jnp.float32), axis=1).astype(g.dtype),
            None, None)


_tokens_by_expert.defvjp(_tokens_by_expert_fwd, _tokens_by_expert_bwd)


@jax.custom_vjp
def _permute_rows(x, perm, inv):
    """x[perm] for a permutation `perm` with inverse `inv`: the cotangent
    is the gather g[inv], not a scatter."""
    return jnp.take(x, perm, axis=0)


_permute_rows.defvjp(
    lambda x, perm, inv: (jnp.take(x, perm, axis=0), inv),
    lambda inv, g: (jnp.take(g, inv, axis=0), None, None))


def _held_chunk(x, probs, experts, w_gate, w_up, w_down, first: int,
                count: int):
    """One chunk of tokens through the held experts (ReGLU, or relu
    squared where ``w_gate`` is None). Returns the chunk's output (n, D)
    and its assignments per held expert (count,)."""
    n, k = experts.shape
    local = experts - first
    held = (local >= 0) & (local < count)
    # not-held assignments sort past the last group: never computed
    key = jnp.where(held, local, count).reshape(-1).astype(jnp.int32)
    order = jnp.argsort(key)                      # stable: by expert
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * k, dtype=order.dtype))
    sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
    # rows past the last group belong to no held expert: the grouped
    # products neither compute them nor their cotangents (on the chip what
    # they leave there is whatever the buffer held), so nothing may flow
    # through them in either direction
    in_group = (jnp.arange(n * k) < jnp.sum(sizes))[:, None]
    xs = jnp.where(in_group, _tokens_by_expert(
        x.astype(w_up.dtype), order, inv, k), 0)
    dot = functools.partial(lax.ragged_dot, group_sizes=sizes,
                            preferred_element_type=jnp.float32)
    if w_gate is None:
        hidden = jnp.square(jax.nn.relu(dot(xs, w_up)))
    else:
        hidden = jax.nn.relu(dot(xs, w_gate)) * dot(xs, w_up)
    ys = dot(hidden.astype(w_down.dtype), w_down).astype(x.dtype)
    # back to (token, choice) order
    y = _permute_rows(jnp.where(in_group, ys, 0), inv, order)
    out = jnp.einsum("nkd,nk->nd", y.reshape(n, k, -1),
                     probs.astype(y.dtype))
    return out, sizes


def held_expert_ffn(x: jnp.ndarray, probs: jnp.ndarray,
                    experts: jnp.ndarray, w_gate: jnp.ndarray | None,
                    w_up: jnp.ndarray, w_down: jnp.ndarray,
                    held: tuple[int, int], chunk_tokens: int = 4096
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """sum over (token, choice) with the chosen expert held here of
    p * body_e(x), the expert body being the call's: gated ReGLU,
    ``(relu(x W_gate_e) * (x W_up_e)) W_down_e``, where ``w_gate`` is
    given; non-gated relu squared, ``relu(x W_up_e)^2 W_down_e``, where it
    is None.

    x (N, D); probs, experts (N, k) from a routing rule (``route_top_k``,
    ``route_sigmoid_top_k``); the weights of the ``count`` held experts
    stacked on axis 0, (count, D, H) (twice for the gated body) and
    (count, H, D); ``held = (first, count)``: this chip holds the global
    experts first .. first + count - 1. Returns the held part of the
    layer's output (N, D) and the assignments each held expert received
    (count,) int32 — nothing is dropped, whatever the imbalance.

    The assignments are sorted by held expert and taken through
    ``lax.ragged_dot`` (on a TPU XLA's grouped matrix product, whose row
    tiles past the last group are not computed). Tokens go through in
    chunks of ``chunk_tokens``, so the sorted copy is bounded by the
    chunk's worst case (every choice held) and not the batch's; a chunk
    is recomputed in the backward pass instead of stored."""
    first, count = int(held[0]), int(held[1])
    body = "relu squared" if w_gate is None else "ReGLU"
    for w in (w_up, w_down) if w_gate is None else (w_gate, w_up, w_down):
        if w.shape[0] != count:
            raise ValueError(f"{body} experts: {w.shape[0]} expert weights "
                             f"for held={held}")
    n = x.shape[0]
    chunk = min(int(chunk_tokens), n)
    if n % chunk:
        raise ValueError(f"{n} tokens do not divide into chunks of {chunk}")
    if jax.default_backend() == "tpu" and x.dtype == jnp.float32:
        # the device's default precision for a float32 product, stated:
        # bfloat16 operands, float32 sums (the grouped product would
        # otherwise take float32 operands in several passes); the weights
        # are cast once a call, not once a chunk
        w_gate, w_up, w_down = (w if w is None else w.astype(jnp.bfloat16)
                                for w in (w_gate, w_up, w_down))
    one = jax.checkpoint(
        lambda xc, pc, ec: _held_chunk(xc, pc, ec, w_gate, w_up, w_down,
                                       first, count))
    if chunk == n:
        return one(x, probs, experts)
    parts = lambda a: a.reshape(n // chunk, chunk, *a.shape[1:])
    out, sizes = lax.map(lambda c: one(*c),
                         (parts(x), parts(probs), parts(experts)))
    return out.reshape(n, -1), jnp.sum(sizes, axis=0)
