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
imbalance. Only the rows a held expert will multiply are moved: the sorted
copy of a chunk is bounded by what its held experts received, on a short
ladder of static row counts from the fair load up to the whole chunk
(``route_rungs``), and the chunk picks its rung on the device from its own
sizes. The last rung holds every assignment of the chunk, which is why
nothing is dropped at any imbalance: a load that no bounded rung holds
costs time, never a token. What the absent experts would add is another
chip's part and is not computed, approximated or stood in for; on a mesh
of one chip the layer runs without an exchange. Two routing rules
(``route_top_k``: a softmax over the chosen logits;
``route_sigmoid_top_k``: sigmoid scores chosen with a correction bias,
renormalised and scaled) and three expert bodies, chosen by name
(``EXPERT_BODIES``: gated ``reglu`` and ``swiglu``; non-gated ``relu2``,
relu squared) share the one layer. An expert that every chip computes
alike (a shared expert) is the model's to add: the sum of the shares
counts it once.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.monitor import device_scope
from paddlebox_tpu.ops.grouped_matmul import (group_tiles, grouped_matmul,
                                              row_tile)

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

# The share layer's device scopes (monitor/names.py): the whole layer is
# ``experts``; inside it the routing rule, the sort and everything that
# moves rows into and out of the sorted copy — the ladder's switch too —
# is ``route``, and the grouped products with the expert body between them
# ``experts`` again (the innermost scope wins). A backward pass written by
# hand here is traced under its call's scopes, and re-enters the ones its
# own calls open.

@device_scope("route")
def route_top_k(router_logits: jnp.ndarray, top_k: int
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(probs, experts), both (N, top_k): the top_k largest of each
    token's logits over ALL experts and the softmax over those top_k
    logits — equal to a softmax over all experts renormalised over the
    chosen ones. Normalised over every choice, held here or not."""
    vals, experts = lax.top_k(router_logits, top_k)
    return jax.nn.softmax(vals, axis=-1), experts


@device_scope("route")
def route_sigmoid_top_k(router_logits: jnp.ndarray, bias: jnp.ndarray,
                        top_k: int, scale: float, eps: float
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(weights, experts), both (N, top_k): scores ``s = sigmoid(logits)``
    over ALL experts; the top_k largest of ``s + bias`` are chosen (the
    correction bias moves the choice and nothing else); their weights are
    ``s`` over the chosen, held here or not, divided by their sum +
    ``eps`` (the family's own: Nemotron-H 1e-20, LFM2 1e-6), times
    ``scale``. No gradient reaches ``bias``."""
    scores = jax.nn.sigmoid(router_logits)
    _, experts = lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    return picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps) \
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


# the sorted copy's row bounds: this many (each is a copy of the chunk's
# program: 5 MB of device code a rung and layer at the published widths),
# in whole row tiles
_RUNGS = 4
_ROW_TILE = 128


def route_rungs(rows: int, count: int, n_experts: int) -> tuple[int, ...]:
    """The static row bounds a chunk of ``rows`` (token, choice)
    assignments may take for its sorted copy, ascending: ``_RUNGS`` bounds
    in equal ratios from the fair load of ``count`` held experts of
    ``n_experts`` — or from rows / 2^(_RUNGS - 1) where the fair load is
    less, so that adjacent bounds never differ by more than 2x — up to
    the whole chunk, each rounded up to whole row tiles."""
    least = max(rows * count / n_experts, rows / 2 ** (_RUNGS - 1))
    tiles = lambda r: -(-int(np.ceil(r)) // _ROW_TILE) * _ROW_TILE
    return tuple(sorted({
        min(rows, tiles(rows * (least / rows) ** (1 - i / (_RUNGS - 1))))
        for i in range(_RUNGS)}))


# the expert bodies by name: (what an error calls it, the gate's activation
# — None: no gate, ``relu(x W_up)^2 W_down``; else
# ``(act(x W_gate) * (x W_up)) W_down``)
EXPERT_BODIES = {"reglu": ("ReGLU", jax.nn.relu),
                 "swiglu": ("SwiGLU", jax.nn.silu),
                 "relu2": ("relu squared", None)}


@device_scope("experts")
def _expert_rows(xs, visits, body, w_gate, w_up, w_down, dtype):
    """Sorted rows through their experts' bodies: (rows, D) -> (rows, D)
    in `dtype`; rows past the last group are not computed. The products
    take their operands as they come and sum in float32; `visits` is the
    chunk's tile metadata (``group_tiles`` of its sizes)."""
    dot = functools.partial(grouped_matmul, meta=visits)
    gate = EXPERT_BODIES[body][1]
    if gate is None:
        hidden = jnp.square(jax.nn.relu(dot(xs, w_up)))
    else:
        hidden = gate(dot(xs, w_gate)) * dot(xs, w_up)
    return dot(hidden.astype(w_down.dtype), w_down).astype(dtype)


def _whole_chunk(body, x, probs, order, inv, sizes, visits, w_gate, w_up,
                 w_down):
    """The last rung: every assignment of the chunk in the sorted copy,
    whatever the imbalance."""
    n, k = probs.shape
    # rows past the last group belong to no held expert: the grouped
    # products neither compute them nor their cotangents (on the chip what
    # they leave there is whatever the buffer held), so nothing may flow
    # through them in either direction
    in_group = (jnp.arange(n * k) < jnp.sum(sizes))[:, None]
    xs = jnp.where(in_group, _tokens_by_expert(
        x.astype(w_up.dtype), order, inv, k), 0)
    ys = _expert_rows(xs, visits, body, w_gate, w_up, w_down, x.dtype)
    # back to (token, choice) order
    y = _permute_rows(jnp.where(in_group, ys, 0), inv, order)
    return jnp.einsum("nkd,nk->nd", y.reshape(n, k, -1),
                      probs.astype(y.dtype))


def _token_order(held, inv):
    """The held assignments in token order, (token, choice) ascending:
    (the sorted row of the j-th one, its token), both (n * k,), the token
    n past the last held one. A token's sorted rows are a run of at most
    k in this order."""
    n, k = held.shape
    flat = held.reshape(-1)
    at = jnp.where(flat, jnp.cumsum(flat) - 1, n * k)
    ids = jnp.full((n * k,), n * k, jnp.int32).at[at].set(
        jnp.arange(n * k, dtype=jnp.int32), mode="drop", unique_indices=True)
    return jnp.take(inv, ids, mode="clip"), ids // k


@jax.custom_vjp
def _rows_of_tokens(x, plan):
    """x (n, D) -> (C, D): each sorted row's token row, zero past the last
    group. The cotangent is `_sum_by_token`: gathers, never a scatter-add
    over repeated rows."""
    token = plan[0]
    live = (token < x.shape[0])[:, None]
    return jnp.where(live, jnp.take(x, token, axis=0, mode="clip"), 0)


@jax.custom_vjp
def _sum_by_token(rows, plan):
    """rows (C, D) -> (n, D): the sum of each token's sorted rows, float32
    sums; rows past the last group are never read (on the chip they hold
    whatever the buffer held). The cotangent is `_rows_of_tokens`."""
    _, perm, same, start, has = plan
    c = rows.shape[0]
    z = jnp.take(rows, perm, axis=0, mode="clip").astype(jnp.float32)
    after = jnp.pad(z, ((0, len(same)), (0, 0)))
    for s, eq in enumerate(same, 1):
        z = z + jnp.where(eq[:, None], after[s:s + c], 0)
    heads = jnp.take(z, start, axis=0, mode="clip")
    return jnp.where(has[:, None], heads, 0).astype(rows.dtype)


_rows_of_tokens.defvjp(
    lambda x, plan: (_rows_of_tokens(x, plan), plan),
    lambda plan, g: (_sum_by_token(g, plan), None))
_sum_by_token.defvjp(
    lambda rows, plan: (_sum_by_token(rows, plan), plan),
    lambda plan, g: (_rows_of_tokens(g, plan), None))


def _bounded_chunk(bound: int, body, x, probs, order, sizes, visits,
                   by_token, held_choices, w_gate, w_up, w_down):
    """A rung under the whole chunk: the held assignments are the first
    sum(sizes) <= bound entries of `order`, and only `order[:bound]` is
    brought into the sorted order; every array on the sorted side has
    `bound` rows. `by_token` is `_token_order`'s pair, `held_choices`
    (n,) how many sorted rows each token has."""
    n, k = probs.shape
    rows = order[:bound]
    # rows past the last group: as in the whole chunk, nothing flows
    # through them in either direction
    live = jnp.arange(bound) < jnp.sum(sizes)
    token = jnp.concatenate([by_token[1][:bound],
                             jnp.full((k - 1,), n, jnp.int32)])
    plan = (jnp.where(live, rows // k, n), by_token[0][:bound],
            tuple(token[s:s + bound] == token[:bound] for s in range(1, k)),
            jnp.cumsum(held_choices) - held_choices, held_choices > 0)
    xs = _rows_of_tokens(x.astype(w_up.dtype), plan)
    ys = _expert_rows(xs, visits, body, w_gate, w_up, w_down, x.dtype)
    weigh = probs.reshape(-1).at[rows].get(
        unique_indices=True, mode="promise_in_bounds").astype(ys.dtype)
    return _sum_by_token(jnp.where(live[:, None], ys, 0) * weigh[:, None],
                         plan)


@device_scope("route")
def _rung(bound: int, body: str, x, probs, routed, weights):
    order, inv, sizes, visits, by_token, held_choices = routed
    if bound == probs.shape[0] * probs.shape[1]:
        return _whole_chunk(body, x, probs, order, inv, sizes, visits,
                            *weights)
    return _bounded_chunk(bound, body, x, probs, order, sizes, visits,
                          by_token, held_choices, *weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ladder(rungs, body, rung, x, probs, routed, weights):
    """The chunk through rung `rung` of `rungs`, its experts of the body
    `body`; `routed` is its sort, the same for every rung
    (`_held_chunk`). Differentiated by hand
    so that the backward pass switches once and runs the taken rung's
    forward and backward inside its branch: nothing is kept but the
    arguments (the chunk is recomputed, not stored), and the rungs not
    taken leave no residuals to fill with zeros."""
    with device_scope("route"):
        return lax.switch(rung,
                          [functools.partial(_rung, b, body) for b in rungs],
                          x, probs, routed, weights)


def _ladder_fwd(rungs, body, rung, x, probs, routed, weights):
    return (_ladder(rungs, body, rung, x, probs, routed, weights),
            (rung, x, probs, routed, weights))


def _ladder_bwd(rungs, body, kept, g):
    rung, x, probs, routed, weights = kept

    def back(bound, x, probs, routed, weights, g):
        return jax.vjp(lambda x, p, w: _rung(bound, body, x, p, routed, w),
                       x, probs, weights)[1](g)

    with device_scope("route"):
        dx, dprobs, dweights = lax.switch(
            rung, [functools.partial(back, b) for b in rungs],
            x, probs, routed, weights, g)
    return None, dx, dprobs, None, dweights


_ladder.defvjp(_ladder_fwd, _ladder_bwd)


def _held_chunk(x, probs, experts, body: str, w_gate, w_up, w_down,
                first: int, count: int, rungs: tuple[int, ...], tile: int):
    """One chunk of tokens through the held experts of the body `body`
    (``EXPERT_BODIES``), the grouped products in row tiles of `tile`.
    Returns the chunk's output (n, D),
    its assignments per held expert (count,) and the rung it took as
    (rows of the sorted copy, 1 if that was the whole chunk)."""
    with device_scope("route"):
        local = experts - first
        held = (local >= 0) & (local < count)
        # not-held assignments sort past the last group: never computed
        key = jnp.where(held, local, count).reshape(-1).astype(jnp.int32)
        order = jnp.argsort(key)                  # stable: by expert
        sizes = jnp.bincount(key, length=count + 1)[:count].astype(
            jnp.int32)
        # the least rung that holds what the held experts received
        rung = jnp.sum(jnp.sum(sizes) > jnp.asarray(rungs[:-1], jnp.int32))
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        # the products' tile metadata, once for all of the chunk's calls:
        # whatever rung it takes, forward, recomputed and backward
        routed = (order, inv, sizes, group_tiles(sizes, rungs[-1], tile),
                  _token_order(held, inv),
                  jnp.sum(held, axis=1, dtype=jnp.int32))
    out = _ladder(rungs, body, rung, x, probs, routed,
                  (w_gate, w_up, w_down))
    with device_scope("route"):
        took = jnp.stack([jnp.asarray(rungs, jnp.int32)[rung],
                          (rung == len(rungs) - 1).astype(jnp.int32)])
    return out, sizes, took


@device_scope("experts")
def held_expert_ffn(x: jnp.ndarray, probs: jnp.ndarray,
                    experts: jnp.ndarray, w_gate: jnp.ndarray | None,
                    w_up: jnp.ndarray, w_down: jnp.ndarray,
                    held: tuple[int, int], n_experts: int,
                    chunk_tokens: int = 4096, *, body: str
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """sum over (token, choice) with the chosen expert held here of
    p * body_e(x), the expert body being the call's, by name
    (``EXPERT_BODIES``): gated ``reglu``,
    ``(relu(x W_gate_e) * (x W_up_e)) W_down_e``, and ``swiglu``, the
    same with ``silu`` for ``relu``; non-gated ``relu2``,
    ``relu(x W_up_e)^2 W_down_e``, which takes ``w_gate=None``.

    x (N, D); probs, experts (N, k) from a routing rule (``route_top_k``,
    ``route_sigmoid_top_k``) over ``n_experts`` experts; the weights of
    the ``count`` held experts stacked on axis 0, (count, D, H) (twice for
    the gated body) and (count, H, D); ``held = (first, count)``: this
    chip holds the global experts first .. first + count - 1. Returns the
    held part of the layer's output (N, D), the assignments each held
    expert received (count,) int32, and how the chunks were routed (2,)
    int32: the rows of the sorted copies taken, summed over the chunks,
    and the chunks that took the whole-chunk copy.

    The assignments are sorted by held expert and taken through
    ``ops/grouped_matmul.py``'s grouped matrix products (on a TPU the
    ``pbtpu_gmm`` / ``pbtpu_tgmm`` kernels, whose row tiles past the last
    group are not visited; their tile metadata is computed once a chunk,
    with the sort). Tokens go through in
    chunks of ``chunk_tokens``, and a chunk's sorted copy is bounded by
    what its held experts received: the held assignments sort first, and
    the chunk takes the least rung of ``route_rungs`` that holds them all
    — a few static row counts from the fair load up to the whole chunk,
    chosen on the device by the chunk's own sizes. The last rung is the
    whole chunk (every choice held), so nothing is dropped, whatever the
    imbalance: a load that no bounded rung holds costs time, never a
    token. A chunk is recomputed in the backward pass instead of stored."""
    first, count = int(held[0]), int(held[1])
    if body not in EXPERT_BODIES:
        raise ValueError(f"expert body {body!r}: one of "
                         f"{sorted(EXPERT_BODIES)}")
    called, gate = EXPERT_BODIES[body]
    if (gate is None) != (w_gate is None):
        raise ValueError(f"{called} experts take "
                         f"{'no w_gate' if gate is None else 'a w_gate'}")
    for w in (w_up, w_down) if w_gate is None else (w_gate, w_up, w_down):
        if w.shape[0] != count:
            raise ValueError(f"{called} experts: {w.shape[0]} expert weights "
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
    rows = chunk * experts.shape[1]
    rungs = route_rungs(rows, count, int(n_experts))
    # the products' row tile is the one a held expert's fair load holds
    tile = row_tile(rows * count // int(n_experts), count)
    one = lambda xc, pc, ec: _held_chunk(xc, pc, ec, body, w_gate, w_up,
                                         w_down, first, count, rungs, tile)
    if chunk == n:
        return one(x, probs, experts)
    parts = lambda a: a.reshape(n // chunk, chunk, *a.shape[1:])
    out, sizes, took = lax.map(lambda c: one(*c),
                               (parts(x), parts(probs), parts(experts)))
    return out.reshape(n, -1), jnp.sum(sizes, axis=0), jnp.sum(took, axis=0)
