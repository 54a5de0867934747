"""The plain reference of one training step, followed for a few steps.

Shared by the model references (one file a model: its parameters and
either its logits or its own loss; ``benchmark/README.md`` has the
contract). Written from the configuration's stated equations in
straightforward ``jax.numpy``; imports nothing of ``paddlebox_tpu`` and
takes nothing the program made: the embedding rows come from the
configuration's stated init rule, the dense weights from the model
reference's ``init_params``, the batches from the generator.

One step, as the configuration states it:

  pull     every token reads [show, clk, w, embedding] of its key's row
  losses   one loss an example, the model's: its reference file's
           ``example_losses`` over the pulled tokens in the order the
           generator wrote them, or, where the file has none, the default:
    pool     tokens of one field are summed (absent tokens add nothing);
             with ``use_cvm`` the summed show/clk become log(show+1) and
             log(clk+1)-log(show+1), else they are dropped
    model    logits(params, pooled features, dense)  (the model reference)
    loss     the sigmoid cross entropy of that one logit and the label
  mean     over the batch, whole, or accumulated with its gradients over
           blocks of ``reference_block_examples`` examples
  dense    Adam(lr, b1 0.9, b2 0.999, eps 1e-8) on the loss's gradient
  push     per row: g = sum of its tokens' gradients w.r.t. (w, embedding)
           — show and clk are counters and get no gradient — show += its
           tokens, clk += its tokens' labels, then the in-table adagrad
             g2w += g_w^2            g2x += mean_e(g_x^2)
             w -= lr sqrt(i/(i+g2w)) g_w    x -= lr sqrt(i/(i+g2x)) g_x
           with i = ``initial_g2sum``.

Row layout: [show, clk, w, embedding(dim), g2w, g2x].

``dtype`` float32 computes every product at ``highest`` precision.
``bfloat16`` is the control: the same steps with every value held and
every operation done in bfloat16. ``fault`` plants what a broken program
would do: ``half_batch`` (the loss and so every gradient taken over the
first half of the batch, the mean over that half) or ``state_unchanged``
(the step returns its state as it got it).
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import datagen

B1, B2, EPS = 0.9, 0.999, 1e-8


def model_reference(cfg: dict):
    """The model's reference module: the one the configuration names under
    ``reference``, else ``benchmark.reference.<model>``."""
    return importlib.import_module(
        cfg.get("reference", f"benchmark.reference.{cfg['model']}"))


def initial_params(cfg: dict, seed: int):
    """The run's dense weights: made on the device in one jitted call from
    the seed, handed to the program and to the reference alike."""
    model = model_reference(cfg)
    return jax.device_get(jax.jit(lambda k: model.init_params(k, cfg))(
        jax.random.PRNGKey(int(seed) % (1 << 31))))


def init_rows(keys: np.ndarray, emb: dict, seed: int) -> np.ndarray:
    """A new key's row by the configuration's rule: counters, w and
    optimizer state 0; embedding[j] uniform in [-r, r) from a 64-bit mix
    of (key, j + seed)."""
    dim = int(emb["dim"])
    rows = np.zeros((len(keys), dim + 5), np.float32)
    k = keys.astype(np.uint64)[:, None]
    j = np.arange(dim, dtype=np.uint64)[None, :]
    with np.errstate(over="ignore"):
        z = (k * np.uint64(0x9E3779B97F4A7C15)
             + (j + np.uint64(seed)) * np.uint64(0xBF58476D1CE4E5B9))
        z ^= z >> np.uint64(30)
        z *= np.uint64(0x94D049BB133111EB)
        z ^= z >> np.uint64(27)
    u = (z >> np.uint64(11)).astype(np.float64) / float(1 << 53)
    rows[:, 3:3 + dim] = ((2.0 * u - 1.0)
                          * float(emb.get("initial_range", 0.02))
                          ).astype(np.float32)
    return rows


def _bce(logit, label):
    return jnp.maximum(logit, 0) - logit * label \
        + jnp.log1p(jnp.exp(-jnp.abs(logit)))


def pooled_losses(cfg: dict, slot_of_token: np.ndarray, logits):
    """The default ``example_losses``, for a model whose reference file
    gives only ``logits`` over one pooled vector per field."""
    use_cvm = bool(cfg["model_args"].get("use_cvm", True))
    n_slots = int(cfg["model_args"]["num_slots"])
    pool = np.zeros((len(slot_of_token), n_slots), np.float32)
    pool[np.arange(len(slot_of_token)), slot_of_token] = 1.0

    def example_losses(params, pulled, mask, dense, labels, local_ids, cfg):
        dtype = pulled.dtype
        pulled = pulled * mask[..., None].astype(dtype)
        pooled = jnp.einsum("btp,ts->bsp", pulled, jnp.asarray(pool, dtype))
        if use_cvm:
            log_show = jnp.log(pooled[..., 0:1] + 1)
            log_ctr = jnp.log(pooled[..., 1:2] + 1) - log_show
            feats = jnp.concatenate([log_show, log_ctr, pooled[..., 2:]],
                                    axis=-1)
        else:
            feats = pooled[..., 2:]
        return _bce(logits(params, feats, dense, cfg), labels)

    return example_losses


def make_step(cfg: dict, slot_of_token: np.ndarray, dtype=jnp.float32,
              fault: str | None = None):
    """A jitted step: (params, m, v, count, rows, idx, mask, dense, labels,
    local_ids) -> (params, m, v, count, rows, loss). `rows` is (K + 1, W)
    with row 0 all zero for absent tokens; `idx` (B, T) indexes it;
    `local_ids` (B, T) are the tokens' indices within their fields."""
    model = model_reference(cfg)
    emb, tr = cfg["embedding"], cfg["trainer"]
    dim = int(emb["dim"])
    example_losses = getattr(model, "example_losses", None) \
        or pooled_losses(cfg, slot_of_token, model.logits)
    block = cfg.get("reference_block_examples")
    lr_d, lr_s = float(tr["dense_lr"]), float(emb["learning_rate"])
    g2_0 = float(emb.get("initial_g2sum", 3.0))
    precision = "highest" if dtype == jnp.float32 else "default"

    def losses(params, trained, counters, mask, dense, labels, local_ids):
        # trained (B, T, 1 + dim): w and embedding; counters (B, T, 2)
        pulled = jnp.concatenate([counters, trained], axis=-1)
        return example_losses(params, pulled, mask, dense, labels, local_ids,
                              cfg)

    def whole_batch_loss(*args):
        per_example = losses(*args)
        if fault == "half_batch":
            half = per_example.shape[0] // 2
            return jnp.mean(per_example[:half])
        return jnp.mean(per_example)

    def loss_and_grads(p, pulled, *batch):
        """The batch's mean loss and its gradients w.r.t. the dense
        parameters and every token's (w, embedding)."""
        trained, counters = pulled[..., 2:3 + dim], pulled[..., 0:2]
        if not block:
            return jax.value_and_grad(whole_batch_loss, argnums=(0, 1))(
                p, trained, counters, *batch)
        n = trained.shape[0]
        if n % block:
            raise ValueError(f"reference_block_examples {block} does not "
                             f"divide the batch of {n}")
        counted = n // 2 if fault == "half_batch" else n

        def block_loss(p, trained, counters, first, *batch):
            per_example = losses(p, trained, counters, *batch)
            keep = first + jnp.arange(block) < counted
            return jnp.sum(jnp.where(keep, per_example, 0)) / counted

        def one_block(carry, xs):
            loss, (gp, gt) = jax.value_and_grad(block_loss, argnums=(0, 1))(
                p, *xs)
            return (carry[0] + loss, jax.tree.map(jnp.add, carry[1], gp)), gt

        blocks = lambda a: a.reshape(n // block, block, *a.shape[1:])
        (loss, gp), gt = jax.lax.scan(
            one_block, (jnp.zeros((), dtype), jax.tree.map(jnp.zeros_like, p)),
            (blocks(trained), blocks(counters), jnp.arange(0, n, block),
             *map(blocks, batch)))
        return loss, (gp, gt.reshape(trained.shape))

    def step(params, m, v, count, rows, idx, mask, dense, labels, local_ids):
        with jax.default_matmul_precision(precision):
            cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)
            p, r = cast(params), rows.astype(dtype)
            loss, (gp, gt) = loss_and_grads(
                p, r[idx], mask, dense.astype(dtype), labels.astype(dtype),
                local_ids)
            if fault == "state_unchanged":
                return params, m, v, count, rows, loss.astype(jnp.float32)
            # dense: Adam
            count = count + 1
            m = jax.tree.map(lambda a, g: B1 * a.astype(dtype)
                             + (1 - B1) * g, m, gp)
            v = jax.tree.map(lambda a, g: B2 * a.astype(dtype)
                             + (1 - B2) * g * g, v, gp)
            c1 = (1 - B1 ** count).astype(dtype)
            c2 = (1 - B2 ** count).astype(dtype)
            p = jax.tree.map(
                lambda a, mm, vv: a - lr_d * (mm / c1)
                / (jnp.sqrt(vv / c2) + EPS), p, m, v)
            # sparse: merge per row, then the in-table adagrad
            flat = idx.reshape(-1)
            msk = mask.reshape(-1).astype(dtype)
            g = jnp.zeros((r.shape[0], 1 + dim), dtype).at[flat].add(
                gt.reshape(-1, 1 + dim) * msk[:, None])
            show = jnp.zeros((r.shape[0],), dtype).at[flat].add(msk)
            clk = jnp.zeros((r.shape[0],), dtype).at[flat].add(
                msk * jnp.repeat(labels.astype(dtype), idx.shape[1]))
            g_w, g_x = g[:, 0], g[:, 1:]
            g2w = r[:, 3 + dim] + g_w * g_w
            g2x = r[:, 4 + dim] + jnp.mean(g_x * g_x, axis=1)
            new = jnp.concatenate([
                (r[:, 0] + show)[:, None], (r[:, 1] + clk)[:, None],
                (r[:, 2] - lr_s * jnp.sqrt(g2_0 / (g2_0 + g2w)) * g_w
                 )[:, None],
                r[:, 3:3 + dim]
                - (lr_s * jnp.sqrt(g2_0 / (g2_0 + g2x)))[:, None] * g_x,
                g2w[:, None], g2x[:, None]], axis=1)
            new = new.at[0].set(0)           # the absent tokens' row
            f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
            return (f32(p), f32(m), f32(v), count, new.astype(jnp.float32),
                    loss.astype(jnp.float32))

    # a step in blocks is a tower too large to hold twice: its state's
    # buffers are the step's to reuse
    return jax.jit(step, donate_argnums=(0, 1, 2, 4) if block else ())


def follow(cfg: dict, params0, batches: list[dict], hotness: np.ndarray,
           seed: int, dtype=jnp.float32, fault: str | None = None) -> dict:
    """Run the reference over `batches` (each: ids (B, T) int64 keys with
    0 where absent, mask, dense, labels) from the seed's initial state.
    Returns what the comparison reads: ``keys`` (sorted, the rows' order),
    ``losses``, and after the first and the last step the dense state
    (``params``, Adam's ``m``) and the rows."""
    keys = np.unique(np.concatenate([b["ids"][b["mask"]] for b in batches]))
    # one row per token would hold every key: the same shapes for every
    # seed, so the step compiles once (row 0 and the tail stay zero)
    n_rows = 1 + sum(b["ids"].size for b in batches)
    rows = np.zeros((n_rows, int(cfg["embedding"]["dim"]) + 5), np.float32)
    rows[1:1 + len(keys)] = init_rows(keys, cfg["embedding"], seed)
    slot_of_token = np.repeat(np.arange(len(hotness)), hotness)
    step = make_step(cfg, slot_of_token, dtype, fault)
    # copies of its own: a step in blocks reuses its state's buffers
    params = jax.tree.map(jnp.array, params0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.float32)
    rows = jnp.array(rows)
    host = lambda t: jax.tree.map(np.array, jax.device_get(t))
    out = {"keys": keys, "rows0": np.asarray(rows[1:1 + len(keys)]),
           "params0": host(params), "losses": [], "after": {}}
    for k, b in enumerate(batches, start=1):
        idx = np.where(b["mask"], np.searchsorted(keys, b["ids"]) + 1, 0)
        params, m, v, count, rows, loss = step(
            params, m, v, count, rows, jnp.asarray(idx, jnp.int32),
            jnp.asarray(b["mask"]), jnp.asarray(b["dense"]),
            jnp.asarray(b["labels"], jnp.float32),
            jnp.asarray(datagen.local_index(b["ids"]), jnp.int32))
        out["losses"].append(float(loss))
        if k in (1, len(batches)):
            out["after"][k] = {"params": host(params), "m": host(m),
                               "rows": np.asarray(rows[1:1 + len(keys)])}
    return out
