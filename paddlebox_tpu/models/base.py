"""Model interface.

A CTR model consumes:
- ``pulled``  (B, T, P) raw pull values for all sparse tokens (P = show, clk,
  w, embedx — see embedding/config.py), with ``mask`` (B, T) and the static
  SparseLayout, and
- ``dense``   (B, F) float slot columns (label excluded),

and produces logits (B,). Models own their dense parameters; the embedding
table is the trainer's (it lives in the sharded working set). This mirrors
the reference's split: pull_box_sparse feeds slot tensors into a
fluid-layers graph while the table lives in BoxPS (SURVEY.md §3.2).
"""

from __future__ import annotations

from typing import Any, Protocol

import jax.numpy as jnp
import numpy as np


class CTRModel(Protocol):
    name: str

    def init(self, key) -> Any: ...

    def apply(self, params: Any, pulled: jnp.ndarray, mask: jnp.ndarray,
              dense: jnp.ndarray, segment_ids: np.ndarray,
              num_slots: int) -> jnp.ndarray: ...


# ---------------------------------------------------------------------------
# what a model declares beside ``apply`` — read by the trainer's step builder
# ---------------------------------------------------------------------------
#
# ``loss(params, pulled, mask, dense, labels, *extras) -> (loss, preds)``
#     The model's own training loss over one local batch: a scalar (the mean
#     over the batch's examples) and its prediction per example, (B,), or
#     None where it makes none. ``pulled (B, T, P)`` holds every token's
#     ``[show, clk, w, embedx]``; for a schema with a sequence slot
#     (``Slot.sequence``) that is every position in file order, unpooled, on
#     every pull engine. ``extras`` are what the model's host stage
#     ``batch_extras(pb, n_shards)`` made of the batch — for ordered tokens
#     their ids within the vocabulary (``local_ids``), the targets of a
#     next-token loss. With ``stat_names`` the loss returns a third value,
#     one float per name.
# ``predicts`` (default True)
#     False: the model declares no prediction; the AUC accumulator and the
#     metric registry get nothing.
# ``stat_names`` (default ())
#     Names registered in ``monitor.names.MODEL_STAT_NAMES``.
#
# A model that declares no ``loss`` keeps the CTR default: the sigmoid cross
# entropy of ``apply``'s one logit against the label, the prediction its
# sigmoid — the same expressions in the same order as before the loss was
# the model's to declare.

def predicts(model) -> bool:
    return bool(getattr(model, "predicts", True))


def stat_names(model) -> tuple:
    names = tuple(getattr(model, "stat_names", ()))
    from paddlebox_tpu.monitor import names as registry
    unknown = [n for n in names if n not in registry.MODEL_STAT_NAMES]
    if unknown:
        raise ValueError(f"model {getattr(model, 'name', model)!r} declares "
                         f"statistics {unknown} that monitor/names.py "
                         f"(MODEL_STAT_NAMES) does not list")
    return names


def declared_loss(model, segment_ids, num_slots):
    """fn(params, pulled, mask, dense, labels, *extras) -> (loss, (preds,
    stats)): the model's declared loss, or the CTR default. ``preds`` is
    always (B,) (zeros where the model declares none, so the step's
    signature is one); ``stats`` is () or a one-vector tuple."""
    import jax
    import optax

    own = getattr(model, "loss", None)
    n_stats = len(stat_names(model))

    def default(params, pulled, mask, dense, labels, *extras):
        logits = model.apply(params, pulled, mask, dense, segment_ids,
                             num_slots, *extras)
        loss = jnp.mean(optax.sigmoid_binary_cross_entropy(logits, labels))
        return loss, (jax.nn.sigmoid(logits), ())

    def declared(params, pulled, mask, dense, labels, *extras):
        loss, preds, *stats = own(params, pulled, mask, dense, labels,
                                  *extras)
        if preds is None:
            preds = jnp.zeros(labels.shape, jnp.float32)
        if len(stats) != (1 if n_stats else 0):
            raise ValueError(
                f"model {model.name!r}: loss returned {len(stats)} statistics "
                f"vectors for {n_stats} declared stat_names")
        return loss, (preds, tuple(jnp.asarray(s, jnp.float32).reshape(
            n_stats) for s in stats))

    return default if own is None else declared


def _extreme(name: str):
    """A gauge's reduction, by its name's ending: ``*_max`` the largest,
    ``*_min`` the smallest; None for a counter (a sum)."""
    return {"_max": "max", "_min": "min"}.get(name[-4:])


def reduce_stats(names: tuple, stats: jnp.ndarray, axes) -> jnp.ndarray:
    """One step's statistics over the mesh: ``*_max`` by max, ``*_min``
    by min, else sums."""
    from jax import lax
    kinds = [_extreme(n) for n in names]
    out = lax.psum(stats, axes)
    for kind, reduce in (("max", lax.pmax), ("min", lax.pmin)):
        if kind in kinds:
            out = jnp.where(np.asarray([k == kind for k in kinds]),
                            reduce(stats, axes), out)
    return out


def publish_stats(model, per_step: np.ndarray) -> dict:
    """A pass's statistics (steps, n) into the stat registry: counters for
    the sums, gauges for the ``*_max`` and ``*_min`` names (the pass's
    extreme step). Returns {name: value}."""
    from paddlebox_tpu import monitor
    out = {}
    for j, name in enumerate(stat_names(model)):
        col = per_step[:, j].astype(np.float64)
        extreme = _extreme(name)
        if extreme:
            out[name] = float(getattr(col, extreme)())
            monitor.gauge_set(name, out[name])
        else:
            out[name] = float(col.sum())
            monitor.counter_add(name, int(round(out[name])))
    return out
