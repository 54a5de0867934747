"""Bucketed AUC / bucket-error / MAE / RMSE — the BasicAucCalculator family.

Reference (box_wrapper.h:61-130, box_wrapper.cc:161-370,542-574): predictions
are histogrammed into ``table_size`` buckets (1M in production) split by
label into positive/negative tables, accumulated on GPU, NCCL-collected and
MPI-allreduced, then AUC is computed by the trapezoid sweep from the top
bucket down; MAE/RMSE/predicted-CTR come from abserr/sqrerr/pred running
sums; ``calculate_bucket_error`` (cc:542-574) measures calibration drift per
adaptive CTR span.

TPU design: the state is a small pytree of float32 arrays that lives on
device, is updated inside the jitted train step, and is reduced with a plain
``psum`` over the mesh (exact — the histogram is additive, simpler and
stronger than the reference's NCCL+MPI two-phase). ``auc_compute`` runs on
host in float64 like the reference's CPU sweep.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.monitor import device_scope, device_scopes

DEFAULT_BUCKETS = 1 << 20  # reference uses 1M buckets (_table_size)


def new_state(n_buckets: int = DEFAULT_BUCKETS) -> dict[str, jnp.ndarray]:
    return {
        "pos": jnp.zeros((n_buckets,), jnp.float32),
        "neg": jnp.zeros((n_buckets,), jnp.float32),
        "abserr": jnp.zeros((), jnp.float32),
        "sqrerr": jnp.zeros((), jnp.float32),
        "pred": jnp.zeros((), jnp.float32),
    }


AucState = dict[str, jnp.ndarray]


@device_scope("auc")
def auc_update(state: AucState, preds: jnp.ndarray, labels: jnp.ndarray,
               mask: jnp.ndarray | None = None,
               sample_scale: jnp.ndarray | None = None) -> AucState:
    """Accumulate a batch (jit-safe, fuses into the train step).

    mask: bool per example — the MaskMetricMsg / CmatchRankMetricMsg
    filtering hook (box_wrapper.h:281-361). sample_scale: per-example weight
    (sample-scale metric variant).
    """
    n_buckets = state["pos"].shape[0]
    p = preds.reshape(-1).astype(jnp.float32)
    y = labels.reshape(-1).astype(jnp.float32)
    w = jnp.ones_like(p)
    if sample_scale is not None:
        w = w * sample_scale.reshape(-1).astype(jnp.float32)
    if mask is not None:
        w = w * mask.reshape(-1).astype(jnp.float32)
    bucket = jnp.clip((p * n_buckets).astype(jnp.int32), 0, n_buckets - 1)
    pos = state["pos"].at[bucket].add(y * w)
    neg = state["neg"].at[bucket].add((1.0 - y) * w)
    return {
        "pos": pos,
        "neg": neg,
        "abserr": state["abserr"] + jnp.sum(w * jnp.abs(p - y)),
        "sqrerr": state["sqrerr"] + jnp.sum(w * (p - y) ** 2),
        "pred": state["pred"] + jnp.sum(w * p),
    }


class AucAccumulator:
    """Two-tier accumulator: device float32 state updated in-jit, drained
    into a host float64 sink every `drain_every` batches.

    float32 histogram adds stop counting once a bucket crosses 2^24; the
    reference avoids this by accumulating in double on CPU
    (box_wrapper.cc:321). On TPU x64 is off, so instead the device state is
    bounded (drain_every × batch ≪ 2^24 per bucket) and exactness lives in
    the float64 host sink.
    """

    def __init__(self, n_buckets: int = DEFAULT_BUCKETS,
                 drain_every: int = 256):
        self.n_buckets = n_buckets
        self.drain_every = drain_every
        self.host = {k: np.zeros_like(np.asarray(v), dtype=np.float64)
                     for k, v in new_state(n_buckets).items()}
        self.dev: AucState = new_state(n_buckets)
        self._updates = 0

    def update(self, fn, *args) -> None:
        """dev_state = fn(dev_state, *args); fn is typically a jitted
        auc_update partial. Non-blocking except on drain boundaries."""
        self.dev = device_scopes.run(fn, self.dev, *args)
        self._updates += 1
        if self._updates >= self.drain_every:
            self.drain()

    def drain(self) -> None:
        for k, v in self.dev.items():
            self.host[k] += np.asarray(v, dtype=np.float64)
        self.dev = new_state(self.n_buckets)
        self._updates = 0

    def compute(self, **kw) -> dict[str, float]:
        self.drain()
        return auc_compute(self.host, **kw)

    def compute_global(self, collectives, **kw) -> dict[str, float]:
        """Exact multi-host AUC: all_reduce the histogram tables over the
        control plane first (the MPICluster::allreduce_sum path,
        box_wrapper.cc:331-356; fleet_util.get_global_auc semantics)."""
        self.drain()
        tot = {k: np.asarray(collectives.all_reduce(
                   np.atleast_1d(np.asarray(v, np.float64)), op="sum"))
               for k, v in self.host.items()}
        tot = {k: v if self.host[k].ndim else v.reshape(())
               for k, v in tot.items()}
        return auc_compute(tot, **kw)


def psum_state(state: AucState, axis_name) -> AucState:
    """Exact global reduction over mesh axes (replaces collect_data_nccl +
    MPICluster::allreduce_sum, box_wrapper.cc:230-332)."""
    return jax.tree.map(lambda x: jax.lax.psum(x, axis_name), state)


def merge_states(states: list[AucState]) -> AucState:
    """Host-side merge (e.g. across processes via collected numpy states)."""
    out = jax.tree.map(lambda *xs: sum(np.asarray(x, dtype=np.float64)
                                       for x in xs), *states)
    return out


def auc_compute(state: AucState,
                max_span: float = 0.01,
                relative_error_bound: float = 0.05) -> dict[str, float]:
    """Host-side sweep (float64), mirroring compute() +
    calculate_bucket_error() exactly (box_wrapper.cc:321-370, 542-574)."""
    pos = np.asarray(state["pos"], dtype=np.float64)
    neg = np.asarray(state["neg"], dtype=np.float64)
    n = len(pos)
    # trapezoid sweep from the top bucket down (cc:339-346)
    tp_cum = np.cumsum(pos[::-1])
    fp_cum = np.cumsum(neg[::-1])
    tp_prev = np.concatenate([[0.0], tp_cum[:-1]])
    fp_prev = np.concatenate([[0.0], fp_cum[:-1]])
    area = np.sum((fp_cum - fp_prev) * (tp_prev + tp_cum) / 2.0)
    fp, tp = float(fp_cum[-1]), float(tp_cum[-1])
    if fp < 1e-3 or tp < 1e-3:
        auc = -0.5  # all nonclick or all click (cc:348-350)
    else:
        auc = float(area / (fp * tp))
    total = fp + tp
    abserr = float(np.asarray(state["abserr"], dtype=np.float64))
    sqrerr = float(np.asarray(state["sqrerr"], dtype=np.float64))
    pred = float(np.asarray(state["pred"], dtype=np.float64))
    out: dict[str, float] = {
        "auc": auc,
        "mae": abserr / total if total else 0.0,
        "rmse": float(np.sqrt(sqrerr / total)) if total else 0.0,
        "predicted_ctr": pred / total if total else 0.0,
        "actual_ctr": tp / total if total else 0.0,
        "size": total,
    }
    out["bucket_error"] = _bucket_error(pos, neg, n, max_span,
                                        relative_error_bound)
    return out


def _bucket_error(pos: np.ndarray, neg: np.ndarray, n: int,
                  max_span: float, rel_bound: float) -> float:
    """Faithful port of the adaptive-span calibration sweep (cc:542-574).

    The reference iterates ALL buckets; empty buckets contribute nothing to
    the sums but can still become the reset anchor (``last_ctr``) when the
    span overflows inside an empty run, which changes where later resets
    land. Iterating 1M buckets per call in Python is too slow, so this walks
    only nonzero buckets and advances the anchor through each empty run
    arithmetically — bit-for-bit the same anchor the full loop would reach
    (each anchor hop advances > max_span, so total hops <= 1/max_span + nnz).
    """
    last_ctr = -1.0
    impression_sum = 0.0
    ctr_sum = 0.0
    click_sum = 0.0
    error_sum = 0.0
    error_count = 0.0
    nz = np.nonzero((pos + neg) > 0)[0]
    prev = -1  # index of the previously processed (nonzero) bucket
    for i in nz:
        # advance the anchor through empty buckets (prev, i) exactly as the
        # full loop would: reset at each bucket whose ctr exceeds the
        # current anchor by more than max_span
        j = prev + 1
        while j < i:
            cj = float(j) / n
            if abs(cj - last_ctr) > max_span:
                last_ctr = cj
                impression_sum = ctr_sum = click_sum = 0.0
                # next possible reset is the first bucket > n*(last+span)
                nxt = int(np.floor(n * (last_ctr + max_span))) + 1
                j = max(j + 1, nxt)
            else:
                nxt = int(np.floor(n * (last_ctr + max_span))) + 1
                j = max(j + 1, nxt)
        click = pos[i]
        show = pos[i] + neg[i]
        ctr = float(i) / n
        if abs(ctr - last_ctr) > max_span:
            last_ctr = ctr
            impression_sum = ctr_sum = click_sum = 0.0
        impression_sum += show
        ctr_sum += ctr * show
        click_sum += click
        adjust_ctr = ctr_sum / impression_sum
        if adjust_ctr <= 0 or adjust_ctr >= 1:
            prev = i
            continue
        relative_error = np.sqrt((1 - adjust_ctr) /
                                 (adjust_ctr * impression_sum))
        if relative_error < rel_bound:
            actual_ctr = click_sum / impression_sum
            error_sum += abs(actual_ctr / adjust_ctr - 1) * impression_sum
            error_count += impression_sum
            last_ctr = -1.0
        prev = i
    return error_sum / error_count if error_count > 0 else 0.0
