"""Dense-parameter sync strategies.

The reference trains dense params in one of three modes per
``BoxPSWorkerParameter.sync_mode`` (trainer_desc.proto:100-108,
boxps_worker.cc:481-521):

- **allreduce per step** — grads pmean'd every step (DenseKStepALL with k=1,
  also the ``c_mixallgather`` fused-buffer op). Trainer default.
- **K-step parameter averaging** — each worker updates its own dense copy
  with purely local grads; every K steps the *parameters* are averaged
  (``SyncParam``: ncclAllReduce of the flat param tensor scaled by 1/n,
  boxps_worker.cc:481-521 — local-SGD semantics). On a 2D (node, dp) mesh a
  single pmean reproduces the reference's hierarchical
  reduce-scatter → inter-node SyncDense → all-gather decomposition.
- **async host dense table** — ``BoxPSAsynDenseTable`` (device_worker.h:586,
  boxps_worker.cc:37-296): workers pull the whole flat param vector and push
  flat grads through queues; a background host thread merges up to
  ``merge_limit`` pending grads and applies a hand-rolled Adam-like update
  (hard-coded betas 0.99/0.9999, cc:173-225) with optional per-parameter
  learning rates (``BoxWrapper::GetLRMap``).

This module provides the host-side async table and the flat-vector
utilities; the Trainer wires the modes into its jitted step.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable

import numpy as np

import jax

from paddlebox_tpu.monitor import context as mon_ctx
from jax.flatten_util import ravel_pytree


def flatten_dense(params) -> tuple[np.ndarray, Callable]:
    """Pytree → (flat float32 numpy vector, unravel fn) — the reference's
    single ``param_sync_`` tensor aliasing every dense param
    (boxps_worker.cc:453-472)."""
    flat, unravel = ravel_pytree(params)
    return np.asarray(flat, dtype=np.float32), unravel


# The flat transport pays for itself on towers of many small leaves (a CTR
# tower: 10^5..10^7 floats in ~30 leaves). Past this many floats the
# trainer keeps the trees: pack and unpack are copies of the whole state
# inside every step, and the public trees would live beside the flat
# vectors through a pass — at 5.6e8 parameters 6.7 GB twice.
FLAT_STATE_MAX_FLOATS = 1 << 26


def make_dense_packer(params_template, opt_template):
    """(pack, unpack, n_args): flatten the dense params and the f32
    leaves of the optimizer state into TWO flat vectors plus the non-f32
    aux leaves (optimizer step counts).

    Why: every jitted-step argument leaf costs host-side dispatch
    processing, and a DeepFM trainer carries ~30 dense-state leaves
    (the reference aliases all dense params into one param_sync_ tensor
    for the same reason, boxps_worker.cc:453-472; the saving is not
    measured on this code). pack/unpack are jit-traceable —
    inside the step they are free reshapes/slices fused by XLA — and
    exact: unpack(pack(x)) == x leaf for leaf.

    Returns None when a params leaf is not float32 (no flat fast path).
    """
    import jax.numpy as jnp

    p_leaves = jax.tree.leaves(params_template)
    if any(l.dtype != jnp.float32 for l in p_leaves):
        return None
    _, unravel_p = ravel_pytree(params_template)
    o_leaves, o_def = jax.tree.flatten(opt_template)
    is_f32 = [l.dtype == jnp.float32 for l in o_leaves]
    f32_shapes = [l.shape for l, m in zip(o_leaves, is_f32) if m]
    f32_sizes = [int(np.prod(s)) if s else 1 for s in f32_shapes]
    n_aux = sum(1 for m in is_f32 if not m)

    def pack(params, opt_state):
        pf = ravel_pytree(params)[0]
        leaves = jax.tree.leaves(opt_state)
        f32s = [jnp.ravel(l) for l, m in zip(leaves, is_f32) if m]
        of = (jnp.concatenate(f32s) if f32s
              else jnp.zeros((0,), jnp.float32))
        aux = tuple(l for l, m in zip(leaves, is_f32) if not m)
        return (pf, of, *aux)

    def unpack(state):
        pf, of = state[0], state[1]
        aux = state[2:]
        params = unravel_p(pf)
        out, off, ai, fi = [], 0, 0, 0
        for m in is_f32:
            if m:
                sz, sh = f32_sizes[fi], f32_shapes[fi]
                out.append(of[off:off + sz].reshape(sh))
                off += sz
                fi += 1
            else:
                out.append(aux[ai])
                ai += 1
        return params, jax.tree.unflatten(o_def, out)

    return pack, unpack, 2 + n_aux


class AsyncDenseTable:
    """Host-resident async dense parameter server (BoxPSAsynDenseTable).

    Staleness semantics match the reference: pulls return the latest applied
    params without waiting for in-flight grads; the updater thread merges up
    to ``merge_limit`` queued grads into one update step.
    """

    def __init__(self, flat_params: np.ndarray, lr: float = 1e-3,
                 betas: tuple[float, float] = (0.99, 0.9999),
                 eps: float = 1e-8, merge_limit: int = 4,
                 lr_map: list[tuple[slice, float]] | None = None):
        self._params = np.array(flat_params, dtype=np.float32)
        self._mom1 = np.zeros_like(self._params)
        self._mom2 = np.zeros_like(self._params)
        self.lr = lr
        self.betas = betas
        self.eps = eps
        self.merge_limit = max(1, merge_limit)
        # per-range LR overrides (the GetLRMap per-param-name map, flattened;
        # (slice, lr) pairs — slices aren't hashable before 3.12)
        self._lr_vec = np.full_like(self._params, lr)
        for sl, r in (lr_map or []):
            self._lr_vec[sl] = r
        self._queue: queue.Queue[np.ndarray | None] = queue.Queue()
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None
        self.steps_applied = 0
        self.grads_merged = 0

    # ---- worker side ----

    def pull(self) -> np.ndarray:
        with self._lock:
            return self._params.copy()

    def push(self, flat_grad: np.ndarray) -> None:
        self._queue.put(np.asarray(flat_grad, dtype=np.float32))

    # ---- updater thread (ThreadUpdate, boxps_worker.cc:173-225) ----

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = mon_ctx.spawn(self._run, name="async-dense-table")
        self._thread.start()

    def stop(self) -> None:
        if self._thread is None:
            return
        self._queue.put(None)
        self._thread.join()
        self._thread = None

    def flush(self) -> None:
        """Block until every grad pushed so far has been applied."""
        self._queue.join()

    def _run(self) -> None:
        while True:
            grad = self._queue.get()
            if grad is None:
                self._queue.task_done()
                return
            merged, n = grad, 1
            # merge whatever else is already waiting, up to the limit
            while n < self.merge_limit:
                try:
                    nxt = self._queue.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    self._apply(merged, n)
                    for _ in range(n + 1):  # n grads + the stop sentinel
                        self._queue.task_done()
                    return
                merged = merged + nxt
                n += 1
            self._apply(merged, n)
            for _ in range(n):
                self._queue.task_done()

    # ---- checkpoint plane (the dense half of SaveBase/LoadModel) ----

    def state_dict(self) -> dict[str, np.ndarray]:
        with self._lock:
            return {"params": self._params.copy(),
                    "mom1": self._mom1.copy(), "mom2": self._mom2.copy(),
                    "steps": np.asarray([self.steps_applied])}

    def load_state_dict(self, state: dict[str, np.ndarray]) -> None:
        with self._lock:
            self._params[:] = state["params"]
            self._mom1[:] = state["mom1"]
            self._mom2[:] = state["mom2"]
            self.steps_applied = int(np.asarray(state["steps"]).reshape(-1)[0])

    def _apply(self, grad_sum: np.ndarray, n: int) -> None:
        g = grad_sum / n
        b1, b2 = self.betas
        with self._lock:
            self._mom1 *= b1
            self._mom1 += (1 - b1) * g
            self._mom2 *= b2
            self._mom2 += (1 - b2) * g * g
            self._params -= self._lr_vec * self._mom1 / (
                np.sqrt(self._mom2) + self.eps)
            self.steps_applied += 1
            self.grads_merged += n


def stack_for_shards(params, n_shards: int):
    """Replicate a pytree along a new leading shard axis — per-device dense
    copies for K-step local training (the reference gives each GPU its own
    dense params between syncs, boxps_worker.cc:403-480)."""
    return jax.tree.map(
        lambda a: np.broadcast_to(np.asarray(a)[None],
                                  (n_shards,) + np.shape(a)).copy(), params)
