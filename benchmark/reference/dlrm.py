"""Plain reference of the DLRM configuration (Naumov et al. 2019; the
MLPerf Training reference, facebookresearch/dlrm on Criteo Terabyte).

  z0     = bottom MLP(dense), ReLU after every layer, width = emb dim
  Z      = [z0, v_1 .. v_S]            one pooled vector per field
  inter  = upper triangle (i < j) of Z Z^T      (dot interaction)
  logit  = top MLP([z0, inter, w_1 .. w_S]), ReLU on hidden layers
This repo's stated departure: the top MLP's input also carries the S
first-order weights w_s of the pulled rows (505 inputs, not 479).

Written from those equations; shares no code with ``paddlebox_tpu``. The
parameter tree has the names the system's model gives its own, because the
harness hands these weights to it.
"""

import jax
import jax.numpy as jnp
import numpy as np


def _sizes(cfg):
    a = cfg["model_args"]
    n_vec = a["num_slots"] + 1
    top_in = a["emb_dim"] + n_vec * (n_vec - 1) // 2 + a["num_slots"]
    return ((max(a["dense_dim"], 1), *a["bottom_hidden"], a["emb_dim"]),
            (top_in, *a["top_hidden"], 1))


def _mlp_init(key, sizes):
    keys = jax.random.split(key, len(sizes) - 1)
    return [{"w": jax.random.normal(keys[i], (sizes[i], sizes[i + 1]),
                                    jnp.float32)
             * (2.0 / (sizes[i] + sizes[i + 1])) ** 0.5,
             "b": jnp.zeros((sizes[i + 1],), jnp.float32)}
            for i in range(len(sizes) - 1)]


def init_params(key, cfg):
    bottom, top = _sizes(cfg)
    kb, kt = jax.random.split(key)
    return {"bottom": _mlp_init(kb, bottom), "top": _mlp_init(kt, top)}


def logits(params, feats, dense, cfg):
    """feats (B, S, C): per field [cvm columns..., w, embedding]."""
    n_cvm = 2 if cfg["model_args"].get("use_cvm", False) else 0
    w = feats[:, :, n_cvm]
    v = feats[:, :, n_cvm + 1:]
    z = dense if cfg["model_args"]["dense_dim"] \
        else jnp.zeros((feats.shape[0], 1), feats.dtype)
    for layer in params["bottom"]:
        z = jnp.maximum(z @ layer["w"] + layer["b"], 0)
    allv = jnp.concatenate([z[:, None, :], v], axis=1)
    gram = jnp.einsum("bse,bte->bst", allv, allv)
    iu, ju = np.triu_indices(allv.shape[1], k=1)
    x = jnp.concatenate([z, gram[:, iu, ju], w], axis=1)
    for i, layer in enumerate(params["top"]):
        x = x @ layer["w"] + layer["b"]
        if i < len(params["top"]) - 1:
            x = jnp.maximum(x, 0)
    return x[:, 0]


def macs_per_example(cfg):
    """Multiply-adds of one forward pass: both MLPs and the whole Gram
    matrix of the S + 1 vectors (the batched product computes all of it)."""
    a = cfg["model_args"]
    n_vec = a["num_slots"] + 1
    return (sum(i * o for sizes in _sizes(cfg)
                for i, o in zip(sizes[:-1], sizes[1:]))
            + n_vec * n_vec * a["emb_dim"])


def tower_sizes(cfg):
    """(dense parameters, activation floats per example)."""
    a = cfg["model_args"]
    n_vec = a["num_slots"] + 1
    n_params = sum(i * o + o for sizes in _sizes(cfg)
                   for i, o in zip(sizes[:-1], sizes[1:]))
    return n_params, sum(sum(s) for s in _sizes(cfg)) + n_vec * n_vec
