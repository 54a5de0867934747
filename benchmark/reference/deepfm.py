"""Plain reference of the DeepFM configuration (Guo et al., IJCAI 2017).

y = wide + FM + deep + b over one pooled vector per field:
  wide = sum_s w_s + dense @ wide_dense          (first order)
  FM   = 0.5 * sum_e ((sum_s v_s)^2 - sum_s v_s^2)   (second order)
  deep = MLP([features of every field, dense]) with ReLU hidden layers
This repo's stated departures from the paper: the deep part reads each
field's whole feature vector — with ``use_cvm`` the two click-value
columns log(show+1), log(clk+1)-log(show+1) before w and the embedding —
instead of the embedding alone; the dense values enter the deep part raw
and the wide part through their own weight vector.

Written from those equations; shares no code with ``paddlebox_tpu``. The
parameter tree has the names the system's model gives its own, because the
harness hands these weights to it.
"""

import jax
import jax.numpy as jnp


def _layer_sizes(cfg):
    a = cfg["model_args"]
    per_field = (3 if a.get("use_cvm", True) else 1) + a["emb_dim"]
    return (a["num_slots"] * per_field + a["dense_dim"], *a["hidden"], 1)


def init_params(key, cfg):
    sizes = _layer_sizes(cfg)
    keys = jax.random.split(key, len(sizes))
    mlp = [{"w": jax.random.normal(keys[i], (sizes[i], sizes[i + 1]),
                                   jnp.float32)
            * (2.0 / (sizes[i] + sizes[i + 1])) ** 0.5,
            "b": jnp.zeros((sizes[i + 1],), jnp.float32)}
           for i in range(len(sizes) - 1)]
    params = {"mlp": mlp, "bias": jnp.zeros((1,), jnp.float32)}
    if cfg["model_args"]["dense_dim"]:
        params["wide_dense"] = jax.random.normal(
            keys[-1], (cfg["model_args"]["dense_dim"],), jnp.float32) * 0.01
    return params


def logits(params, feats, dense, cfg):
    """feats (B, S, C): per field [cvm columns..., w, embedding]."""
    n_cvm = 2 if cfg["model_args"].get("use_cvm", True) else 0
    w = feats[:, :, n_cvm]
    v = feats[:, :, n_cvm + 1:]
    wide = jnp.sum(w, axis=1)
    sum_v = jnp.sum(v, axis=1)
    fm = 0.5 * jnp.sum(sum_v * sum_v - jnp.sum(v * v, axis=1), axis=1)
    x = feats.reshape(feats.shape[0], -1)
    if cfg["model_args"]["dense_dim"]:
        x = jnp.concatenate([x, dense], axis=1)
        wide = wide + dense @ params["wide_dense"]
    for i, layer in enumerate(params["mlp"]):
        x = x @ layer["w"] + layer["b"]
        if i < len(params["mlp"]) - 1:
            x = jnp.maximum(x, 0)
    return wide + fm + x[:, 0] + params["bias"][0]


def macs_per_example(cfg):
    """Multiply-adds of one forward pass: the deep MLP and the FM's
    sum-square trick (2 per field and embedding column)."""
    sizes = _layer_sizes(cfg)
    a = cfg["model_args"]
    return (sum(i * o for i, o in zip(sizes[:-1], sizes[1:]))
            + 2 * a["num_slots"] * a["emb_dim"])


def tower_sizes(cfg):
    """(dense parameters, activation floats per example)."""
    sizes = _layer_sizes(cfg)
    n_params = sum(i * o + o for i, o in zip(sizes[:-1], sizes[1:])) \
        + 1 + cfg["model_args"]["dense_dim"]
    return n_params, sum(sizes)
