"""A test's fixture: the pooled CTR tower of ``configs/deepfm_criteo.json``
with its loss stated here, through ``example_losses``, instead of by the
step's default. The equations are those of ``reference/deepfm.py``'s
docstring, written a second time and another way (slices and loops where
that file has an einsum and reshapes), so that the two agree only if the
seam hands a reference file what the default path computes from.
"""

import jax.numpy as jnp

from benchmark.reference.deepfm import (init_params,  # noqa: F401
                                        macs_per_example, tower_sizes)


def example_losses(params, pulled, mask, dense, labels, local_ids, cfg):
    """pulled (B, T, 3 + dim) = [show, clk, w, embedding] per token, one
    token a field here (T = S)."""
    present = mask[..., None].astype(pulled.dtype)
    show, clk = pulled[..., 0:1] * present, pulled[..., 1:2] * present
    w = (pulled[..., 2:3] * present)[..., 0]                 # (B, S)
    v = pulled[..., 3:] * present                            # (B, S, dim)
    log_show = jnp.log(show + 1)
    per_field = jnp.concatenate(
        [log_show, jnp.log(clk + 1) - log_show, w[..., None], v], axis=-1)
    x = jnp.concatenate([per_field[:, s] for s in range(per_field.shape[1])]
                        + [dense], axis=1)
    for i, layer in enumerate(params["mlp"]):
        x = x @ layer["w"] + layer["b"]
        if i < len(params["mlp"]) - 1:
            x = jnp.where(x > 0, x, 0)
    square_of_sum = jnp.square(sum(v[:, s] for s in range(v.shape[1])))
    sum_of_squares = sum(jnp.square(v[:, s]) for s in range(v.shape[1]))
    logit = (jnp.sum(w, axis=1) + dense @ params["wide_dense"]
             + 0.5 * jnp.sum(square_of_sum - sum_of_squares, axis=1)
             + x[:, 0] + params["bias"][0])
    # the sigmoid cross entropy, as -y log p - (1 - y) log(1 - p)
    return -(labels * jnp.log(1 / (1 + jnp.exp(-logit)))
             + (1 - labels) * jnp.log(1 / (1 + jnp.exp(logit))))
