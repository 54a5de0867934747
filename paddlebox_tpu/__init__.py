"""paddlebox_tpu — a TPU-native sparse-CTR training framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of PaddleBox
(Baidu's GPU sparse-CTR fork of PaddlePaddle 1.8, see SURVEY.md):

- pass-based training with an HBM-sharded embedding table (the role of the
  closed-source BoxPS GPU parameter server in the reference),
- slot-formatted data ingestion with multi-threaded parse + global shuffle,
- dense-parameter synchronization lowered to mesh collectives (psum /
  reduce_scatter / all_gather over ICI+DCN mesh axes),
- in-training AUC / bucket-error metrics with exact global reduction,
- day/pass base+delta checkpointing for online serving.

Layer map (vs. reference SURVEY.md §1): the Program/Scope/Executor +
operator-registry machinery collapses into jitted functions over a
`jax.sharding.Mesh`; the CUDA glue kernels become XLA-fused jnp code and
Pallas kernels; libbox_ps becomes `paddlebox_tpu.embedding`.
"""

__version__ = "0.1.0"

import os as _os

# same truthiness predicate as Flags.from_env — PBTPU_NO_JAX=false/no/0
# must NOT enable the opt-out
if _os.environ.get("PBTPU_NO_JAX", "").lower() in ("1", "true", "yes"):
    # Pure-host tooling opt-out (the pblint CLI gate sets this): skip the
    # accelerator stack entirely so `python -m paddlebox_tpu.analysis.lint`
    # costs milliseconds, not a jax import. The opt-out must fail LOUDLY
    # if training code runs under it, so jax imports are blocked outright
    # and the error names the flag.
    import sys as _sys

    class _JaxBlockedUnderNoJax:
        def find_spec(self, name, path=None, target=None):
            if name.partition(".")[0] in ("jax", "jaxlib"):
                raise ModuleNotFoundError(
                    f"{name!r} blocked: paddlebox_tpu was imported with "
                    "PBTPU_NO_JAX=1 (pure-host tooling mode — lint/"
                    "analysis only); unset PBTPU_NO_JAX to use the "
                    "accelerator stack", name=name)
            return None

    _sys.meta_path.insert(0, _JaxBlockedUnderNoJax())
from paddlebox_tpu import config as config  # noqa: F401
from paddlebox_tpu.config import flags as flags  # noqa: F401
