"""Not a metric: what the readers of the ordered-token cell's kernel
metrics share — the cell's configuration as it is run, and its reference's
counts of each kernel's operations (``reference/smallthinker.py``)."""

import json
import os

from benchmark.reference import smallthinker as reference

_HERE = os.path.dirname(os.path.abspath(__file__))


def config() -> dict:
    with open(os.path.join(_HERE, "..", "configs",
                           "smallthinker_21b_ep4.json")) as f:
        return json.load(f)


def step_flops(macs_per_example: float, cfg: dict) -> float:
    """Forward and backward: 6 operations a multiply-add, a batch."""
    return 6.0 * cfg["trainer"]["global_batch_size"] * macs_per_example


def attention_flops() -> float:
    cfg = config()
    return step_flops(sum(reference.attention_macs(cfg, kind)
                          for kind in cfg["model_args"]["layer_kinds"]), cfg)


def expert_flops() -> float:
    cfg = config()
    return step_flops(len(cfg["model_args"]["layer_kinds"])
                      * reference.expert_gmm_macs(cfg), cfg)


def route_rows() -> int:
    """Rows of one chunk's sorted (token, choice) assignments: the shape
    that names the route's gathers, sort and combine in a trace."""
    cfg = config()
    a = cfg["model_args"]
    tokens = cfg["trainer"]["global_batch_size"] * a["seq_len"]
    return min(a["expert_chunk_tokens"], tokens) * a["experts_per_token"]
