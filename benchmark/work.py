"""The work one training step must do, counted from a configuration's
shapes — the algorithm's work, whatever kernels carry it, so the shares
stay valid when a later PR swaps or deletes a kernel.

FLOPs: the model's forward and backward pass for one batch. The model's
multiply-adds per example come from its reference file
(``macs_per_example``); the backward pass costs twice the forward, and a
multiply-add is two operations, so a step is 6 * batch * macs.

Bytes: what the step's sparse work must move through HBM whatever
implements it — the pull reads ``pull_width`` floats per token, the push
reads and writes ``row_width`` floats per unique row — plus the tower's
weights and activations once: what no engine can avoid.

The peaks are in ``peaks.json`` with their source; a device that is not
there is an error, never a default.
"""

from __future__ import annotations

import json
import os

from benchmark.reference.steps import model_reference

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    kind = device_kind.lower()
    for key, val in table.items():
        if key in kind:
            return val
    raise RuntimeError(
        f"device_kind {device_kind!r} is not in benchmark/peaks.json: add "
        f"its published peaks, with their source, before measuring on it")


def row_widths(emb: dict) -> tuple[int, int]:
    """(pull_width, row_width) in floats: [show, clk, w, embedding] and
    the same plus the optimizer's state columns."""
    opt_cols = {"sgd": 0, "adagrad": 2, "ftrl": 3, "adam": 4}[
        emb.get("optimizer", "adagrad")]
    pull = 3 + int(emb["dim"])
    return pull, pull + opt_cols


def step_flops(cfg: dict) -> float:
    batch = int(cfg["trainer"]["global_batch_size"])
    return 6.0 * batch * model_reference(cfg).macs_per_example(cfg)


def step_bytes(cfg: dict, tokens_per_step: float,
               unique_rows_per_step: float) -> dict:
    batch = int(cfg["trainer"]["global_batch_size"])
    pull, row = row_widths(cfg["embedding"])
    n_params, act_per_example = model_reference(cfg).tower_sizes(cfg)
    parts = {
        "pull": 4.0 * tokens_per_step * pull,
        "push": 2 * 4.0 * unique_rows_per_step * row,
        "tower": 4.0 * (n_params + batch * act_per_example),
    }
    parts["total"] = sum(parts.values())
    return parts
