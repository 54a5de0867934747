"""The reader of ``latent_kv_ms_per_step`` (``metrics/_scopes.py``) on a
made-up record and a made-up table of the program's: a value where the
``latent`` scope ran inside ``attention``, nothing where the table has no
such scope (a program without multi-head latent attention, or from before
the scope) or no table at all — and its entry in ``BENCHMARK.json``."""

import importlib
import json
import os

import pytest

from benchmark.metrics import _scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "latent_kv_ms_per_step"


def _read(record):
    return importlib.import_module(f"benchmark.metrics.{NAME}").read(record)


def _row(shape, scope):
    return {"result": shape + "{2,1,0:T(8,128)}", "scope": scope}


# a traced pass of 4 steps: the latent's projection and expansion under
# ``latent``, the attention kernel and the query's projection under
# ``attention`` around it, the head under ``head_loss``
TABLE = {"jit_step": {
    "fusion.3": _row("f32[1,16384,576]", "latent"),
    "fusion.4": _row("f32[1,16384,8192]", "latent"),
    "pbtpu_attention_fwd.1": _row("bf16[1,32,16384,128]", "attention"),
    "fusion.5": _row("f32[1,16384,6144]", "attention"),
    "fusion.6": _row("f32[2048,16032]", "head_loss")}}
BY_OP = {"fusion.3 f32[1,16384,576]": 0.004,
         "fusion.4 f32[1,16384,8192]": 0.012,
         "pbtpu_attention_fwd.1 bf16[1,32,16384,128]": 0.2,
         "fusion.5 f32[1,16384,6144]": 0.03,
         "fusion.6 f32[2048,16032]": 0.05}
RECORD = {"passes": [{"steps": 4}], "trace": {"devices": 1, "by_op": BY_OP}}
# the same program without the scope: its rows are attention's
UNSCOPED = {"jit_step": {
    name: {**row, "scope": "attention" if row["scope"] == "latent"
           else row["scope"]} for name, row in TABLE["jit_step"].items()}}


@pytest.mark.parametrize("table,want", [
    (TABLE, (0.004 + 0.012) * 1e3 / 4),
    (UNSCOPED, None), ({}, None)],
    ids=["scope_present", "scope_absent", "no_table"])
def test_latent_reader_on_a_made_up_record(table, want, monkeypatch):
    monkeypatch.setattr(_scopes, "table", lambda: table)
    got = _read(RECORD)
    assert got == (None if want is None else pytest.approx(want))
    # no trace, or no steps: nothing to read either way
    assert _read({"passes": [{"steps": 4}], "trace": None}) is None
    assert _read({"passes": [], "trace": RECORD["trace"]}) is None


def test_latent_metric_is_registered_and_lists_its_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                       NAME + ".py"))
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"]) \
        == ("device_trace", "kernels", "examples_per_s_per_chip", "ms")
    assert entry["workloads"] and set(entry["workloads"]) <= set(cells)
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in entry["workloads"]:
        with open(os.path.join(ROOT, configs[cells[cell]["config"]]["file"])
                  ) as f:
            assert json.load(f)["model"] == "deepseek_v3", cell
    from paddlebox_tpu.monitor import names
    assert "latent" in names.DEVICE_SCOPE_NAMES
