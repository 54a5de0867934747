"""The readers of ``kda_ms_per_step`` and ``kda_roofline_pct`` on a
made-up record: the delta rule's kernels' seconds found by their names
(forward, its call under differentiation, backward), and their share of
the roofline read off the running cell's own reference counts, found from
the record's work — for a cell that lists itself under the metric, and
nothing for any other cell, a trace without the kernels, or a record
without peaks — and their entries in ``BENCHMARK.json``."""

import json
import os

import pytest

from benchmark import work
from benchmark.metrics import _cell, kda_ms_per_step, kda_roofline_pct

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _listed_cells():
    """(cell, its configuration) of every cell both metrics list."""
    bench = _bench()
    entries = {m["name"]: m for m in bench["per_layer"]}
    files = {c["name"]: c["file"] for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    listed = set(entries["kda_ms_per_step"]["workloads"])
    assert listed == set(entries["kda_roofline_pct"]["workloads"])
    out = []
    for name in sorted(listed):
        with open(os.path.join(ROOT, files[cells[name]["config"]])) as f:
            out.append((name, json.load(f)))
    return out


def _record(cfg, by_op):
    # a traced pass of 4 steps
    return {"passes": [{"steps": 4}], "peaks": PEAKS,
            "work": {"flops": work.step_flops(cfg)},
            "trace": {"devices": 1, "by_op": by_op}}


BY_OP = {"pbtpu_kda_fwd": 0.1, "jvp_pbtpu_kda_fwd_ x": 0.1,
         "pbtpu_kda_bwd": 0.2, "pbtpu_attention_fwd": 0.5, "fusion.1": 9.0}


def test_the_readers_find_the_kernels_and_the_cells_own_counts():
    cells = _listed_cells()
    assert cells
    for name, cfg in cells:
        reference = work.model_reference(cfg)
        record = _record(cfg, BY_OP)
        assert _cell.cell_config(record, "kda_roofline_pct") == cfg, name
        # 0.4 s of the three kernels over 4 steps
        assert kda_ms_per_step.read(record) == pytest.approx(100.0)
        least = max(6.0 * cfg["trainer"]["global_batch_size"]
                    * reference.kda_macs(cfg) / PEAKS["flops_per_s"],
                    reference.kda_bytes(cfg) / PEAKS["hbm_bytes_per_s"])
        assert kda_roofline_pct.read(record) == pytest.approx(
            100.0 * least / 0.1)
        assert 0 < kda_roofline_pct.read(record) < 100
        # the rule moves more bytes than its products can hide: bandwidth
        # bounds it
        assert reference.kda_bytes(cfg) / PEAKS["hbm_bytes_per_s"] > \
            6.0 * reference.kda_macs(cfg) / PEAKS["flops_per_s"]


def test_nothing_to_read_without_the_kernels_or_of_another_cell():
    bench = _bench()
    name, cfg = _listed_cells()[0]
    bare = _record(cfg, {"fusion.1": 9.0})
    assert kda_ms_per_step.read(bare) is None
    assert kda_roofline_pct.read(bare) is None
    no_peaks = {k: v for k, v in _record(cfg, BY_OP).items() if k != "peaks"}
    assert kda_roofline_pct.read(no_peaks) is None
    assert kda_ms_per_step.read(no_peaks) == pytest.approx(100.0)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    listed = {n for n, _ in _listed_cells()}
    for cell in bench["workloads"]:
        if cell["name"] in listed:
            continue
        with open(os.path.join(ROOT, files[cell["config"]])) as f:
            other = json.load(f)
        assert kda_roofline_pct.read(_record(other, BY_OP)) is None, \
            cell["name"]


def test_the_metrics_are_registered():
    entries = {m["name"]: m for m in _bench()["per_layer"]}
    for metric, unit in (("kda_ms_per_step", "ms"),
                         ("kda_roofline_pct", "%")):
        entry = entries[metric]
        assert (entry["source"], entry["layer"], entry["moves"],
                entry["unit"]) == ("device_trace", "kernels",
                                   "examples_per_s_per_chip", unit)
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                           metric + ".py"))
    from paddlebox_tpu.monitor import names
    assert {"pbtpu_kda_fwd", "pbtpu_kda_bwd"} <= set(names.KERNEL_NAMES)
