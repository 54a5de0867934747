"""The counted work against hand-worked numbers, and the peaks table."""

import json
import os

import pytest

from benchmark import work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_deepfm_step_flops_by_hand():
    # deep input 26 * (3 + 10) + 13 = 351; 351*400 + 400*400*2 + 400 =
    # 460,800 multiply-adds, FM 2 * 26 * 10 = 520; forward + backward of
    # 8192 examples = 6 * 8192 * 461,320
    assert work.step_flops(_cfg("deepfm_criteo")) == 6 * 8192 * 461_320
    assert abs(work.step_flops(_cfg("deepfm_criteo")) - 22.7e9) < 0.1e9


def test_dlrm_step_flops_by_hand():
    # bottom 13*512 + 512*256 + 256*128 = 170,496; top 505*1024 +
    # 1024*1024 + 1024*512 + 512*256 + 256 = 2,221,312; Gram 27*27*128 =
    # 93,312: 2,485,120 multiply-adds an example
    assert work.step_flops(_cfg("dlrm_mlperf")) == 6 * 8192 * 2_485_120
    assert abs(work.step_flops(_cfg("dlrm_mlperf")) - 122.1e9) < 0.1e9


def test_step_bytes_by_hand():
    cfg = _cfg("dlrm_mlperf")
    b = work.step_bytes(cfg, tokens_per_step=212_992,
                        unique_rows_per_step=90_000)
    assert work.row_widths(cfg["embedding"]) == (131, 133)
    assert b["pull"] == 4 * 212_992 * 131
    assert b["push"] == 2 * 4 * 90_000 * 133
    assert b["total"] == b["pull"] + b["push"] + b["tower"]
    assert work.row_widths(_cfg("deepfm_criteo")["embedding"]) == (13, 15)


def test_peaks_unknown_device_is_an_error():
    assert work.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(RuntimeError):
        work.peaks("cpu")


# ---- the kernels' work: the running cell's own reference counts it -------

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
KERNEL_COUNTS = {"attention_ms_per_step": None,
                 "attention_roofline_pct": "attention_macs",
                 "expert_gmm_ms_per_step": None,
                 "expert_gmm_roofline_pct": "expert_gmm_macs",
                 "moe_route_ms_per_step": "route_rows",
                 "ssm_scan_ms_per_step": None,
                 "ssm_scan_roofline_pct": "ssm_scan_macs"}
TOKEN_CELLS = ["smallthinker_21b_ep4.seq8k", "nemotron3_nano_ep16.seq4k"]


def _cell_cfg(cell):
    entry = {w["name"]: w for w in BENCH["workloads"]}[cell]
    return _cfg(entry["config"])


def _listed(metric):
    return {m["name"]: m for m in BENCH["per_layer"]}[metric]["workloads"]


@pytest.mark.parametrize("metric", sorted(KERNEL_COUNTS))
@pytest.mark.parametrize("cell", TOKEN_CELLS)
def test_cell_config_finds_the_running_cell_for_each_kernel_metric(cell,
                                                                   metric):
    """By the record's work alone: the cell's own configuration where the
    metric lists the cell, nothing where it does not."""
    from benchmark.metrics import _cell
    cfg = _cell_cfg(cell)
    record = {"work": {"flops": work.step_flops(cfg)}}
    found = _cell.cell_config(record, metric)
    assert found == (cfg if cell in _listed(metric) else None)
    assert _cell.cell_config({}, metric) is None
    count = KERNEL_COUNTS[metric]
    if count and found:
        assert _cell.reference_count(record, metric, count) == (
            cfg, getattr(work.model_reference(cfg), count)(cfg))
        assert _cell.reference_count(record, metric, "no_such_count") \
            == (None, None)


@pytest.mark.parametrize("metric", sorted(
    m for m, count in KERNEL_COUNTS.items() if count))
def test_every_listed_cells_reference_counts_the_metrics_work(metric):
    for cell in _listed(metric):
        reference = work.model_reference(_cell_cfg(cell))
        assert callable(getattr(reference, KERNEL_COUNTS[metric])), \
            (cell, metric)


@pytest.mark.parametrize("cell", TOKEN_CELLS)
def test_a_token_cell_lists_itself_under_the_five_shared_kernel_metrics(cell):
    for metric in ("attention_ms_per_step", "attention_roofline_pct",
                   "expert_gmm_ms_per_step", "expert_gmm_roofline_pct",
                   "moe_route_ms_per_step"):
        assert cell in _listed(metric), metric
    reference = work.model_reference(_cell_cfg(cell))
    for count in ("attention_macs", "expert_gmm_macs", "route_rows"):
        assert callable(getattr(reference, count)), count


def test_no_reader_names_a_configuration():
    names = [c["name"] for c in BENCH["configs"]] \
        + sorted({c["file"].split("/")[-1].split("_")[0]
                  for c in BENCH["configs"]})
    metrics = os.path.join(ROOT, "benchmark", "metrics")
    for file in sorted(os.listdir(metrics)):
        if file.endswith(".py"):
            with open(os.path.join(metrics, file)) as f:
                text = f.read()
            assert not [n for n in names if n in text], file


def test_the_kernels_counts_by_hand():
    from benchmark.reference import nemotron_h, smallthinker
    cfg = _cfg("smallthinker_21b_ep4")
    # one full layer reads (8192 + 1) / 2 keys a query, three windowed
    # ones (4096 * 4097 / 2 + 4096 * 4096) / 8192 = 3072.25; scores and
    # values of 28 heads of 128
    scores_and_values = 8192 * 2 * 28 * 128
    assert smallthinker.attention_macs(cfg) \
        == scores_and_values * (4096.5 + 3 * 3072.25)
    # 6 of 64 choices fall on 16 held experts: 1.5 a token, three
    # products of 2560 x 768, four layers
    assert smallthinker.expert_gmm_macs(cfg) \
        == 4 * 8192 * 1.5 * 3 * 2560 * 768
    assert smallthinker.route_rows(cfg) == (4096 * 6, 16, 64)
    # 6 x 2 examples x those over the peak, at the recorded 175.9 and
    # 110.7 ms a step: 27.1 and 16.0 % (PERF.md section 5)
    assert round(100 * 12 * smallthinker.attention_macs(cfg)
                 / 197e12 / 0.1759, 1) == 27.1
    assert round(100 * 12 * smallthinker.expert_gmm_macs(cfg)
                 / 197e12 / 0.1107, 1) == 16.0
    cfg = _cfg("nemotron3_nano_ep16")
    # one '*' block of 32 heads of 128 over 4096 positions
    assert nemotron_h.attention_macs(cfg) == 4096 * 2 * 32 * 128 * 2048.5
    # 6 of 128 choices fall on 8 held experts: 0.375 a token, up and down
    # of 2688 x 1856, four 'E' blocks
    assert nemotron_h.expert_gmm_macs(cfg) \
        == 4 * 4096 * 0.375 * 2 * 2688 * 1856
    assert nemotron_h.route_rows(cfg) == (4096 * 6, 8, 128)
