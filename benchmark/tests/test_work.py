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
