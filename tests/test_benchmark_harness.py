"""The benchmark's harness under the driver's count: the tests of
``benchmark/tests`` collected here as they are — the seam for a model's
own loss over ordered tokens (``test_seam``), the traffic generator
(``test_datagen``), the work counts (``test_work``), the per-layer readers
held to the program's span and counter names (``test_stage_metrics``,
``test_trace_reduce``), and what decides ``correct`` (``test_correct``: the
program against the f32 reference, the bfloat16 control and the planted
faults, at CPU size for every cell of ``BENCHMARK.json`` and the waiting
one) — plus a whole ``run.py --rehearse`` of each cell at its own rehearsal
sizes: traffic files, the program through BoxPS passes, the window, the
read-back, the blocked reference and the comparison, on the CPU."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests import test_correct as _correct    # noqa: E402
from benchmark.tests.test_correct import *      # noqa: E402,F401,F403
from benchmark.tests.test_datagen import *      # noqa: E402,F401,F403
from benchmark.tests.test_seam import *         # noqa: E402,F401,F403
from benchmark.tests.test_stage_metrics import *    # noqa: E402,F401,F403
from benchmark.tests.test_trace_reduce import *     # noqa: E402,F401,F403
from benchmark.tests.test_work import *         # noqa: E402,F401,F403

# The cases of benchmark/tests in which nothing is planted: the fault
# patches optax.sigmoid_binary_cross_entropy, which a tower that declares
# its own loss never calls, so the run comes out correct (PERF.md section
# 7, "for a `benchmark` PR"): the two token cells, by name.
_PLANTS_NOTHING = {("smallthinker_21b_ep4.seq8k", "_half_batch"),
                   ("nemotron3_nano_ep16.seq4k", "_half_batch")}


@pytest.mark.parametrize("fault", [_correct._unchanged_state,
                                   _correct._half_batch,
                                   _correct._write_back_dropped])
@pytest.mark.parametrize("cell", _correct.CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):  # noqa: F811
    if (cell, fault.__name__) in _PLANTS_NOTHING:
        pytest.skip("the fault patches a loss this cell's model never calls")
    _correct.test_broken_timed_path_is_not_correct(cell, fault, monkeypatch)


@pytest.mark.parametrize("cell", _correct.CELLS)
def test_the_cell_rehearses_whole(cell, capsys):
    import json
    from benchmark import run
    code = run.main(["--workload", cell, "--seed", "2800000321",
                     "--seconds", "1", "--rehearse",
                     "--waiting", _correct.WAITING])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == 0 and last["rehearsal"] == "passed"
    assert last["failed"] == 0 and last["attempted"] > 0
    assert set(last["compared"]) >= {"ingest_mismatch", "counter_mismatch",
                                     "window_counter_mismatch",
                                     "loss_gap_1", "grad_gap", "change_gap"}


def _smallthinker_cut(entry, cfg):
    a = cfg["model_args"]
    published = {"hidden_size": 2560, "num_attention_heads": 28,
                 "num_key_value_heads": 4, "head_dim": 128,
                 "moe_ffn_hidden_size": 768, "sliding_window_size": 4096,
                 "rope_theta": 1500000, "rms_norm_eps": 1e-6}
    for key, value in published.items():
        assert cfg[key] == value and a[key] == value, key
    assert (a["router_experts"], a["experts_per_token"]) == (64, 6)
    assert cfg["moe_num_active_primary_experts"] == 6
    assert a["experts_held"] == cfg["moe_num_primary_experts"] == 16
    assert a["vocab_size"] == cfg["vocab_size"] == 151936 // 4
    assert a["layer_kinds"] == cfg["sliding_window_layout"][:4] \
        == cfg["rope_layout"][:cfg["num_hidden_layers"]] == [0, 1, 1, 1]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size",
        "steps_per_pass"}
    from benchmark.reference import smallthinker as ref
    # 559.3 M dense parameters; 8192 x 312.6 M multiply-adds an example
    assert round(ref.tower_sizes(cfg)[0] / 1e6, 1) == 559.3
    assert round(ref.macs_per_example(cfg) / 8192 / 1e6, 1) == 312.6


def _nemotron_cut(entry, cfg):
    a = cfg["model_args"]
    # every width as published: hidden 2688, 64 x 64 Mamba heads in 8
    # groups with a state of 128, convolution 4, chunks of 128, 32 / 2
    # attention heads of 128, experts 1856 wide, the shared one 3712
    published = {"hidden_size": 2688, "mamba_num_heads": 64,
                 "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
                 "conv_kernel": 4, "chunk_size": 128,
                 "num_attention_heads": 32, "num_key_value_heads": 2,
                 "head_dim": 128, "moe_intermediate_size": 1856,
                 "moe_shared_expert_intermediate_size": 3712,
                 "routed_scaling_factor": 2.5, "layer_norm_epsilon": 1e-5,
                 "time_step_min": 0.001, "time_step_max": 0.1,
                 "time_step_floor": 0.0001}
    for key, value in published.items():
        assert cfg[key] == value and a[key] == value, key
    assert (a["router_experts"], a["experts_per_token"]) == (128, 6)
    assert cfg["num_experts_per_tok"] == 6 and cfg["n_shared_experts"] == 1
    assert cfg["intermediate_size"] == 1856 and cfg["norm_topk_prob"]
    assert cfg["mlp_hidden_act"] == "relu2"
    assert a["experts_held"] == cfg["n_routed_experts"] == 8
    assert a["vocab_size"] == cfg["vocab_size"] == 131072 // 8
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == 52 and cfg["num_hidden_layers"] == 9
    assert a["block_pattern"] == pattern[:9] == "MEMEM*EME"
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "steps_per_pass"}
    assert set(cfg["reduced_from"]) == set(cfg["reduced"])
    assert "16 chips" in cfg["deployment"] and cfg["assumed"]
    from benchmark.reference import nemotron_h as ref
    # 622.9 M dense parameters (9.97 GB at 16 B); 4096 x 340.8 M
    # multiply-adds an example, 5.5 M of them a token the scans'
    assert round(ref.tower_sizes(cfg)[0] / 1e6, 1) == 622.9
    assert round(ref.macs_per_example(cfg) / 4096 / 1e6, 1) == 340.8
    assert round(ref.ssm_scan_macs(cfg) / 4096 / 1e6, 1) == 5.5
    assert round(ref.ssm_scan_bytes(cfg) / 1e9, 2) == 1.80


@pytest.mark.parametrize("config,holds", [
    ("smallthinker_21b_ep4", _smallthinker_cut),
    ("nemotron3_nano_ep16", _nemotron_cut)])
def test_the_cells_files_state_the_cut_and_the_published_widths(config,
                                                                holds):
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}[config]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["embedding"]["dim"] == cfg["model_args"]["hidden_size"]
    assert cfg["source"] == entry["source"]
    holds(entry, cfg)


def test_the_scan_roofline_reader_takes_the_records_own_cell():
    """The reader names no configuration: it finds the running cell's from
    the record's work, and its counts from that cell's reference."""
    import json
    from benchmark import work
    from benchmark.metrics import ssm_scan_roofline_pct as reader
    from benchmark.reference import nemotron_h as ref

    def config(name):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            return json.load(f)

    cfg = config("nemotron3_nano_ep16")
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    record = {"passes": [{"steps": 4}], "peaks": peaks,
              "work": {"flops": work.step_flops(cfg)},
              "trace": {"devices": 1, "by_op": {
                  "pbtpu_ssm_fwd": 0.02, "jvp_pbtpu_ssm_fwd_ x": 0.02,
                  "pbtpu_ssm_bwd": 0.04, "fusion.1": 9.0}}}
    assert reader.cell_config(record, "ssm_scan_roofline_pct") == cfg
    # bandwidth bounds the scan: its bytes over the peak, over 20 ms a step
    least = ref.ssm_scan_bytes(cfg) / 819e9
    assert 6.0 * 2 * ref.ssm_scan_macs(cfg) / 197e12 < least
    assert reader.read(record) == pytest.approx(100.0 * least / 0.02)
    # another cell's work, or a trace with no such kernel: nothing to read
    other = work.step_flops(config("smallthinker_21b_ep4"))
    assert reader.read({**record, "work": {"flops": other}}) is None
    assert reader.read({**record, "trace": {
        "devices": 1, "by_op": {"fusion.1": 9.0}}}) is None
