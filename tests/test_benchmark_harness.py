"""The benchmark's harness under the driver's count: the tests of
``benchmark/tests`` collected here as they are — the seam for a model's
own loss over ordered tokens (``test_seam``), the traffic generator
(``test_datagen``), the work counts (``test_work``), the per-layer readers
held to the program's span, counter and device-scope names
(``test_stage_metrics``, ``test_trace_reduce``, ``test_scope_metrics``,
``test_grouped_product``, ``test_latent_kv_metric``, ``test_kda_metric``), and what decides
``correct`` (``test_correct``: the program against the f32 reference, the
bfloat16 control and the planted faults, at CPU size for every cell of
``BENCHMARK.json`` and the waiting one) — plus a whole ``run.py --rehearse`` of each cell at its own rehearsal
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
from benchmark.tests.test_grouped_product import *  # noqa: E402,F401,F403
from benchmark.tests.test_kda_metric import *   # noqa: E402,F401,F403
from benchmark.tests.test_latent_kv_metric import *  # noqa: E402,F401,F403
from benchmark.tests.test_seam import *         # noqa: E402,F401,F403
from benchmark.tests.test_scope_metrics import *    # noqa: E402,F401,F403
from benchmark.tests.test_stage_metrics import *    # noqa: E402,F401,F403
from benchmark.tests.test_trace_reduce import *     # noqa: E402,F401,F403
from benchmark.tests.test_work import *         # noqa: E402,F401,F403

# The cases of benchmark/tests in which nothing is planted: the fault
# patches optax.sigmoid_binary_cross_entropy, which a tower that declares
# its own loss never calls, so the run comes out correct (PERF.md section
# 7, "for a `benchmark` PR"): the five token cells, by name.
_PLANTS_NOTHING = {("smallthinker_21b_ep4.seq8k", "_half_batch"),
                   ("nemotron3_nano_ep16.seq4k", "_half_batch"),
                   ("lfm2_24b_a2b_ep8.seq8k", "_half_batch"),
                   ("kanana2_30b_a3b_ep8.seq16k", "_half_batch"),
                   ("kimi_linear_48b_a3b_ep32.seq16k", "_half_batch")}


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


def _lfm2_cut(entry, cfg):
    a = cfg["model_args"]
    # every width as published: hidden 2048, 32 / 8 heads of 64, the dense
    # MLP 11776, experts 1536, three taps, theta 1e6, eps 1e-5
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "intermediate_size": 11776,
                 "moe_intermediate_size": 1536, "conv_L_cache": 3,
                 "norm_eps": 1e-5, "routed_scaling_factor": 1}
    for key, value in published.items():
        assert cfg[key] == value and a[key] == value, key
    assert a["head_dim"] == 2048 // 32 and "head_dim" in cfg["assumed"]
    assert a["rope_theta"] == cfg["rope_parameters"]["rope_theta"] == 1000000
    assert (a["router_experts"], a["experts_per_token"]) == (64, 4)
    assert cfg["num_experts_per_tok"] == 4 and cfg["norm_topk_prob"]
    assert cfg["use_expert_bias"] and not cfg["conv_bias"]
    assert a["experts_held"] == cfg["num_experts"] == 8
    assert a["vocab_size"] == cfg["vocab_size"] == 65536 // 8
    # layers 1-5 of the 40 published: one leading dense layer (they count
    # once) and the whole period that follows it
    kinds = cfg["layer_types"]
    assert len(kinds) == 40 and kinds.count("full_attention") == 10
    assert a["layer_types"] == kinds[1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert cfg["num_hidden_layers"] == len(a["layer_types"]) == 5
    assert a["dense_layers"] == cfg["num_dense_layers"] == 1
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts",
        "vocab_size", "steps_per_pass"}
    assert set(cfg["reduced_from"]) == set(cfg["reduced"])
    assert "8 chips" in cfg["deployment"]
    assert {"expert_bias", "untied head", "initialisation", "sequences",
            "trainer.dense_lr"} <= set(cfg["assumed"])
    from benchmark.reference import lfm2_moe as ref
    # 469.3 M dense parameters (7.5 GB at 16 B); 8192 x 202.9 M
    # multiply-adds an example (19.95 TFLOP a step of 2); the short
    # convolutions move 5.9 GB a step at the least
    assert round(ref.tower_sizes(cfg)[0] / 1e6, 1) == 469.3
    assert round(ref.macs_per_example(cfg) / 8192 / 1e6, 1) == 202.9
    assert round(ref.attention_macs(cfg) / 8192 / 1e6, 2) == 16.78
    assert round(ref.expert_gmm_macs(cfg) / 8192 / 1e6, 2) == 18.87
    assert round(ref.short_conv_bytes(cfg) / 1e9, 2) == 5.91
    assert ref.short_conv_macs(cfg) == 4 * 8192 * 2048 * 5
    assert ref.route_rows(cfg) == (16384, 8, 64)


def _kanana_cut(entry, cfg):
    a = cfg["model_args"]
    # every width as published: hidden 2048, 32 heads whose queries and
    # keys are 128 + 64 = 192 channels beside values of 128, a latent of
    # 512, the dense MLP 6144, experts 768 with two shared, theta 1e6
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "kv_lora_rank": 512,
                 "intermediate_size": 6144, "moe_intermediate_size": 768,
                 "n_shared_experts": 2, "routed_scaling_factor": 2.448,
                 "rope_theta": 1000000, "rope_interleave": True,
                 "rms_norm_eps": 1e-6}
    for key, value in published.items():
        assert cfg[key] == value and a[key] == value, key
    assert cfg["qk_head_dim"] == 192 and cfg["num_key_value_heads"] == 32
    assert cfg["q_lora_rank"] is None and cfg["rope_scaling"] is None
    assert (cfg["n_group"], cfg["topk_group"]) == (1, 1)
    assert (a["router_experts"], a["experts_per_token"]) == (128, 6)
    assert cfg["num_experts_per_tok"] == 6 and cfg["norm_topk_prob"]
    assert a["experts_held"] == cfg["n_routed_experts"] == 16
    assert a["vocab_size"] == cfg["vocab_size"] == 128256 // 8
    # layers 0-4 of the 48 published: the leading dense layer and four of
    # the expert layers after it
    assert a["num_layers"] == cfg["num_hidden_layers"] == 5
    assert a["dense_layers"] == cfg["first_k_dense_replace"] == 1
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "steps_per_pass"}
    assert set(cfg["reduced_from"]) == set(cfg["reduced"])
    assert "8 chips" in cfg["deployment"]
    assert {"e_score_correction_bias", "untied head", "initialisation",
            "sequences", "rope_interleave", "trainer.dense_lr",
            "weights_seed"} <= set(cfg["assumed"])
    from benchmark import sut
    from benchmark.reference import deepseek_v3 as ref
    # 543.1 M dense parameters (8.7 GB at 16 B); 11.05 T multiply-adds an
    # example, 6.87 T of them attention's (66.3 TFLOP a step of one)
    assert round(ref.tower_sizes(cfg)[0] / 1e6, 1) == 543.1
    assert round(ref.macs_per_example(cfg) / 1e12, 2) == 11.05
    assert round(ref.attention_macs(cfg) / 1e12, 2) == 6.87
    assert round(ref.expert_gmm_macs(cfg) / 16384 / 1e6, 2) == 14.16
    assert ref.route_rows(cfg) == (24576, 16, 128)
    # every rung but the first is also a length of the model (the query
    # projection's and the dense MLP's 6,144, twice that, a chunk's rows),
    # so the cell is not listed under moe_route_ms_per_step
    assert sut.route_rungs(*ref.route_rows(cfg)) == (3072, 6144, 12288,
                                                     24576)


def _kimi_cut(entry, cfg):
    a = cfg["model_args"]
    # every width as published: hidden 2304; KDA 32 heads of 128 with
    # convolutions of 4 taps; latent attention of 32 heads, 128 + 64 / 128,
    # a latent of 512; the dense MLP 9216, experts 1024 with one shared
    published = {"hidden_size": 2304, "num_attention_heads": 32,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "kv_lora_rank": 512,
                 "intermediate_size": 9216, "moe_intermediate_size": 1024,
                 "routed_scaling_factor": 2.446, "rope_theta": 10000,
                 "rms_norm_eps": 1e-5}
    for key, value in published.items():
        assert cfg[key] == value and a[key] == value, key
    la = cfg["linear_attn_config"]
    assert (a["kda_num_heads"], a["kda_head_dim"],
            a["short_conv_kernel_size"]) == (
        la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]) \
        == (32, 128, 4)
    assert (a["kda_layers"], a["full_attn_layers"]) == (
        la["kda_layers"], la["full_attn_layers"])
    assert cfg["mla_use_nope"] and cfg["q_lora_rank"] is None
    assert a["n_shared_experts"] == cfg["num_shared_experts"] == 1
    assert (cfg["num_expert_group"], cfg["topk_group"]) == (1, 1)
    assert (a["router_experts"], a["experts_per_token"]) == (256, 8)
    assert cfg["num_experts_per_token"] == 8 and cfg["moe_renormalize"]
    assert a["experts_held"] == cfg["num_experts"] == 8
    assert a["vocab_size"] == cfg["vocab_size"] == 163840 // 8
    # layers 1-5 of the 27 published: the leading dense KDA layer and one
    # whole period after it (KDA, KDA, latent attention, KDA)
    assert a["num_layers"] == cfg["num_hidden_layers"] == 5
    assert a["dense_layers"] == cfg["first_k_dense_replace"] == 1
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "steps_per_pass"}
    assert set(cfg["reduced_from"]) == set(cfg["reduced"])
    assert "32 chips" in cfg["deployment"]
    assert {"e_score_correction_bias", "untied head", "initialisation",
            "sequences", "mla_use_nope", "kda gates' low rank", "kda o_norm",
            "A_log and dt_bias", "trainer.dense_lr",
            "weights_seed"} <= set(cfg["assumed"])
    from benchmark import sut
    from benchmark.reference import kimi_linear as ref
    # 555.2 M dense parameters (8.88 GB at 16 B); 7.07 T multiply-adds an
    # example, 1.37 T of them the latent-attention layer's scores and
    # values, 0.195 T the four delta rules' (42.4 TFLOP a step of one)
    assert round(ref.tower_sizes(cfg)[0] / 1e6, 1) == 555.2
    assert round(ref.macs_per_example(cfg) / 1e12, 2) == 7.07
    assert round(ref.attention_macs(cfg) / 1e12, 2) == 1.37
    assert round(ref.kda_macs(cfg) / 1e9, 1) == 195.1
    assert round(ref.kda_bytes(cfg) / 1e9, 2) == 10.23
    # 8 of 256 choices fall on 8 held experts: a quarter of a choice a
    # token, three products of 2304 x 1024, four layers
    assert ref.expert_gmm_macs(cfg) == 4 * 16384 * 0.25 * 3 * 2304 * 1024
    assert ref.route_rows(cfg) == (32768, 8, 256)
    assert sut.route_rungs(*ref.route_rows(cfg)) == (4096, 8192, 16384,
                                                     32768)


@pytest.mark.parametrize("config,holds", [
    ("smallthinker_21b_ep4", _smallthinker_cut),
    ("nemotron3_nano_ep16", _nemotron_cut),
    ("lfm2_24b_a2b_ep8", _lfm2_cut),
    ("kanana2_30b_a3b_ep8", _kanana_cut),
    ("kimi_linear_48b_a3b_ep32", _kimi_cut)])
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
    from benchmark.metrics import _cell
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
    assert _cell.cell_config(record, "ssm_scan_roofline_pct") == cfg
    # bandwidth bounds the scan: its bytes over the peak, over 20 ms a step
    least = ref.ssm_scan_bytes(cfg) / 819e9
    assert 6.0 * 2 * ref.ssm_scan_macs(cfg) / 197e12 < least
    assert reader.read(record) == pytest.approx(100.0 * least / 0.02)
    # another cell's work, or a trace with no such kernel: nothing to read
    other = work.step_flops(config("smallthinker_21b_ep4"))
    assert reader.read({**record, "work": {"flops": other}}) is None
    assert reader.read({**record, "trace": {
        "devices": 1, "by_op": {"fusion.1": 9.0}}}) is None


def test_the_short_conv_readers_take_the_records_own_cell():
    """Both new readers on a fixture record: the kernels' milliseconds a
    step; their share of the roofline bandwidth sets, the configuration
    found from the record's work through ``_cell.cell_config``; nothing on
    a record without the kernels or of another cell."""
    import json
    from benchmark import work
    from benchmark.metrics import (_cell, short_conv_ms_per_step,
                                   short_conv_roofline_pct)
    from benchmark.reference import lfm2_moe as ref

    def config(name):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            return json.load(f)

    cfg = config("lfm2_24b_a2b_ep8")
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    record = {"passes": [{"steps": 4}], "peaks": peaks,
              "work": {"flops": work.step_flops(cfg)},
              "trace": {"devices": 1, "by_op": {
                  "pbtpu_short_conv_fwd": 0.016,
                  "jvp_pbtpu_short_conv_fwd_ x": 0.016,
                  "pbtpu_short_conv_bwd": 0.032,
                  "pbtpu_attention_fwd": 0.5, "fusion.1": 9.0}}}
    assert _cell.cell_config(record, "short_conv_roofline_pct") == cfg
    assert short_conv_ms_per_step.read(record) == pytest.approx(16.0)
    # bandwidth bounds it: 5.9 GB over 819 GB/s, over 16 ms a step
    least = ref.short_conv_bytes(cfg) / 819e9
    assert 6.0 * 2 * ref.short_conv_macs(cfg) / 197e12 < least
    assert short_conv_roofline_pct.read(record) == pytest.approx(
        100.0 * least / 0.016)
    assert 0 < short_conv_roofline_pct.read(record) < 100
    # a trace with no such kernel, or another cell's work: nothing to read
    bare = {**record, "trace": {"devices": 1, "by_op": {"fusion.1": 9.0}}}
    assert short_conv_ms_per_step.read(bare) is None
    assert short_conv_roofline_pct.read(bare) is None
    other = work.step_flops(config("nemotron3_nano_ep16"))
    assert short_conv_roofline_pct.read(
        {**record, "work": {"flops": other}}) is None
    # and the readers of the kernels this tower shares find its cell too
    from benchmark.metrics import attention_roofline_pct, moe_route_ms_per_step
    assert attention_roofline_pct.read(record) == pytest.approx(
        100.0 * 6 * 2 * ref.attention_macs(cfg) / 197e12 / 0.125)
    # ... but not the route's: every rung of this cell's ladder (2,048 /
    # 4,096 / 8,192 / 16,384 rows) is also a length of the model (the
    # hidden size, the MLPs' token chunks, the sequence, a step's tokens),
    # so the cell is not listed under that metric and its reader is silent
    from benchmark import sut
    assert sut.route_rungs(*ref.route_rows(cfg)) == (2048, 4096, 8192, 16384)
    assert moe_route_ms_per_step.read(record) is None
