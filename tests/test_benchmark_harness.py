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

# The one case of benchmark/tests that fails at the parent: the fault
# patches optax.sigmoid_binary_cross_entropy, which the ordered-token
# tower's own loss never calls, so nothing is planted and the run comes
# out correct (PERF.md section 7, "for a `benchmark` PR").
_PLANTS_NOTHING = ("smallthinker_21b_ep4.seq8k", "_half_batch")


@pytest.mark.parametrize("fault", [_correct._unchanged_state,
                                   _correct._half_batch,
                                   _correct._write_back_dropped])
@pytest.mark.parametrize("cell", _correct.CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):  # noqa: F811
    if (cell, fault.__name__) == _PLANTS_NOTHING:
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


def test_the_cells_files_state_the_cut_and_the_published_widths():
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}["smallthinker_21b_ep4"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
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
    assert cfg["embedding"]["dim"] == a["hidden_size"]
    from benchmark.reference import smallthinker as ref
    # 559.3 M dense parameters; 8192 x 312.6 M multiply-adds an example
    assert round(ref.tower_sizes(cfg)[0] / 1e6, 1) == 559.3
    assert round(ref.macs_per_example(cfg) / 8192 / 1e6, 1) == 312.6
