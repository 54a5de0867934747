"""The readers of the device-scope metrics (``metrics/_scopes.py``), each
on a made-up record and a made-up table of the program's: a value where
the scope ran, nothing without a table (a program from before the table),
a label two programs give different scopes counted as no scope's — and
every entry of ``BENCHMARK.json`` that reads a scope has its file and
lists accepted cells."""

import importlib
import json
import os

import pytest

from benchmark.metrics import _scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# metric -> the scope it reads
SCOPE_OF = {
    "pull_ms_per_step": "pull", "premerge_ms_per_step": "premerge",
    "push_ms_per_step": "push", "tower_rest_ms_per_step": "tower",
    "dense_update_ms_per_step": "dense_update",
    "head_loss_ms_per_step": "head_loss",
    "moe_route_scoped_ms_per_step": "route", "mixer_ms_per_step": "mixer",
    "dense_mlp_ms_per_step": "dense_mlp",
}
SCOPES = sorted(set(SCOPE_OF.values()))


def reader(name):
    return importlib.import_module(f"benchmark.metrics.{name}").read


def row(shape, scope):
    return {"result": shape + "{1,0:T(8,128)}", "scope": scope}


# the step's program holds one instruction a scope, 0.01 s x (k + 1) each
# in a traced pass of 10 steps; the apply holds the push whole
TABLE = {
    "jit_step_flat": {
        **{f"fusion.{k}": row(f"f32[{k + 1},128]", scope)
           for k, scope in enumerate(SCOPES)},
        "copy.77": row("f32[8]", None),
        # the same label as the apply's, under another scope
        "fusion.90": row("f32[64,5]", "premerge"),
        "pbtpu_ssm_fwd.1": {"result": "(bf16[2,64]{1,0}, f32[2]{0})",
                            "scope": "mixer"}},
    "jit_apply": {"fusion.90": row("f32[64,5]", "push"),
                  "fusion.91": row("f32[64,128]", "push")},
}
BY_OP = {
    **{f"fusion.{k} f32[{k + 1},128]": 0.01 * (k + 1)
       for k in range(len(SCOPES))},
    "copy.77 f32[8]": 0.02,                     # unscoped
    "fusion.90 f32[64,5]": 0.03,                # ambiguous
    "fusion.91 f32[64,128]": 0.05,              # push, beside fusion.<k>
    "pbtpu_ssm_fwd.1 bf16[2,64]": 0.04,         # a kernel under mixer
    "jit_reshape.3 f32[4]": 0.01,               # unknown
}
RECORD = {"passes": [{"steps": 10}],
          "trace": {"devices": 1, "by_op": BY_OP}}


@pytest.fixture()
def table(monkeypatch):
    monkeypatch.setattr(_scopes, "table", lambda: TABLE)


def expected_ms(scope):
    s = 0.01 * (SCOPES.index(scope) + 1)
    s += {"push": 0.05, "mixer": 0.04}.get(scope, 0.0)
    return s * 1e3 / 10


@pytest.mark.parametrize("name", sorted(SCOPE_OF))
def test_reader_on_a_made_up_record_and_table(name, table):
    assert reader(name)(RECORD) == pytest.approx(expected_ms(SCOPE_OF[name]))


@pytest.mark.parametrize("name", sorted(SCOPE_OF) + ["device_unscoped_pct"])
def test_reader_reads_nothing_without_a_table(name, monkeypatch):
    monkeypatch.setattr(_scopes, "table", lambda: {})
    assert reader(name)(RECORD) is None
    monkeypatch.setattr(_scopes, "table", lambda: TABLE)
    assert reader(name)({"passes": [{"steps": 10}], "trace": None}) is None
    if name in SCOPE_OF:        # a share needs no steps, a time a step does
        assert reader(name)({"passes": [], "trace": RECORD["trace"]}) is None


def test_an_ambiguous_label_counts_as_unscoped(table):
    by_scope = _scopes.seconds_by_scope(RECORD)
    assert by_scope[_scopes.AMBIGUOUS] == pytest.approx(0.03)
    assert by_scope[_scopes.UNSCOPED] == pytest.approx(0.02)
    assert by_scope[_scopes.UNKNOWN] == pytest.approx(0.01)
    # neither side of the ambiguous label got its seconds
    assert by_scope["premerge"] == pytest.approx(
        0.01 * (SCOPES.index("premerge") + 1))
    total = sum(BY_OP.values())
    assert sum(by_scope.values()) == pytest.approx(total)
    assert reader("device_unscoped_pct")(RECORD) == pytest.approx(
        100 * (0.03 + 0.02 + 0.01) / total)


def test_a_scope_that_did_not_run_reads_nothing(table):
    record = {**RECORD, "trace": {"devices": 1, "by_op": {
        "fusion.0 f32[1,128]": 0.5}}}
    assert reader("moe_route_scoped_ms_per_step")(record) is None
    assert SCOPES[0] == "dense_mlp"       # fusion.0's, in TABLE
    assert reader("dense_mlp_ms_per_step")(record) == pytest.approx(50)
    assert reader("device_unscoped_pct")(record) == 0.0


def test_the_program_without_the_table_is_read_as_none(monkeypatch):
    """The parent of ISSUE 38 has no ``monitor.device_scopes``: the
    import fails and every reader reads nothing, without raising."""
    import builtins
    real = builtins.__import__

    def no_table(name, *a, **kw):
        if name.endswith("monitor") and "device_scopes" in (a[2] or ()):
            raise ImportError(name)
        return real(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", no_table)
    assert _scopes.table() == {}
    assert reader("pull_ms_per_step")(RECORD) is None


def test_every_scope_metric_has_its_file_and_lists_accepted_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in sorted(SCOPE_OF) + ["device_unscoped_pct"]:
        entry = entries[name]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "metrics", name + ".py")), name
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "examples_per_s_per_chip"
        assert entry["workloads"] and set(entry["workloads"]) <= cells, name
    from paddlebox_tpu.monitor import names
    assert set(SCOPE_OF.values()) <= set(names.DEVICE_SCOPE_NAMES)
