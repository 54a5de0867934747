"""The device's time by the program's own stages (ISSUE 38): the step is
written under ``monitor.device_scope``; ``device_scopes.scopes_of_hlo``
reads the scopes back from a compiled program's text; a pass under a
``jax.profiler`` capture leaves the table of every program it ran in
``device_scopes.TABLE``; ``python -m paddlebox_tpu.monitor.trace
--device`` joins that table with the capture's own time by instruction;
and with no capture open nothing of it runs."""

from __future__ import annotations

import json
import shutil
import tempfile

import pytest

from paddlebox_tpu import monitor
from paddlebox_tpu.config import flags, set_flags
from paddlebox_tpu.monitor import device_scopes, names
from paddlebox_tpu.monitor import trace as trace_lib
from paddlebox_tpu.monitor.registry import STATS

from test_monitor import _tiny_trainer
from token_tower_common import rehearsal_cell


@pytest.fixture(autouse=True)
def _clean_table():
    device_scopes.TABLE.clear()
    yield
    device_scopes.TABLE.clear()


# ---------------------------------------------------------------------------
# (1) the table's program side, on canned optimized-HLO text
# ---------------------------------------------------------------------------

_META = 'metadata={op_name="jit(step)/%s" stack_frame_id=7}'
CANNED = "\n".join([
    "HloModule jit_step, is_scheduled=true, entry_computation_layout={()}",
    "",
    "FileNames",
    '1 "trainer.py"',
    "",
    "%fused_computation.1 (param_0: f32[8,4], param_1: f32[8,4]) -> f32[8,4] {",
    "  %param_0 = f32[8,4]{1,0} parameter(0)",
    "  %param_1 = f32[8,4]{1,0} parameter(1)",
    "  %multiply.3 = f32[8,4]{1,0} multiply(%param_0, %param_1), "
    + _META % "jvp(pbtpu.tower)/pbtpu.experts/pbtpu.route/mul",
    "  %convert.9 = f32[8,4]{1,0} convert(%multiply.3)",
    "  ROOT %add.5 = f32[8,4]{1,0} add(%convert.9, %param_1), "
    + _META % "jvp(pbtpu.tower)/pbtpu.experts/pbtpu.route/add",
    "}",
    "",
    "%region_0.2 (a: f32[], b: f32[]) -> f32[] {",
    "  %a = f32[] parameter(0)",
    "  %b = f32[] parameter(1)",
    "  ROOT %add.6 = f32[] add(%a, %b), " + _META % "pbtpu.dense_update/add",
    "}",
    "",
    "%body.3 (carry: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {",
    "  %carry = (s32[], f32[8,4]{1,0}) parameter(0)",
    "  %get-tuple-element.30 = f32[8,4]{1,0} get-tuple-element(%carry), "
    "index=1",
    "  %tanh.31 = f32[8,4]{1,0} tanh(%get-tuple-element.30), "
    + _META % "pbtpu.tower/pbtpu.mixer/while/body/tanh",
    "  %copy.32 = f32[8,4]{1,0} copy(%tanh.31)",
    "  %constant.33 = s32[] constant(1)",
    "  ROOT %tuple.34 = (s32[], f32[8,4]{1,0}) tuple(%constant.33, %copy.32)",
    "}",
    "",
    "%cond.4 (carry.1: (s32[], f32[8,4])) -> pred[] {",
    "  %carry.1 = (s32[], f32[8,4]{1,0}) parameter(0)",
    "  ROOT %constant.40 = pred[] constant(false)",
    "}",
    "",
    "ENTRY %main.20 (Arg_0.1: f32[8,4], Arg_1.2: f32[8,4]) -> (f32[8,4], f32[]) {",
    "  %Arg_0.1 = f32[8,4]{1,0} parameter(0), " + _META % "pbtpu.pull/x",
    "  %Arg_1.2 = f32[8,4]{1,0} parameter(1)",
    "  %constant.4 = f32[] constant(0)",
    "  %copy-start.15 = (f32[8,4]{1,0:S(1)}, f32[8,4]{1,0}, u32[]{:S(2)}) "
    "copy-start(%Arg_1.2)",
    "  %copy-done.15 = f32[8,4]{1,0:S(1)} copy-done(%copy-start.15)",
    "  %fusion.7 = f32[8,4]{1,0:T(8,128)} fusion(%Arg_0.1, %copy-done.15), "
    "kind=kLoop, calls=%fused_computation.1",
    "  %ragged-dot-none.3 = f32[8,4]{1,0} custom-call(%fusion.7, %Arg_1.2), "
    'custom_call_target="tpu_custom_call", '
    'metadata={op_name="ragged-dot-none"}',
    "  %dot.8 = f32[8,4]{1,0} dot(%ragged-dot-none.3, %Arg_1.2), "
    + _META % ("transpose(jvp(pbtpu.tower))/jvp(pbtpu.tower)/checkpoint/"
               "rematted_computation/pbtpu.head_loss/dot_general"),
    "  %jvp_pbtpu_attention_fwd_.1 = (bf16[2,4]{1,0}, f32[2]{0}) "
    "custom-call(%dot.8), custom_call_target=\"tpu_custom_call\", "
    + _META % "jvp(pbtpu.tower)/pbtpu.attention/pbtpu_attention_fwd",
    "  %get-tuple-element.2 = bf16[2,4]{1,0} get-tuple-element("
    "%jvp_pbtpu_attention_fwd_.1), index=0",
    "  %reduce.11 = f32[] reduce(%dot.8, %constant.4), dimensions={0,1}, "
    "to_apply=%region_0.2, " + _META % "pbtpu.dense_update/reduce_sum",
    "  %constant.16 = s32[] constant(0)",
    "  %broadcast.17 = f32[8,4]{1,0} broadcast(%constant.4), dimensions={}",
    "  %tuple.18 = (s32[], f32[8,4]{1,0}) tuple(%constant.16, %broadcast.17)",
    "  %while.19 = (s32[], f32[8,4]{1,0}) while(%tuple.18), "
    "condition=%cond.4, body=%body.3, "
    + _META % "pbtpu.tower/pbtpu.mixer/while",
    "  %copy.12 = f32[8,4]{0,1} copy(%dot.8), " + _META % "add",
    "  %bitcast.13 = f32[8,4]{1,0} bitcast(%copy.12), "
    + _META % "pbtpu.nonesuch/reshape",
    "  ROOT %tuple.14 = (f32[8,4]{1,0}, f32[]) tuple(%bitcast.13, %reduce.11)",
    "}",
])


def test_scopes_of_hlo_reads_the_innermost_registered_scope():
    rows = device_scopes.scopes_of_hlo(CANNED)
    assert device_scopes.module_name(CANNED) == "jit_step"
    # through transpose(jvp(..))/checkpoint/rematted_computation
    assert rows["dot.8"] == {"result": "f32[8,4]{1,0}",
                             "scope": "head_loss"}
    assert rows["jvp_pbtpu_attention_fwd_.1"]["scope"] == "attention"
    assert rows["jvp_pbtpu_attention_fwd_.1"]["result"] == \
        "(bf16[2,4]{1,0}, f32[2]{0})"
    assert rows["reduce.11"]["scope"] == "dense_update"
    # a path of the program outside every scope, and under a name the
    # registry does not hold, stay as they are
    assert rows["copy.12"]["scope"] is None
    assert rows["bitcast.13"]["scope"] is None
    # a loop's body runs instruction by instruction: its rows are events
    assert rows["while.19"]["scope"] == rows["tanh.31"]["scope"] == "mixer"


def test_an_instruction_with_no_path_of_its_own_takes_its_neighbours():
    rows = device_scopes.scopes_of_hlo(CANNED)
    # a fusion: what most of its computation's named instructions carry
    assert rows["fusion.7"] == {"result": "f32[8,4]{1,0:T(8,128)}",
                                "scope": "route"}
    # a prefetch: its user's, through the chain start -> done -> fusion
    assert rows["copy-start.15"]["scope"] == "route"
    assert rows["copy-done.15"]["scope"] == "route"
    # a loop's initial value: the loop's, through the tuple between them;
    # a copy inside the body with no user but the result: what it reads
    assert rows["broadcast.17"]["scope"] == "mixer"
    assert rows["copy.32"]["scope"] == "mixer"
    # the compiler's own name for an expanded grouped product
    assert rows["ragged-dot-none.3"]["scope"] == "experts"


def test_what_is_no_event_is_left_out():
    rows = device_scopes.scopes_of_hlo(CANNED)
    # parameters, constants, tuples and their elements; what lies inside
    # a fusion; a reduction's own computation
    assert set(rows) == {"copy-start.15", "copy-done.15", "fusion.7",
                         "ragged-dot-none.3", "dot.8",
                         "jvp_pbtpu_attention_fwd_.1", "reduce.11",
                         "broadcast.17", "while.19", "tanh.31", "copy.32",
                         "copy.12", "bitcast.13"}


def test_an_unregistered_scope_is_refused_at_trace_time():
    import jax
    import jax.numpy as jnp

    def step(x):
        with monitor.device_scope("nonesuch"):
            return x + 1

    with pytest.raises(ValueError, match="not registered"):
        jax.jit(step).lower(jnp.ones(3))
    for name in names.DEVICE_SCOPE_NAMES:
        with monitor.device_scope(name):
            pass


def test_the_scope_survives_differentiation_and_checkpoint():
    """What the whole mechanism rests on, on this jax: the scope is a
    path component of every instruction's ``op_name`` in the compiled
    text, backward pass and recomputation included."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def layer(w, x):
        with monitor.device_scope("route"):
            return jnp.tanh(x @ w)

    def step(w, x):
        with monitor.device_scope("tower"):
            loss, g = jax.value_and_grad(
                lambda w: jnp.sum(layer(w, x) ** 2))(w)
        with monitor.device_scope("dense_update"):
            return loss, w - 0.1 * g

    table = device_scopes.table_of(
        jax.jit(step), (jnp.ones((16, 16)), jnp.ones((8, 16))))
    rows = table["jit_step"]
    held = {r["scope"] for r in rows.values()}
    assert {"route", "dense_update"} <= held
    assert sum(r["scope"] is None for r in rows.values()) <= len(rows) // 4


# ---------------------------------------------------------------------------
# (2) a pass under a capture leaves the table of the programs it ran
# ---------------------------------------------------------------------------

ENGINE = ("pull", "premerge", "push", "tower", "dense_update", "boundary")
TOWER = ENGINE + ("attention", "route", "experts", "head_loss")
CELLS = {
    "dlrm_mlperf.onehot": ENGINE + ("auc",),
    "smallthinker_21b_ep4.seq8k": TOWER,
    "nemotron3_nano_ep16.seq4k": TOWER + ("mixer", "dense_mlp"),
    "lfm2_24b_a2b_ep8.seq8k": TOWER + ("mixer", "dense_mlp"),
    "kanana2_30b_a3b_ep8.seq16k": TOWER + ("latent", "dense_mlp"),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_captured_pass_leaves_the_table_of_its_programs(cell):
    """Each model at its cell's rehearsal sizes through the day loop: a
    warm-up cycle with no capture open (nothing is kept), then one pass
    under ``jax.profiler``: every scope the model should have holds
    instructions — the differentiated step's hand-written backward
    passes included — and the trainer's own call agrees."""
    import jax
    from benchmark import datagen, sut
    from benchmark.reference import steps
    cfg, mix = rehearsal_cell(cell)
    batch = cfg["trainer"]["global_batch_size"]
    n_sparse, dense_dim = datagen.slot_counts(cfg)
    hot = datagen.slot_hotness(mix, n_sparse)
    passes = datagen.make_passes(mix, n_sparse, dense_dim, batch, 38)
    tmp = tempfile.mkdtemp(prefix="pbtpu_scopes_")
    saved = flags.push_engine
    # the chip's push: the host plan and its premerge, on any backend
    set_flags(push_engine="scatter_accumulate")
    try:
        files = [datagen.write_pass(tmp, tag, p, 2)
                 for tag, p in zip("AB", passes)]
        system = sut.System(cfg, hot, 0,
                            dense_params=steps.initial_params(cfg, 0))
        for f in files:
            system.run_pass(f)
        assert not device_scopes.TABLE and not system.trainer._scope_programs
        errors0 = STATS.snapshot().get("trace.device_scope_errors", 0)
        jax.profiler.start_trace(tmp + "/capture")
        try:
            system.run_pass(files[0])
            system.block()
        finally:
            jax.profiler.stop_trace()
        table = {m: dict(rows) for m, rows in device_scopes.TABLE.items()}
        assert STATS.snapshot().get("trace.device_scope_errors", 0) == errors0
        assert {"jit_step_flat", "jit_apply", "jit_combine"} <= set(table)
        held = {r["scope"] for rows in table.values() for r in rows.values()}
        assert set(CELLS[cell]) <= held, set(CELLS[cell]) - held
        assert held - {None} <= set(names.DEVICE_SCOPE_NAMES)
        # the deferred apply is the push, whole
        assert {r["scope"] for r in table["jit_apply"].values()} \
            <= {"push", "premerge", None}
        assert system.trainer.device_scope_table() == table
        system.free()
    finally:
        set_flags(push_engine=saved)
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# (3) the capture's own reader joins the two
# ---------------------------------------------------------------------------

def test_the_capture_reader_puts_the_devices_time_under_scopes(
        tmp_path, capsys):
    """A capture made with ``flags.trace_device`` has the table beside
    it; ``--device`` prints seconds, share and launches by scope and
    program, and the rows sum to the device-busy seconds it reports."""
    tr, ds = _tiny_trainer(tmp_path)
    tr.train_pass(ds)
    saved = {k: flags.get(k) for k in ("trace", "trace_device",
                                       "trace_device_dir")}
    h = monitor.hub()
    h.enable(monitor.MemorySink())
    set_flags(trace=True, trace_device=True,
              trace_device_dir=str(tmp_path / "cap"))
    try:
        tr.train_pass(ds)
        tr.block_until_ready()
    finally:
        set_flags(**saved)
        h.disable()
    logdir = next((tmp_path / "cap").iterdir())
    xplane = trace_lib.find_xplane(str(logdir))
    beside = json.load(open(xplane.rsplit("/", 1)[0] + "/"
                            + device_scopes.TABLE_FILE))
    assert beside == device_scopes.TABLE and "jit_step_flat" in beside
    assert trace_lib.main(["--device", str(logdir), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    rows = report["scopes"]
    by = {}
    for r in rows:
        by[r["scope"]] = by.get(r["scope"], 0.0) + r["seconds"]
    assert {"tower", "push", "pull", "dense_update", "auc"} <= set(by)
    assert all(r["launches"] > 0 for r in rows)
    assert sum(by.values()) == pytest.approx(
        report["device_busy_capture_s"], rel=0.01)
    assert sum(by.values()) == pytest.approx(
        report["train_pass"]["device_busy_s"], rel=0.01)
    assert sum(r["share"] for r in rows) == pytest.approx(1.0)
    # eager one-op programs of the host's own (the flat state's split at
    # the close) are no program of the table's
    assert {r["program"] for r in rows
            if r["scope"] == trace_lib.UNKNOWN} & {
                "jit_split", "jit_reshape", "jit_dynamic_slice"}
    # the text report holds the table; another table is read on request
    assert trace_lib.main(["--device", str(logdir)]) == 0
    text = capsys.readouterr().out
    assert "device time by scope and program" in text
    assert "jit_step_flat" in text and "dense_update" in text
    other = tmp_path / "other.json"
    other.write_text(json.dumps({}))
    assert trace_lib.main(["--device", str(logdir), "--json",
                           "--scopes", str(other)]) == 0
    alone = json.loads(capsys.readouterr().out)["scopes"]
    assert {r["scope"] for r in alone} == {trace_lib.UNKNOWN}


def test_by_scope_rows():
    ops = {"jit_step": {"fusion.1": [3, 0.3], "copy.2": [3, 0.06],
                        "fusion.9": [1, 0.04]},
           "jit_other": {"fusion.1": [2, 0.1]}}
    table = {"jit_step": {"fusion.1": {"result": "f32[4]", "scope": "pull"},
                          "copy.2": {"result": "f32[4]", "scope": None}}}
    rows = trace_lib.by_scope(ops, table)
    assert [(r["scope"], r["program"], r["launches"]) for r in rows] == [
        ("pull", "jit_step", 3), ("unknown", "jit_other", 2),
        ("unscoped", "jit_step", 3), ("unknown", "jit_step", 1)]
    assert sum(r["seconds"] for r in rows) == pytest.approx(0.5)
    assert rows[0]["share"] == pytest.approx(0.6)


# ---------------------------------------------------------------------------
# (4) with no capture open the mechanism is one static call a pass
# ---------------------------------------------------------------------------

def test_no_capture_no_table_and_one_question_a_pass(tmp_path, monkeypatch):
    import jax
    tr, ds = _tiny_trainer(tmp_path)
    asked = []
    real = jax.profiler.TraceAnnotation.is_enabled

    def is_enabled():
        asked.append(1)
        return real()

    monkeypatch.setattr(jax.profiler.TraceAnnotation, "is_enabled",
                        staticmethod(is_enabled))
    lowered = []
    monkeypatch.setattr(device_scopes, "table_of",
                        lambda *a: lowered.append(a) or {})
    for k in range(3):
        tr.train_pass(ds)
        assert len(asked) == k + 1
    assert not device_scopes.TABLE and not lowered
    assert not tr._scope_programs and tr.device_scope_table() == {}
    assert device_scopes._build is None


def test_a_failed_build_is_counted_and_training_goes_on(tmp_path,
                                                        monkeypatch):
    import jax
    tr, ds = _tiny_trainer(tmp_path)
    tr.train_pass(ds)

    def broken(fn, specs):
        raise RuntimeError("no text")

    monkeypatch.setattr(device_scopes, "table_of", broken)
    monkeypatch.setattr(device_scopes, "_warned", False)
    errors0 = STATS.snapshot().get("trace.device_scope_errors", 0)
    jax.profiler.start_trace(str(tmp_path / "cap"))
    try:
        with pytest.warns(RuntimeWarning, match="device scopes"):
            out = tr.train_pass(ds)
    finally:
        jax.profiler.stop_trace()
    assert out["steps"] == 2
    assert STATS.snapshot()["trace.device_scope_errors"] > errors0
    assert not device_scopes.TABLE and device_scopes._build is None


def test_a_second_captured_pass_lowers_nothing_again(tmp_path, monkeypatch):
    import jax
    tr, ds = _tiny_trainer(tmp_path)
    tr.train_pass(ds)
    built = []
    real = device_scopes.table_of
    monkeypatch.setattr(device_scopes, "table_of",
                        lambda fn, specs: built.append(fn) or real(fn, specs))
    jax.profiler.start_trace(str(tmp_path / "cap"))
    try:
        tr.train_pass(ds)
        first = len(built)
        tr.train_pass(ds)
    finally:
        jax.profiler.stop_trace()
    assert first >= 3 and len(built) == first
    assert "jit_step_flat" in device_scopes.TABLE


def test_a_stale_compile_cache_is_compiled_past(tmp_path):
    """JAX's persistent cache leaves metadata out of its key: a program
    that differs from an older tree's in scopes alone gets that tree's
    executable, with its ``op_name``s. ``table_of`` sees a program traced
    under scopes come back with none and compiles it past the cache."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()

    def program(scoped):
        def step(x, w):
            if scoped:
                with monitor.device_scope("tower"):
                    return jnp.sum(jnp.tanh(x @ w))
            return jnp.sum(jnp.tanh(x @ w))
        return jax.jit(step)

    try:
        x, w = jnp.ones((64, 64)), jnp.ones((64, 64))
        program(False)(x, w).block_until_ready()    # the older tree's
        scoped = program(True)
        scoped(x, w).block_until_ready()            # answered from the cache
        assert "pbtpu." not in scoped.lower(x, w).compile().as_text()
        again0 = STATS.snapshot().get("trace.device_scope_recompiles", 0)
        rows = device_scopes.table_of(scoped, (x, w))["jit_step"]
        assert {r["scope"] for r in rows.values()} == {"tower"}
        assert STATS.snapshot()["trace.device_scope_recompiles"] == again0 + 1
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_the_repos_compile_cache_keys_hold_metadata(tmp_path, monkeypatch):
    """``enable_compile_cache`` puts metadata into the cache's key, so a
    tree whose scopes differ from the tree that filled the cache compiles
    its own programs (and names) in its warm-up instead of reading the
    other's: the scoped twin of a cached program misses."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    from paddlebox_tpu.utils import compile_cache
    names_ = ("jax_compilation_cache_dir",
              "jax_compilation_cache_include_metadata_in_key",
              "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in names_}
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    try:
        assert compile_cache.enable_compile_cache()["from"] == "env"
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()

        def step(x, scoped):
            if scoped:
                with monitor.device_scope("tower"):
                    return jnp.sum(jnp.tanh(x))
            return jnp.sum(jnp.tanh(x))

        x = jnp.ones((32, 32))
        jax.jit(step, static_argnums=1)(x, False).block_until_ready()
        scoped = jax.jit(step, static_argnums=1)
        scoped(x, True).block_until_ready()
        assert "pbtpu.tower" in scoped.lower(x, True).compile().as_text()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
