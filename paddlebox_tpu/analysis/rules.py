"""The pblint rules. Each is grounded in a real prior incident — see
docs/INVARIANTS.md for the incident catalogue and how to add a rule.

A rule is one class: ``id`` (the waiver / --rules name), ``doc`` (one
line for --list-rules), a per-file :meth:`visit_file`, and optionally a
whole-project :meth:`check_project` for facts no single file can
establish. Register new rules in :data:`ALL_RULES`; ship them with a
fixture test in tests/test_pblint.py proving they fire on a violation
and stay quiet on the fixed/waived form, or land them behind a baseline
(``--write-baseline`` / ``--baseline``) when the tree is not yet clean.
"""

from __future__ import annotations

import ast

from paddlebox_tpu.analysis.core import (
    FileContext,
    Finding,
    Project,
    ProjectIndex,
    Rule,
    call_kwarg,
    dotted_name,
    import_aliases,
    iter_calls,
    iter_faultpoint_refs,
    iter_flag_refs,
    module_aliases,
    str_const,
)

# ---------------------------------------------------------------------------
# durable-write
# ---------------------------------------------------------------------------

def _open_write_mode(call: ast.Call) -> str | None:
    """The mode string when this is an ``open(path, "w"/"wb"/...)``."""
    f = call.func
    name = f.id if isinstance(f, ast.Name) else None
    if name != "open":
        return None
    mode_node = call.args[1] if len(call.args) > 1 else call_kwarg(
        call, "mode")
    mode = str_const(mode_node) if mode_node is not None else None
    if mode is not None and ("w" in mode or "x" in mode):
        return mode
    return None


def _atomic_bindings(tree: ast.AST) -> list[tuple[str, int, int]]:
    """(name, first_line, last_line) for every ``with ...atomic_file(...)
    as name`` body — opens of that name inside the body are sanctioned."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        for item in node.items:
            ce = item.context_expr
            if not (isinstance(ce, ast.Call)
                    and isinstance(dotted_name(ce.func), str)
                    and dotted_name(ce.func).split(".")[-1]
                    == "atomic_file"):
                continue
            if isinstance(item.optional_vars, ast.Name):
                out.append((item.optional_vars.id, node.lineno,
                            node.end_lineno or node.lineno))
    return out


def _local_idiom_tmp_names(tree: ast.AST) -> list[tuple[str, int, int]]:
    """(tmp_name, first_line, last_line) per function carrying the
    tmp->fsync->os.replace idiom: only names that are the SOURCE of an
    ``os.replace(tmp, ...)`` in a function that also fsyncs are
    sanctioned — a second raw open to a different final path in the same
    function stays a finding (whole-function sanctioning would pass
    exactly the torn-write class the rule exists to catch)."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        has_fsync = False
        replaced: set[str] = set()
        for call in iter_calls(node):
            d = dotted_name(call.func) or ""
            if d.split(".")[-1] == "fsync":
                has_fsync = True
            if d == "os.replace" and call.args and isinstance(
                    call.args[0], ast.Name):
                replaced.add(call.args[0].id)
        if has_fsync and replaced:
            a, b = node.lineno, node.end_lineno or node.lineno
            out.extend((name, a, b) for name in replaced)
    return out


class DurableWriteRule(Rule):
    id = "durable-write"
    doc = ("raw open(..., 'w'/'wb') in a durability module must flow "
           "through atomic_file / fs_lib.put_replacing or the local "
           "tmp->fsync->os.replace idiom")

    def visit_file(self, ctx: FileContext, index: ProjectIndex,
                   project: Project) -> list[Finding]:
        if not project.in_durability_module(ctx.relpath):
            return []
        bindings = _atomic_bindings(ctx.tree)
        idiom_tmps = _local_idiom_tmp_names(ctx.tree)
        out = []
        for call in iter_calls(ctx.tree):
            mode = _open_write_mode(call)
            if mode is None:
                continue
            target = call.args[0] if call.args else None
            if isinstance(target, ast.Name) and any(
                    target.id == n and a <= call.lineno <= b
                    for n, a, b in bindings):
                continue            # the atomic_file tmp handle
            if isinstance(target, ast.Name) and any(
                    target.id == n and a <= call.lineno <= b
                    for n, a, b in idiom_tmps):
                continue            # local tmp->fsync->os.replace idiom
            out.append(Finding(
                ctx.relpath, call.lineno, self.id,
                f"raw open(..., {mode!r}) in a durability module — a "
                "crash mid-write leaves a torn file under the final "
                "name; route it through utils/checkpoint.atomic_file "
                "(or fs_lib.put_replacing for uploads), or write "
                "tmp -> fsync -> os.replace locally (PR-3 incident: "
                "every snapshot writer was converted to this)"))
        return out


# ---------------------------------------------------------------------------
# faultpoint-registry
# ---------------------------------------------------------------------------

class FaultpointRegistryRule(Rule):
    id = "faultpoint-registry"
    doc = ("every faultpoint hit/arm site names a registered point, and "
           "every registered point is referenced by a test under tests/")

    def visit_file(self, ctx: FileContext, index: ProjectIndex,
                   project: Project) -> list[Finding]:
        if ctx.relpath == project.faultpoint_module:
            return []               # the registry/dispatcher itself
        points = index.all_faultpoints
        if not points and not index.faultpoint_registries:
            return []               # no registry in this project: no rule
        out = []
        for ref in iter_faultpoint_refs(ctx, project):
            if ref.name not in points:
                regs = ", ".join(project.faultpoint_registries)
                out.append(Finding(
                    ctx.relpath, ref.line, self.id,
                    f"faultpoint {ref.name!r} is not in the closed "
                    f"registry ({regs}) — register it in "
                    f"{project.faultpoint_module} so the kill->resume "
                    "matrices cover it (an unregistered crash window is "
                    "an untested crash window)"))
        return out

    def check_project(self, index: ProjectIndex, project: Project,
                      contexts: dict[str, FileContext]) -> list[Finding]:
        out = []
        for point, line in sorted(index.all_faultpoints.items()):
            if not index.point_is_tested(point):
                out.append(Finding(
                    project.faultpoint_module, line, self.id,
                    f"faultpoint {point!r} is registered but no test "
                    f"under {project.tests_dir}/ references it (by "
                    "literal name or by parametrizing over its registry "
                    "tuple) — a registered-but-untested kill point "
                    "proves nothing"))
        return out


# ---------------------------------------------------------------------------
# thread-context
# ---------------------------------------------------------------------------

class ThreadContextRule(Rule):
    id = "thread-context"
    doc = ("threading.Thread outside monitor/context.py loses pass/step "
           "telemetry tagging — use monitor.context.spawn")

    def visit_file(self, ctx: FileContext, index: ProjectIndex,
                   project: Project) -> list[Finding]:
        if ctx.relpath == project.thread_context_module:
            return []               # the sanctioned wrapper itself
        mod_names = module_aliases(ctx, "threading")
        fn_aliases = import_aliases(ctx, "threading", ("Thread",))
        out = []
        for call in iter_calls(ctx.tree):
            f = call.func
            is_thread = (isinstance(f, ast.Attribute)
                         and f.attr == "Thread"
                         and dotted_name(f.value) in mod_names) or (
                isinstance(f, ast.Name) and f.id in fn_aliases)
            if is_thread:
                out.append(Finding(
                    ctx.relpath, call.lineno, self.id,
                    "raw threading.Thread starts with an EMPTY "
                    "contextvars context, so telemetry from the worker "
                    "loses its pass/step tags (PR-4 incident: pack/"
                    "stager/dump threads emitted untagged events) — "
                    "spawn through monitor.context.spawn, or waive with "
                    "the reason the thread must not inherit context"))
        return out


# ---------------------------------------------------------------------------
# donefile-discipline
# ---------------------------------------------------------------------------

def _walk_values(node: ast.AST):
    """ast.walk, but skipping every Call's ``func`` subtree: a method
    NAMED after donefiles (``_read_donefile_raw()``) reads one, it does
    not make its result a donefile *path* — only literals, names, and
    value attributes carry path taint."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if isinstance(n, ast.Call) and child is n.func:
                continue
            stack.append(child)


def _mentions_donefile(node: ast.AST, tainted: "set[str] | None" = None
                       ) -> bool:
    for sub in _walk_values(node):
        lit = str_const(sub)
        if lit is not None and "donefile" in lit.lower():
            return True
        if isinstance(sub, ast.Name) and (
                "donefile" in sub.id.lower()
                or (tainted and sub.id in tainted)):
            return True
        if isinstance(sub, ast.Attribute) and (
                "donefile" in sub.attr.lower()):
            return True
    return False


def _donefile_ish_names(tree: ast.AST) -> set[str]:
    """Names (module- or function-local) assigned from expressions that
    mention a donefile — two propagation passes so ``alt = f"{path}.x"``
    chains resolve."""
    names: set[str] = set()
    for _ in range(2):
        for node in ast.walk(tree):
            tgt = None
            if isinstance(node, ast.Assign) and len(
                    node.targets) == 1 and isinstance(
                    node.targets[0], ast.Name):
                tgt, val = node.targets[0].id, node.value
            elif isinstance(node, ast.AnnAssign) and isinstance(
                    node.target, ast.Name) and node.value is not None:
                tgt, val = node.target.id, node.value
            if tgt and _mentions_donefile(val, names):
                names.add(tgt)
    return names


class DonefileDisciplineRule(Rule):
    id = "donefile-discipline"
    doc = ("only fleet/fleet_util.py (and its append_donefile API) may "
           "write a *donefile* target — the one announce channel")

    # (call shape) -> index of the TARGET argument
    _ATTR_TARGETS = {"write_text": 0, "put": 1}
    _DOTTED_TARGETS = {"os.replace": 1, "os.rename": 1,
                       "shutil.copy": 1, "shutil.copy2": 1,
                       "shutil.copyfile": 1, "shutil.move": 1}

    def visit_file(self, ctx: FileContext, index: ProjectIndex,
                   project: Project) -> list[Finding]:
        if ctx.relpath in project.donefile_writers:
            return []
        tainted = _donefile_ish_names(ctx.tree)

        def is_donefile_target(node: ast.AST) -> bool:
            return _mentions_donefile(node, tainted)

        out = []
        for call in iter_calls(ctx.tree):
            f = call.func
            target: ast.AST | None = None
            # open(path, "w"/"a"/...)
            if isinstance(f, ast.Name) and f.id == "open" and call.args:
                mode_node = call.args[1] if len(
                    call.args) > 1 else call_kwarg(call, "mode")
                mode = (str_const(mode_node) or "r"
                        ) if mode_node is not None else "r"
                if "w" in mode or "a" in mode or "x" in mode or (
                        "+" in mode):
                    target = call.args[0]
            elif isinstance(f, ast.Attribute):
                if f.attr == project.donefile_appender:
                    continue        # the sanctioned API
                d = dotted_name(f)
                if d in self._DOTTED_TARGETS:
                    i = self._DOTTED_TARGETS[d]
                    target = call.args[i] if len(call.args) > i else None
                elif f.attr in self._ATTR_TARGETS:
                    i = self._ATTR_TARGETS[f.attr]
                    target = call.args[i] if len(call.args) > i else None
                elif f.attr == "put_replacing":
                    target = call.args[2] if len(call.args) > 2 else None
            elif isinstance(f, ast.Name) and f.id == "put_replacing":
                target = call.args[2] if len(call.args) > 2 else None
            if target is not None and is_donefile_target(target):
                writers = ", ".join(project.donefile_writers)
                out.append(Finding(
                    ctx.relpath, call.lineno, self.id,
                    "write to a *donefile* target outside the "
                    f"sanctioned writer ({writers}) — donefile lines "
                    "are the ONLY model-visibility channel and must "
                    f"ride FleetUtil.{project.donefile_appender} "
                    "(append-after-commit, crash-replay dedup; PR-7 "
                    "made this 'donefile discipline in ONE place')"))
        return out


# ---------------------------------------------------------------------------
# flag-audit
# ---------------------------------------------------------------------------

class FlagAuditRule(Rule):
    id = "flag-audit"
    doc = ("every flags.X read resolves to a config.py field, and every "
           "field is read somewhere — no phantom or dead flags")

    def visit_file(self, ctx: FileContext, index: ProjectIndex,
                   project: Project) -> list[Finding]:
        if not index.flags_fields:
            return []
        out = []
        for ref in iter_flag_refs(ctx, project):
            if ref.name not in index.flags_fields:
                out.append(Finding(
                    ctx.relpath, ref.line, self.id,
                    f"flags.{ref.name} does not resolve to a field of "
                    f"{project.flags_class} in {project.flags_module} — "
                    "a phantom flag reads as a typo'd knob that "
                    "silently never engages (the registry is closed, "
                    "like the reference's flags.cc)"))
        return out

    def check_project(self, index: ProjectIndex, project: Project,
                      contexts: dict[str, FileContext]) -> list[Finding]:
        out = []
        for field, line in sorted(index.flags_fields.items()):
            if not index.flag_reads.get(field):
                out.append(Finding(
                    project.flags_module, line, self.id,
                    f"flag {field!r} is never read anywhere (package, "
                    "tests, examples) — a dead flag documents "
                    "behavior the code does not have; remove it, wire "
                    "it, or waive naming the future consumer"))
        return out


# ---------------------------------------------------------------------------
# event-registry
# ---------------------------------------------------------------------------

class EventRegistryRule(Rule):
    id = "event-registry"
    doc = ("every hub event/span name emitted in the tree must be in the "
           "closed registry (monitor/names.py) — no forked telemetry "
           "namespace")

    def visit_file(self, ctx: FileContext, index: ProjectIndex,
                   project: Project) -> list[Finding]:
        if ctx.relpath == project.event_registry_module:
            return []               # the registry itself
        names = index.all_event_names
        if not names:
            return []               # no registry in this project: no rule
        fn_aliases = (
            import_aliases(ctx, "paddlebox_tpu.monitor",
                           ("event", "span"))
            | import_aliases(ctx, "paddlebox_tpu.monitor.hub",
                             ("event", "span")))
        out = []
        for call in iter_calls(ctx.tree):
            f = call.func
            is_emit = (isinstance(f, ast.Attribute)
                       and f.attr in ("event", "span")) or (
                isinstance(f, ast.Name) and f.id in fn_aliases)
            if not is_emit:
                continue
            arg = call.args[0] if call.args else call_kwarg(call, "name")
            lit = str_const(arg) if arg is not None else None
            if lit is None:
                out.append(Finding(
                    ctx.relpath, call.lineno, self.id,
                    "event/span name is not a string literal — the "
                    "registry check cannot see it (dashboards, doctor "
                    "rules, and the world-trace merger key off names "
                    "verbatim); emit a literal registered in "
                    f"{project.event_registry_module}, or waive naming "
                    "the registered names the expression takes"))
            elif lit not in names:
                regs = ", ".join(project.event_registries)
                out.append(Finding(
                    ctx.relpath, call.lineno, self.id,
                    f"event/span name {lit!r} is not in the closed "
                    f"registry ({regs} in "
                    f"{project.event_registry_module}) — an unregistered "
                    "name silently forks the telemetry namespace every "
                    "consumer greps (register it next to the consumer "
                    "that reads it)"))
        return out


# ---------------------------------------------------------------------------
# silent-except
# ---------------------------------------------------------------------------

class SilentExceptRule(Rule):
    id = "silent-except"
    doc = ("`except ...: pass` without a telemetry event swallows "
           "errors invisibly — count/log it, or waive with the reason "
           "silence is correct")

    def visit_file(self, ctx: FileContext, index: ProjectIndex,
                   project: Project) -> list[Finding]:
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            body = [s for s in node.body
                    if not (isinstance(s, ast.Expr)
                            and str_const(s.value) is not None)]
            if len(body) == 1 and isinstance(body[0], ast.Pass):
                out.append(Finding(
                    ctx.relpath, node.lineno, self.id,
                    "silent `except: pass` — the swallowed error leaves "
                    "no counter, no event, no trace (the PR-7 "
                    "malformed-donefile incident: a torn line was "
                    "re-swallowed every poll); emit a telemetry "
                    "counter/event, or waive stating why silence is "
                    "the correct behavior here"))
        return out


ALL_RULES: tuple[type[Rule], ...] = (
    DurableWriteRule,
    FaultpointRegistryRule,
    ThreadContextRule,
    DonefileDisciplineRule,
    FlagAuditRule,
    EventRegistryRule,
    SilentExceptRule,
)
