"""pblint rule framework: file contexts, cross-file index, waivers, baseline.

Stdlib-only by design (``ast`` + ``tokenize``): the lint gate must run on a
bare CPU box without importing jax or any package module it checks — a
linter that needs the full training stack up cannot gate a broken tree.

Vocabulary:

- :class:`FileContext` — one parsed source file: AST, repo-relative path,
  and the waivers extracted from its comments.
- :class:`Project` — where the project's load-bearing files live (flags
  registry, faultpoint registry, donefile writer, durability modules).
  Defaults describe this repository; tests construct fixture projects.
- :class:`ProjectIndex` — the cross-file facts rules consult: flag fields
  and every read of them, faultpoint registries and every hit site, the
  string literals and registry references appearing under ``tests/``.
- :class:`Rule` — per-file visitor (:meth:`Rule.visit_file`) plus an
  optional whole-project check (:meth:`Rule.check_project`) for facts no
  single file can establish (dead flags, untested kill points).

Waivers: ``# pblint: disable=<rule>[,<rule>] -- <reason>`` — trailing on
the offending line, or standalone on the line(s) immediately above it.
The reason is mandatory; a waiver without one raises a ``bad-waiver``
finding AND does not suppress anything, so a waiver can never be cheaper
than a fix without leaving a recorded why.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import json
import os
import re
import tokenize
from typing import Iterable, Iterator

# rules synthesized by the framework itself (waiver problems, unparseable
# files) — always active, not subject to --rules selection
BAD_WAIVER = "bad-waiver"
PARSE_ERROR = "parse-error"

_WAIVER_RE = re.compile(
    r"#\s*pblint:\s*disable=([A-Za-z0-9_,\-]+)"  # rule list
    r"(?:\s*--\s*(?P<reason>.*\S))?\s*$")        # mandatory reason


@dataclasses.dataclass(frozen=True, order=True)
class Finding:
    """One rule violation at one source location."""

    file: str          # repo-relative, forward slashes
    line: int
    rule: str
    message: str

    def key(self) -> tuple[str, str, str]:
        """Baseline identity: line numbers drift, (file, rule, message)
        survives unrelated edits above the finding."""
        return (self.file, self.rule, self.message)

    def render(self) -> str:
        return f"{self.file}:{self.line} {self.rule} {self.message}"


@dataclasses.dataclass
class Project:
    """Where the linted project keeps its load-bearing files.

    All paths are repo-relative with forward slashes; entries ending in
    ``/`` match as directory prefixes. Defaults describe this repository;
    tests build fixture projects in tmp dirs with the same shape.
    """

    root: str
    package: str = "paddlebox_tpu"
    durability_modules: tuple[str, ...] = (
        "paddlebox_tpu/utils/checkpoint.py",
        "paddlebox_tpu/utils/pass_ckpt.py",
        "paddlebox_tpu/serving/artifact.py",
        "paddlebox_tpu/embedding/store.py",
        "paddlebox_tpu/embedding/spill_store.py",
        "paddlebox_tpu/data/archive.py",
        "paddlebox_tpu/fleet/",
    )
    thread_context_module: str = "paddlebox_tpu/monitor/context.py"
    donefile_writers: tuple[str, ...] = ("paddlebox_tpu/fleet/fleet_util.py",)
    donefile_appender: str = "append_donefile"
    flags_module: str = "paddlebox_tpu/config.py"
    flags_class: str = "Flags"
    faultpoint_module: str = "paddlebox_tpu/utils/faultpoint.py"
    faultpoint_registries: tuple[str, ...] = (
        "POINTS", "ELASTIC_POINTS", "SERVING_POINTS", "EXCHANGE_POINTS",
        "MONITOR_POINTS")
    # closed hub event/span NAME registry (monitor/names.py) — the
    # event-registry rule checks every literal monitor.event/span site
    # against the union of these tuples
    event_registry_module: str = "paddlebox_tpu/monitor/names.py"
    event_registries: tuple[str, ...] = ("EVENT_NAMES", "SPAN_NAMES")
    tests_dir: str = "tests"
    # extra trees indexed for *references* (flag reads, faultpoint names)
    # but never linted themselves
    aux_reference_paths: tuple[str, ...] = ("examples",)

    @classmethod
    def discover(cls, start: str, package: str = "paddlebox_tpu"
                 ) -> "Project":
        """Walk up from ``start`` to the directory holding the package's
        flags module — that directory is the repo root."""
        d = os.path.abspath(start)
        if os.path.isfile(d):
            d = os.path.dirname(d)
        while True:
            if os.path.isfile(os.path.join(d, package, "config.py")):
                return cls(root=d, package=package)
            parent = os.path.dirname(d)
            if parent == d:
                # no marker found: fall back to the start directory so
                # relpaths are at least stable
                return cls(root=os.path.abspath(start) if os.path.isdir(
                    start) else os.path.dirname(os.path.abspath(start)),
                    package=package)
            d = parent

    def relpath(self, abspath: str) -> str:
        return os.path.relpath(os.path.abspath(abspath),
                               self.root).replace(os.sep, "/")

    def in_durability_module(self, relpath: str) -> bool:
        for m in self.durability_modules:
            if (relpath == m) or (m.endswith("/") and relpath.startswith(m)):
                return True
        return False


class FileContext:
    """One parsed source file + its waivers."""

    def __init__(self, abspath: str, relpath: str, source: str,
                 tree: ast.AST, waivers: dict[int, dict[str, str]],
                 waiver_problems: list[Finding]):
        self.abspath = abspath
        self.relpath = relpath
        self.source = source
        self.tree = tree
        # line -> {rule: reason}
        self.waivers = waivers
        self.waiver_problems = waiver_problems
        self._imports: "list[tuple[str, str, str | None, str]] | None" \
            = None

    @property
    def import_table(self) -> "list[tuple[str, str, str | None, str]]":
        """(kind, module, name, local_alias) rows, computed once — every
        alias question is a scan of this instead of an ast.walk."""
        if self._imports is None:
            rows: list = []
            for node in ast.walk(self.tree):
                if isinstance(node, ast.Import):
                    for a in node.names:
                        rows.append(("import", a.name, None,
                                     a.asname or a.name))
                elif isinstance(node, ast.ImportFrom) and node.module:
                    for a in node.names:
                        rows.append(("from", node.module, a.name,
                                     a.asname or a.name))
            self._imports = rows
        return self._imports

    @classmethod
    def parse(cls, abspath: str, relpath: str,
              known_rules: Iterable[str]) -> "FileContext | Finding":
        try:
            with open(abspath, encoding="utf-8", errors="replace") as f:
                source = f.read()
            tree = ast.parse(source, filename=relpath)
        except (SyntaxError, ValueError, OSError) as e:
            return Finding(relpath, getattr(e, "lineno", None) or 1,
                           PARSE_ERROR, f"cannot lint: {e}")
        waivers, problems = _parse_waivers(source, relpath,
                                           set(known_rules))
        return cls(abspath, relpath, source, tree, waivers, problems)

    def waiver_for(self, rule: str, line: int) -> str | None:
        """The waiver reason covering (rule, line), or None."""
        w = self.waivers.get(line)
        if w is None:
            return None
        return w.get(rule)


def _parse_waivers(source: str, relpath: str, known_rules: set[str]
                   ) -> tuple[dict[int, dict[str, str]], list[Finding]]:
    """Extract ``# pblint: disable=...`` comments.

    A trailing comment waives its own line; a standalone comment line
    waives the next line that carries code (so a waiver can sit above a
    long statement without blowing the line length).
    """
    comments: list[tuple[int, bool, str]] = []   # (line, standalone, text)
    code_lines: set[int] = set()
    try:
        toks = list(tokenize.generate_tokens(io.StringIO(source).readline))
    except (tokenize.TokenizeError, IndentationError, SyntaxError):
        return {}, []
    for tok in toks:
        if tok.type == tokenize.COMMENT:
            prefix = tok.line[:tok.start[1]]
            comments.append((tok.start[0], not prefix.strip(),
                             tok.string))
        elif tok.type not in (tokenize.NL, tokenize.NEWLINE,
                              tokenize.INDENT, tokenize.DEDENT,
                              tokenize.ENDMARKER):
            for ln in range(tok.start[0], tok.end[0] + 1):
                code_lines.add(ln)

    waivers: dict[int, dict[str, str]] = {}
    problems: list[Finding] = []
    for line, standalone, text in comments:
        m = _WAIVER_RE.search(text)
        if m is None:
            if "pblint:" in text:
                problems.append(Finding(
                    relpath, line, BAD_WAIVER,
                    "unrecognized pblint comment (want `# pblint: "
                    "disable=<rule>[,<rule>] -- <reason>`): "
                    f"{text.strip()[:80]!r}"))
            continue
        rules = tuple(r.strip() for r in m.group(1).split(",") if r.strip())
        reason = m.group("reason") or ""
        bad = False
        if not reason.strip():
            problems.append(Finding(
                relpath, line, BAD_WAIVER,
                f"waiver for {','.join(rules)} has no reason — the reason "
                "is mandatory (`-- <why>`); the waiver is NOT honored"))
            bad = True
        unknown = [r for r in rules if r not in known_rules]
        if unknown:
            problems.append(Finding(
                relpath, line, BAD_WAIVER,
                f"waiver names unknown rule(s) {', '.join(unknown)} "
                f"(known: {', '.join(sorted(known_rules))})"))
            bad = True
        if bad:
            continue
        target = line
        if standalone:
            later = [ln for ln in code_lines if ln > line]
            if not later:
                continue
            target = min(later)
        slot = waivers.setdefault(target, {})
        for r in rules:
            slot[r] = reason.strip()
    return waivers, problems


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def dotted_name(node: ast.AST) -> str | None:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def str_const(node: ast.AST) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def call_kwarg(call: ast.Call, name: str) -> ast.expr | None:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def iter_calls(tree: ast.AST) -> Iterator[ast.Call]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            yield node


def import_aliases(ctx: "FileContext", module: str, names: Iterable[str]
                   ) -> dict[str, str]:
    """Local alias -> canonical name, for ``from <module> import <name>
    [as alias]`` over the given names."""
    want = set(names)
    out: dict[str, str] = {}
    for kind, mod, name, alias in ctx.import_table:
        if kind == "from" and mod == module and name in want:
            out[alias] = name
    return out


def module_aliases(ctx: "FileContext", module: str) -> set[str]:
    """Dotted prefixes under which ``module`` is reachable in this file:
    handles ``import m``, ``import m as x``, ``from pkg import leaf``."""
    head, _, leaf = module.rpartition(".")
    out: set[str] = set()
    for kind, mod, name, alias in ctx.import_table:
        if kind == "import" and mod == module:
            out.add(alias)
        elif kind == "from" and leaf and mod == head and name == leaf:
            out.add(alias)
    return out


# ---------------------------------------------------------------------------
# flag / faultpoint reference extraction (shared by index + rules)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlagRef:
    name: str
    line: int
    is_read: bool


def flag_object_prefixes(ctx: FileContext, project: Project) -> set[str]:
    """Dotted names under which this file can reach the flags object."""
    pkg = project.package
    cfg_mod = f"{pkg}.config"
    prefixes: set[str] = set()
    for alias, canon in import_aliases(ctx, cfg_mod,
                                       ("flags",)).items():
        prefixes.add(alias)
    for alias, canon in import_aliases(ctx, pkg, ("flags",)).items():
        prefixes.add(alias)
    for mod_alias in module_aliases(ctx, cfg_mod):
        prefixes.add(f"{mod_alias}.flags")
    for mod_alias in module_aliases(ctx, pkg):
        prefixes.add(f"{mod_alias}.flags")
    return prefixes


_FLAGS_METHODS = ("set", "get", "from_env")


def iter_flag_refs(ctx: FileContext, project: Project
                   ) -> Iterator[FlagRef]:
    """Every reference to a flags-registry field in this file: attribute
    loads/stores on the flags object, literal ``flags.get/set`` names,
    and ``set_flags(name=...)`` keywords."""
    prefixes = flag_object_prefixes(ctx, project)
    set_flags_aliases = set(import_aliases(
        ctx, f"{project.package}.config", ("set_flags",)))
    cfg_mod_aliases = module_aliases(ctx, f"{project.package}.config")
    if not prefixes and not set_flags_aliases and not cfg_mod_aliases:
        return
    method_call_funcs: set[int] = set()
    for call in iter_calls(ctx.tree):
        f = call.func
        # flags.get("x") / flags.set("x", v)
        if (isinstance(f, ast.Attribute) and f.attr in ("get", "set")
                and dotted_name(f.value) in prefixes):
            method_call_funcs.add(id(f))
            lit = str_const(call.args[0]) if call.args else None
            if lit is not None:
                yield FlagRef(lit, call.lineno, f.attr == "get")
        # set_flags(a=..., b=...) — by from-import alias or module attr
        is_set_flags = (isinstance(f, ast.Name)
                        and f.id in set_flags_aliases) or (
            isinstance(f, ast.Attribute) and f.attr == "set_flags"
            and dotted_name(f.value) in cfg_mod_aliases)
        if is_set_flags:
            for kw in call.keywords:
                if kw.arg:
                    yield FlagRef(kw.arg, call.lineno, False)
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Attribute) and id(node) not in
                method_call_funcs and dotted_name(node.value) in prefixes):
            if node.attr in _FLAGS_METHODS or node.attr.startswith("__"):
                continue
            yield FlagRef(node.attr, node.lineno,
                          isinstance(node.ctx, ast.Load))


@dataclasses.dataclass(frozen=True)
class FaultpointRef:
    name: str
    line: int


def iter_faultpoint_refs(ctx: FileContext, project: Project
                         ) -> Iterator[FaultpointRef]:
    """Literal faultpoint names used in this file: ``hit("x")`` /
    ``arm("x")`` (direct or via the module), and ``fault_point="x"``
    keywords on any call (the atomic_file / write_manifest plumbing).
    Non-literal names are skipped — they are forwarding plumbing, and
    their literal sources are checked at the caller."""
    fp_mod = f"{project.package}.utils.faultpoint"
    fn_aliases = import_aliases(ctx, fp_mod, ("hit", "arm"))
    mod_names = module_aliases(ctx, fp_mod)
    for call in iter_calls(ctx.tree):
        f = call.func
        is_hit = (isinstance(f, ast.Name) and f.id in fn_aliases) or (
            isinstance(f, ast.Attribute) and f.attr in ("hit", "arm")
            and dotted_name(f.value) in mod_names)
        if is_hit and call.args:
            lit = str_const(call.args[0])
            if lit is not None:
                yield FaultpointRef(lit, call.lineno)
        kw = call_kwarg(call, "fault_point")
        if kw is not None:
            lit = str_const(kw)
            if lit is not None:
                yield FaultpointRef(lit, call.lineno)


# ---------------------------------------------------------------------------
# cross-file index
# ---------------------------------------------------------------------------

class ProjectIndex:
    """Cross-file facts: built once over lint targets + reference trees."""

    def __init__(self) -> None:
        self.flags_fields: dict[str, int] = {}      # field -> config.py line
        self.flag_reads: dict[str, list[tuple[str, int]]] = {}
        self.faultpoint_registries: dict[str, dict[str, int]] = {}
        self.faultpoint_sites: dict[str, list[tuple[str, int]]] = {}
        self.event_registries: dict[str, dict[str, int]] = {}
        self.test_literals: set[str] = set()
        self.test_registry_refs: set[str] = set()

    @property
    def all_faultpoints(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for reg in self.faultpoint_registries.values():
            out.update(reg)
        return out

    def point_registries(self, point: str) -> list[str]:
        return [name for name, reg in self.faultpoint_registries.items()
                if point in reg]

    def point_is_tested(self, point: str) -> bool:
        """A point is test-referenced when its exact name appears as a
        string literal under tests/, or a test references a registry
        tuple the point is a member of (the kill matrices parametrize
        over the closed registries — that IS per-member coverage)."""
        if point in self.test_literals:
            return True
        return any(r in self.test_registry_refs
                   for r in self.point_registries(point))

    # ---- builders --------------------------------------------------------

    def add_flags_module(self, ctx: FileContext, project: Project) -> None:
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.ClassDef)
                    and node.name == project.flags_class):
                for stmt in node.body:
                    tgt = None
                    if isinstance(stmt, ast.AnnAssign) and isinstance(
                            stmt.target, ast.Name):
                        tgt = stmt.target.id
                    elif isinstance(stmt, ast.Assign) and len(
                            stmt.targets) == 1 and isinstance(
                            stmt.targets[0], ast.Name):
                        tgt = stmt.targets[0].id
                    if tgt and not tgt.startswith("_"):
                        self.flags_fields[tgt] = stmt.lineno
                break

    def add_faultpoint_module(self, ctx: FileContext,
                              project: Project) -> None:
        for node in ctx.tree.body if isinstance(
                ctx.tree, ast.Module) else []:
            tgt = None
            value = None
            if isinstance(node, ast.AnnAssign) and isinstance(
                    node.target, ast.Name):
                tgt, value = node.target.id, node.value
            elif isinstance(node, ast.Assign) and len(
                    node.targets) == 1 and isinstance(
                    node.targets[0], ast.Name):
                tgt, value = node.targets[0].id, node.value
            if tgt in project.faultpoint_registries and isinstance(
                    value, (ast.Tuple, ast.List)):
                reg = self.faultpoint_registries.setdefault(tgt, {})
                for el in value.elts:
                    lit = str_const(el)
                    if lit is not None:
                        reg[lit] = el.lineno

    @property
    def all_event_names(self) -> "set[str]":
        out: set = set()
        for reg in self.event_registries.values():
            out.update(reg)
        return out

    def add_event_registry_module(self, ctx: FileContext,
                                  project: Project) -> None:
        """Collect the closed hub event/span name registry — the same
        module-level-tuple shape as the faultpoint registries."""
        for node in ctx.tree.body if isinstance(
                ctx.tree, ast.Module) else []:
            tgt = None
            value = None
            if isinstance(node, ast.AnnAssign) and isinstance(
                    node.target, ast.Name):
                tgt, value = node.target.id, node.value
            elif isinstance(node, ast.Assign) and len(
                    node.targets) == 1 and isinstance(
                    node.targets[0], ast.Name):
                tgt, value = node.targets[0].id, node.value
            if tgt in project.event_registries and isinstance(
                    value, (ast.Tuple, ast.List, ast.Set)):
                reg = self.event_registries.setdefault(tgt, {})
                for el in value.elts:
                    lit = str_const(el)
                    if lit is not None:
                        reg[lit] = el.lineno

    def add_reference_file(self, ctx: FileContext, project: Project
                           ) -> None:
        for ref in iter_flag_refs(ctx, project):
            if ref.is_read:
                self.flag_reads.setdefault(ref.name, []).append(
                    (ctx.relpath, ref.line))
        for ref in iter_faultpoint_refs(ctx, project):
            self.faultpoint_sites.setdefault(ref.name, []).append(
                (ctx.relpath, ref.line))

    def add_test_file(self, ctx: FileContext, project: Project) -> None:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Constant) and isinstance(
                    node.value, str):
                self.test_literals.add(node.value)
            elif isinstance(node, ast.Name) and (
                    node.id in project.faultpoint_registries):
                self.test_registry_refs.add(node.id)
            elif isinstance(node, ast.Attribute) and (
                    node.attr in project.faultpoint_registries):
                self.test_registry_refs.add(node.attr)
        # tests reference flags too (set_flags in fixtures): count reads
        self.add_reference_file(ctx, project)


# ---------------------------------------------------------------------------
# rules base + linter
# ---------------------------------------------------------------------------

class Rule:
    """One invariant. ``id`` is the waiver/CLI name; ``doc`` one line."""

    id: str = ""
    doc: str = ""

    def visit_file(self, ctx: FileContext, index: ProjectIndex,
                   project: Project) -> list[Finding]:
        return []

    def check_project(self, index: ProjectIndex, project: Project,
                      contexts: dict[str, FileContext]) -> list[Finding]:
        return []


@dataclasses.dataclass
class LintResult:
    findings: list[Finding]                    # unwaived, unbaselined
    waived: list[tuple[Finding, str]]          # (finding, reason)
    baselined: list[Finding]
    files_linted: int

    @property
    def clean(self) -> bool:
        return not self.findings


def _iter_py_files(path: str) -> Iterator[str]:
    if os.path.isfile(path):
        if path.endswith(".py"):
            yield path
        return
    for dirpath, dirnames, filenames in os.walk(path):
        dirnames[:] = [d for d in sorted(dirnames)
                       if d != "__pycache__" and not d.startswith(".")]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


class Linter:
    def __init__(self, project: Project, rules: "list[Rule] | None" = None):
        from paddlebox_tpu.analysis.rules import ALL_RULES
        self.project = project
        self.rules = list(rules) if rules is not None else [
            r() for r in ALL_RULES]
        self.rule_ids = {r.id for r in self.rules}

    def _known_waiver_rules(self) -> set[str]:
        # every shipped rule is waivable by name even when --rules narrows
        # the active set — a narrowed run must not misreport the other
        # rules' waivers as unknown
        from paddlebox_tpu.analysis.rules import ALL_RULES
        return {r.id for r in ALL_RULES} | {BAD_WAIVER, PARSE_ERROR}

    def lint(self, paths: Iterable[str],
             baseline: "set[tuple[str, str, str]] | None" = None
             ) -> LintResult:
        project = self.project
        known = self._known_waiver_rules()

        # 1. parse lint targets. Relative paths resolve against the repo
        # root first (the gate's convention), then the CWD; a path that
        # matches NOTHING is an error — a gate that silently lints zero
        # files would report a false green on a typo'd invocation.
        contexts: dict[str, FileContext] = {}
        hard_findings: list[Finding] = []
        for path in paths:
            if os.path.isabs(path):
                resolved = path
            else:
                resolved = os.path.join(project.root, path)
                if not os.path.exists(resolved) and os.path.exists(path):
                    resolved = os.path.abspath(path)
            matched = False
            for f in _iter_py_files(resolved):
                matched = True
                rel = project.relpath(f)
                if rel in contexts:
                    continue
                got = FileContext.parse(f, rel, known)
                if isinstance(got, Finding):
                    hard_findings.append(got)
                else:
                    contexts[rel] = got
            if not matched:
                raise FileNotFoundError(
                    f"lint path {path!r} matched no .py files (looked at "
                    f"{resolved}) — refusing to report a clean run over "
                    "nothing")

        # 2. parse reference-only trees (tests, examples) and any
        # load-bearing module not among the targets
        index = ProjectIndex()
        ref_contexts: dict[str, FileContext] = {}

        def _ref_ctx(rel: str) -> FileContext | None:
            if rel in contexts:
                return contexts[rel]
            if rel in ref_contexts:
                return ref_contexts[rel]
            ab = os.path.join(project.root, rel)
            if not os.path.isfile(ab):
                return None
            got = FileContext.parse(ab, rel, known)
            if isinstance(got, Finding):
                return None
            ref_contexts[rel] = got
            return got

        fctx = _ref_ctx(project.flags_module)
        if fctx is not None:
            index.add_flags_module(fctx, project)
        pctx = _ref_ctx(project.faultpoint_module)
        if pctx is not None:
            index.add_faultpoint_module(pctx, project)
        ectx = _ref_ctx(project.event_registry_module)
        if ectx is not None:
            index.add_event_registry_module(ectx, project)

        for ctx in contexts.values():
            index.add_reference_file(ctx, project)
        for aux in project.aux_reference_paths:
            ab = os.path.join(project.root, aux)
            if not os.path.exists(ab):
                continue
            for f in _iter_py_files(ab):
                ctx = _ref_ctx(project.relpath(f))
                if ctx is not None and ctx.relpath not in contexts:
                    index.add_reference_file(ctx, project)
        tests_ab = os.path.join(project.root, project.tests_dir)
        if os.path.isdir(tests_ab):
            for f in _iter_py_files(tests_ab):
                ctx = _ref_ctx(project.relpath(f))
                if ctx is not None:
                    index.add_test_file(ctx, project)

        # 3. run rules
        raw: list[Finding] = list(hard_findings)
        for ctx in contexts.values():
            raw.extend(ctx.waiver_problems)
            for rule in self.rules:
                raw.extend(rule.visit_file(ctx, index, project))
        for rule in self.rules:
            for f in rule.check_project(index, project, contexts):
                # project-level findings anchor at a file; only report
                # them when that file is being linted (linting one leaf
                # file must not surface whole-repo findings)
                if f.file in contexts:
                    raw.append(f)

        # 4. waivers + baseline
        findings: list[Finding] = []
        waived: list[tuple[Finding, str]] = []
        baselined: list[Finding] = []
        for f in sorted(set(raw)):
            ctx = contexts.get(f.file)
            reason = ctx.waiver_for(f.rule, f.line) if ctx else None
            if reason is not None and f.rule not in (BAD_WAIVER,
                                                     PARSE_ERROR):
                waived.append((f, reason))
            elif baseline and f.key() in baseline:
                baselined.append(f)
            else:
                findings.append(f)
        return LintResult(findings, waived, baselined, len(contexts))


# ---------------------------------------------------------------------------
# baseline — machine-readable accepted-findings snapshot
# ---------------------------------------------------------------------------

BASELINE_VERSION = 1


def load_baseline(path: str) -> set[tuple[str, str, str]]:
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    if doc.get("version") != BASELINE_VERSION:
        raise ValueError(f"baseline {path}: version {doc.get('version')!r} "
                         f"(want {BASELINE_VERSION})")
    return {(e["file"], e["rule"], e["message"])
            for e in doc.get("findings", [])}


def baseline_doc(findings: Iterable[Finding],
                 rule_ids: Iterable[str]) -> dict:
    return {
        "version": BASELINE_VERSION,
        "tool": "pblint",
        "rules": sorted(rule_ids),
        "findings": [
            {"file": f.file, "line": f.line, "rule": f.rule,
             "message": f.message}
            for f in sorted(findings)],
    }


def write_baseline(path: str, findings: Iterable[Finding],
                   rule_ids: Iterable[str]) -> None:
    doc = baseline_doc(findings, rule_ids)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=False)
        f.write("\n")
    os.replace(tmp, path)
