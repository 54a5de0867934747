"""The device's time by the program's own stages.

A step is written under ``device_scope("<name>")`` = ``jax.named_scope(
"pbtpu.<name>")`` (names closed in ``names.DEVICE_SCOPE_NAMES``). No
device event carries the scope, but the compiled program does: in the
optimized HLO every instruction's ``op_name`` holds the scopes it was
traced under as path components, through differentiation, transposition
and ``jax.checkpoint`` (``jit(step)/transpose(jvp(pbtpu.tower))/.../
rematted_computation/pbtpu.route/mul``), and a capture's ``XLA Ops`` event
is named by its instruction. So the program says, for each program it
compiled, which stage each instruction belongs to — :func:`scopes_of_hlo`,
the innermost registered scope wins — and a reader joins that table with
the seconds a capture gives by instruction name (``monitor.trace
--device``; the benchmark's ``metrics/_scopes.py``).

The table is built only under an open ``jax.profiler`` capture and never
in the device's way: a captured pass's programs are noted at their first
call (:func:`run`) and lowered, compiled — a hit in jit's own cache: the
executable the call just ran — and read by a daemon thread
(:class:`TableBuild`) that ``Trainer.train_pass`` starts at the pass's
close, after its last dispatch, and joins after the drain and the read. ``TABLE`` keeps the result for the
process's life (a benchmark's readers run after the trainer is gone),
replaced per module name. With no capture open nothing is noted, lowered
or kept.

One executable may hold another tree's names: JAX's persistent compile
cache leaves metadata out of its key by default, so a program that
differs from an older tree's in scopes alone is answered with that tree's
executable and its ``op_name``s. ``utils/compile_cache.py`` puts metadata
into the key for the repo's entry points; for a process that sets the
cache up itself: where a program traced under scopes comes back with
none in its text, :func:`table_of` compiles it once more past both caches
(seconds to a minute and a half on the builder thread, counted in
``trace.device_scope_recompiles``); the instruction names are the running
executable's, because the compiler is deterministic and reads no
metadata. A scope that only MOVED since such a cache was filled cannot be
told from the text (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import collections
import queue
import re
import threading
import warnings
import weakref

from paddlebox_tpu.monitor import context
from paddlebox_tpu.monitor.hub import span
from paddlebox_tpu.monitor.names import (DEVICE_SCOPE_NAMES,
                                         DEVICE_SCOPE_PREFIX)
from paddlebox_tpu.monitor.registry import STATS

# {module name: {instruction name: {"result": the result type as printed,
#                                   "scope": name | None}}}
TABLE: dict[str, dict[str, dict]] = {}
TABLE_FILE = "device_scopes.json"      # beside a flags.trace_device capture

_SCOPE = re.compile(re.escape(DEVICE_SCOPE_PREFIX) + r"(\w+)")
_MODULE = re.compile(r"HloModule ([\w.\-]+)")
_COMPUTATION = re.compile(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
# no ``XLA Ops`` event is one of these
_NO_EVENT = ("parameter", "constant", "tuple", "get-tuple-element")
# what the compiler names itself when it expands an operation, dropping the
# ``op_name`` the operation had, by the one place the program asks for it:
# ``lax.ragged_dot``'s grouped products and their tile metadata
# (``parallel/expert.py::_expert_rows``)
_COMPILER_NAMED = (("ragged-dot", "experts"),)
# the computations these call run instruction by instruction (each an
# event); every other callee — a fusion's, a reduction's or a sort's
# comparator, an async wrapper's — is part of its caller's one event
_RUNS_ITS_CALLEES = ("while", "conditional", "call")


def device_scope(name: str):
    """``jax.named_scope("pbtpu.<name>")`` for a registered stage of the
    device's work; a name outside ``names.DEVICE_SCOPE_NAMES`` raises (at
    trace time: a step pays nothing)."""
    if name not in DEVICE_SCOPE_NAMES:
        raise ValueError(f"device scope {name!r} is not registered "
                         f"(monitor/names.py::DEVICE_SCOPE_NAMES)")
    import jax
    return jax.named_scope(DEVICE_SCOPE_PREFIX + name)


def scope_of(op_name: str | None) -> str | None:
    """The innermost registered ``pbtpu.<name>`` of an ``op_name``."""
    for name in reversed(_SCOPE.findall(op_name or "")):
        if name in DEVICE_SCOPE_NAMES:
            return name
    return None


def _result_and_opcode(rest: str) -> tuple[str, str]:
    """``f32[8,4]{1,0} fusion(...), ...`` -> (``f32[8,4]{1,0}``,
    ``fusion``); a tuple's result type is its whole bracket."""
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if not depth:
                break
        result, tail = rest[:end + 1], rest[end + 1:].lstrip()
    else:
        result, _, tail = rest.partition(" ")
    return result, tail.partition("(")[0]


def scopes_of_hlo(text: str) -> dict[str, dict]:
    """``{instruction name: {"result", "scope"}}`` over every computation
    of an optimized HLO module's text (``Compiled.as_text()``). An
    instruction's scope is, in this order: :func:`scope_of` its
    ``op_name``; where it has no ``op_name`` that is a path of the
    program — none at all, or the bare name the compiler or a shared
    lowering gave it (``ragged-dot-none``, ``reduce_window_sum``) — the
    scope most named instructions of the computations it calls carry (a
    fusion, a call, a loop), else the scope most of its users carry (a
    prefetch, a copy, an expanded custom call exists for what reads it),
    else most of its operands. Left out, because no ``XLA Ops`` event is
    one: parameters, constants, tuples, ``get-tuple-element``, and what
    lies inside a fusion or any other computation that is not run
    instruction by instruction (only the entry's, a loop's, a
    conditional's and a call's are)."""
    rows: dict[str, dict] = {}
    members: dict[str, list[str]] = {}      # computation -> its rows
    stepped: set[str] = set()               # run instruction by instruction
    inside = None
    for line in text.splitlines():
        if inside is None:
            m = _COMPUTATION.match(line)
            if m:
                inside = members.setdefault(m.group(2), [])
                if m.group(1):
                    stepped.add(m.group(2))
            continue
        if line.startswith("}"):
            inside = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        # (a kernel's body, hundreds of kB of text, follows the metadata)
        rest = rest.partition(", backend_config=")[0]
        result, opcode = _result_and_opcode(rest)
        called = [c.strip().lstrip("%") for single, many in
                  _CALLED.findall(rest) for c in (many.split(",")
                                                  if many else [single])]
        if opcode in _RUNS_ITS_CALLEES:
            stepped.update(called)
        op_name = _OP_NAME.search(rest)
        op_name = op_name.group(1) if op_name else ""
        scope = scope_of(op_name)
        if "/" not in op_name:
            # the compiler's own name for what it expanded, its op_name lost
            scope = next((sc for prefix, sc in _COMPILER_NAMED
                          if name.startswith(prefix)), scope)
        # (what is no event — a tuple, a tuple's element — still hands a
        # scope on between the instructions on either side of it)
        rows[name] = {"result": result, "scope": scope,
                      "path": "/" in op_name or scope is not None,
                      "called": called, "event": opcode not in _NO_EVENT,
                      "reads": _OPERAND.findall(rest[len(result):])}
        inside.append(name)

    def most(scopes):
        votes = collections.Counter(scopes)
        return votes.most_common(1)[0][0] if votes else None

    # a callee is printed before its caller: one pass in order settles a
    # chain of callers without a path of their own
    for row in rows.values():
        if not row["path"] and row["called"]:
            row["scope"] = most(
                rows[r]["scope"] for c in row["called"]
                for r in members.get(c, ()) if rows[r]["path"])
            row["path"] = row["scope"] is not None
    # a user is printed after what it reads: in reverse, a chain (a copy's
    # start, its end, the fusion that reads it) settles from its far end
    users: dict[str, list[str]] = {}
    for name, row in rows.items():
        for read in row["reads"]:
            users.setdefault(read, []).append(name)
    for name in reversed(rows):
        row = rows[name]
        if row["path"] or row["scope"] is not None:
            continue
        for near in (users.get(name, ()), row["reads"]):
            row["scope"] = most(rows[n]["scope"] for n in near
                                if n in rows and rows[n]["scope"])
            if row["scope"] is not None:
                break
    # (a callee of a computation that is itself no event's keeps no rows
    # either: nothing steps into a fusion's conditional)
    return {name: {"result": rows[name]["result"],
                   "scope": rows[name]["scope"]}
            for c in stepped for name in members.get(c, ())
            if rows[name]["event"]}


def module_name(text: str) -> str:
    m = _MODULE.match(text)
    return m.group(1) if m else ""


# ---------------------------------------------------------------------------
# the build: under an open capture, off the dispatch path
# ---------------------------------------------------------------------------

_build: "TableBuild | None" = None
_warned = False
# jitted program -> (the specs its table was built for, its module name):
# a later captured pass lowers again only what it has not seen so
_known: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _failed(what: str, err: Exception) -> None:
    """As ``trace._capture_failed``: counted every time, said once;
    training goes on and the table lacks that program."""
    global _warned
    STATS.add("trace.device_scope_errors", 1)
    if not _warned:
        _warned = True
        warnings.warn(f"device scopes: {what} failed ({err!r}); the table "
                      f"is left without that program", RuntimeWarning,
                      stacklevel=2)


def _spec(a):
    import jax
    if not hasattr(a, "shape") or not hasattr(a, "dtype"):
        return a
    sharding = a.sharding if getattr(a, "committed", False) else None
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding,
                                weak_type=getattr(a, "weak_type", False))


def table_of(fn, specs) -> dict[str, dict]:
    """``{module name: scopes_of_hlo(...)}`` of the jitted `fn` compiled
    for `specs`: for a program that has run with such arguments a hit in
    jit's own cache (the executable that ran), else in the persistent
    one."""
    lowered = fn.lower(*specs)
    text = lowered.compile().as_text()
    if DEVICE_SCOPE_PREFIX not in text:
        # an executable the persistent cache answered with an older
        # tree's metadata (module docstring): compile past both caches.
        # The option is part of their keys (the dump options are not) and
        # changes no instruction: the text is what it adds to
        if DEVICE_SCOPE_PREFIX in lowered.as_text(debug_info=True):
            STATS.add("trace.device_scope_recompiles", 1)
            text = lowered.compile(compiler_options={
                "xla_embed_ir_in_executable": True}).as_text()
    return {module_name(text): scopes_of_hlo(text)}


def run(fn, *args):
    """``fn(*args)`` for a jitted program. While a captured pass builds
    the table (:class:`TableBuild`), the program is noted at its first
    call with the call's shapes, dtypes and shardings."""
    build = _build
    if build is not None and fn not in build.seen:
        build.note(fn, args)
    return fn(*args)


class TableBuild:
    """One captured pass's build of ``TABLE``: programs come in through
    :func:`run` (any thread) and wait; from :meth:`start` — the pass's
    close, after its last dispatch — a daemon thread lowers, compiles and
    reads each, beside the training thread's drain; :meth:`close` waits
    for it. (A thread at work from the pass's head holds the interpreter
    while the training thread dispatches its first steps into an empty
    queue: on the chip that idled the device 1-2 s of a traced pass,
    PERF.md section 6, PR 38.)"""

    def __init__(self):
        self.seen: dict = {}                  # jitted fn -> its arg specs
        self._todo: queue.SimpleQueue = queue.SimpleQueue()
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        if self._thread is None:
            # the pass's context: the thread's span carries its pass_id
            self._thread = context.spawn(self._work,
                                         name="pbtpu-device-scopes")
            self._thread.start()

    def note(self, fn, args) -> None:
        import jax
        try:
            specs = jax.tree.map(_spec, args)
        except Exception as e:
            _failed("reading a call's arguments", e)
            specs = None
        self.seen[fn] = specs
        if specs is not None:
            self._todo.put((fn, specs))

    def _work(self) -> None:
        while True:
            item = self._todo.get()
            if item is None:
                return
            fn, specs = item
            known = _known.get(fn)
            if known is not None and known[0] == specs \
                    and known[1] in TABLE:
                continue
            try:
                with span("device_scopes"):
                    part = table_of(fn, specs)
            except Exception as e:
                _failed(f"building the table of "
                        f"{getattr(fn, '__name__', fn)}", e)
                continue
            TABLE.update(part)
            _known[fn] = (specs, next(iter(part)))

    def close(self) -> None:
        self.start()
        self._todo.put(None)
        self._thread.join()


def open_build() -> TableBuild | None:
    """Start noting programs (``Trainer.train_pass``, at its head, where
    ``jax.profiler.TraceAnnotation.is_enabled()``); None where another
    pass's build is open (a second trainer on another thread)."""
    global _build
    if _build is not None:
        return None
    _build = TableBuild()
    return _build


def close_build(build: TableBuild | None) -> None:
    global _build
    if build is None:
        return
    _build = None
    build.close()
