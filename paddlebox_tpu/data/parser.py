"""Slot-format text parsing.

The reference parses slot-formatted examples three ways (SlotPaddleBoxDataFeed,
reference data_feed.cc:3104-3115): built-in ``ParseOneInstance`` for the
MultiSlot text protocol, a dlopen'd parser plugin (``ISlotParser``,
data_feed.h:1283), or an arbitrary ``pipe_command`` whose stdout is the
MultiSlot protocol. We keep all three ingestion modes (see ``reader.py``); this
module holds the protocol parser itself, with two implementations:

- a vectorized numpy fallback (pure Python), and
- a native C++ parser (``paddlebox_tpu/native/slot_parser.cc``) loaded via
  ctypes, which is the production path — the reference burns dozens of host
  parser threads per node (platform/flags.cc:480-484) and host-side parse is
  the known ingest bottleneck (SURVEY.md §7 "Hard parts").

MultiSlot text protocol: for each example (one line), for each slot in schema
order: ``<len> v_1 ... v_len`` separated by whitespace. uint64 slots carry
feature signs, float slots carry floats. Lines may optionally be prefixed with
``<ins_id>\\t`` when the schema's reader enables instance ids.
"""

from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np

from paddlebox_tpu.data.schema import DataFeedSchema, SlotType
from paddlebox_tpu.data.slot_record import SlotRecordBatch
from paddlebox_tpu.utils.hashing import hash64


def parse_multislot_lines(
    lines: Iterable[str],
    schema: DataFeedSchema,
    with_ins_id: bool = False,
) -> SlotRecordBatch:
    """Parse MultiSlot text lines into one columnar SlotRecordBatch."""
    native = _maybe_native()
    if native is not None:
        lines = list(lines)      # re-iterable for the fallback below
        try:
            out = native.parse_lines(lines, schema, with_ins_id=with_ins_id)
        except ValueError:
            # the native fast path is strict (first bad line raises);
            # re-parse in Python, which applies the skip-with-a-name
            # malformed-line treatment (reader.parse_errors) — the
            # contract must not depend on whether the .so is built
            out = None
        if out is not None:
            return out
    return _parse_python(lines, schema, with_ins_id)


def parse_multislot_buffer(
    buf: bytes,
    schema: DataFeedSchema,
    with_ins_id: bool = False,
) -> SlotRecordBatch:
    """Parse a whole raw text buffer — the zero-copy native fast path (the
    file reader hands bytes straight to C++, no Python line iteration)."""
    native = _maybe_native()
    if native is not None:
        try:
            out = native.parse_buffer(buf, schema, with_ins_id=with_ins_id)
        except ValueError:
            out = None           # strict native parser: fall back (above)
        if out is not None:
            return out
    # errors="replace", not strict: a torn line of binary garbage must
    # reach the per-line skip logic (reader.parse_errors), not brick the
    # whole file with an UnicodeDecodeError that names nothing
    return _parse_python(buf.decode("utf-8", errors="replace").splitlines(),
                         schema, with_ins_id)


_U64_MASK = (1 << 64) - 1
_U64_WRAP = 1 << 64
_I64_MAX1 = 1 << 63


def _note_malformed_line(lineno: int, line: str, err: Exception,
                         n_bad: int) -> None:
    """Malformed-line diagnostics: every skip counts, the first few per
    parse call carry the line's identity, and the first warns — the
    skip-with-a-name discipline of FleetUtil._entries (PR-7)."""
    from paddlebox_tpu import monitor
    monitor.counter_add("reader.parse_errors")
    if n_bad <= 5:    # identity for the head; the counter carries the rest
        monitor.event("reader_malformed_line", lineno=lineno,
                      error=str(err)[:200], line=line[:120])
    if n_bad == 1:
        import warnings
        warnings.warn(
            f"malformed MultiSlot line {lineno} (skipped): "
            f"{line[:120]!r} ({err}); counting under reader.parse_errors")


def _wrap_i64(v: str) -> int:
    u = int(v) & _U64_MASK
    return u - _U64_WRAP if u >= _I64_MAX1 else u


def _parse_python(lines: Iterable[str], schema: DataFeedSchema,
                  with_ins_id: bool) -> SlotRecordBatch:
    slots = schema.slots
    n_sparse = len(schema.sparse_slots)
    n_float = len(schema.float_slots)
    sparse_vals: list[list[int]] = [[] for _ in range(n_sparse)]
    sparse_lens: list[list[int]] = [[] for _ in range(n_sparse)]
    float_vals: list[list[float]] = [[] for _ in range(n_float)]
    ins_ids: list[int] = []
    num = 0
    n_bad = 0
    lineno = 0
    for line in lines:
        lineno += 1
        line = line.strip()
        if not line:
            continue
        # parse into per-LINE buffers and commit to the columns only on
        # success: a line failing mid-slot leaves no partial state, with
        # zero happy-path rollback bookkeeping
        row_ins = 0
        row_sparse: list[tuple[list[int], int]] = []
        row_float: list[list[float]] = []
        try:
            if with_ins_id:
                ins_id_str, _, line = line.partition("\t")
                row_ins = hash64(ins_id_str)
            toks = line.split()
            pos = 0
            for slot in slots:
                if pos >= len(toks):
                    raise ValueError(
                        f"ran out of tokens at slot {slot.name!r}")
                ln = int(toks[pos]); pos += 1
                if ln < 0:
                    # a negative length passes the bounds check below
                    # (empty slice, pos moves BACKWARDS) and would emit
                    # negative sparse_lens — silent batch corruption
                    raise ValueError(
                        f"slot {slot.name!r} declares negative length {ln}")
                if pos + ln > len(toks):
                    raise ValueError(
                        f"slot {slot.name!r} declares {ln} values but "
                        f"line ends")
                vals = toks[pos:pos + ln]; pos += ln
                if slot.type == SlotType.UINT64:
                    if slot.is_used:
                        # Feature signs are full-range uint64; storage is
                        # int64 bit patterns (reinterpret, like the native
                        # parser), so signs >= 2^63 wrap instead of
                        # overflowing.
                        row_sparse.append(
                            ([_wrap_i64(v) for v in vals], ln))
                else:
                    if slot.is_used:
                        w = slot.max_len
                        fv = [float(v) for v in vals[:w]]
                        fv += [0.0] * (w - len(fv))
                        row_float.append(fv)
        except ValueError as err:
            # A torn/foreign line must not brick the whole file: skip it
            # WITH A NAME — counter + event carrying the line's identity —
            # the same treatment PR-7 gave malformed donefile lines. An
            # input that parses to NOTHING still raises below: dirty data
            # is survivable, a wrong schema or binary garbage is not.
            n_bad += 1
            _note_malformed_line(lineno, line, err, n_bad)
            continue
        for i, (vals_i, ln_i) in enumerate(row_sparse):
            sparse_vals[i].extend(vals_i)
            sparse_lens[i].append(ln_i)
        for i, fv_i in enumerate(row_float):
            float_vals[i].extend(fv_i)
        if with_ins_id:
            ins_ids.append(row_ins)
        num += 1
    if num == 0 and n_bad:
        raise ValueError(
            f"every line was malformed MultiSlot ({n_bad} skipped) — "
            f"wrong schema or non-MultiSlot input?")
    sparse_values = [np.asarray(v, dtype=np.int64) for v in sparse_vals]
    sparse_offsets = []
    for lens in sparse_lens:
        offs = np.zeros(num + 1, dtype=np.int64)
        if lens:
            np.cumsum(np.asarray(lens, dtype=np.int64), out=offs[1:])
        sparse_offsets.append(offs)
    if not with_ins_id:
        ins = np.zeros(num, dtype=np.uint64)
    else:
        ins = np.asarray(ins_ids, dtype=np.uint64)
    return SlotRecordBatch(
        schema=schema, num=num,
        sparse_values=sparse_values, sparse_offsets=sparse_offsets,
        float_values=[np.asarray(v, dtype=np.float32) for v in float_vals],
        ins_id=ins,
        search_id=np.zeros(num, dtype=np.uint64),
        rank=np.zeros(num, dtype=np.int32),
        cmatch=np.zeros(num, dtype=np.int32),
    )


_native_cache: list = []


def _maybe_native():
    """The C++ parser binding, or None when its library cannot be built
    or loaded here (native/loader.py names the reason once)."""
    if not _native_cache:
        from paddlebox_tpu.native import slot_parser_binding
        _native_cache.append(slot_parser_binding
                             if slot_parser_binding.available() else None)
    return _native_cache[0]


def format_multislot_example(slot_values: Sequence[tuple[str, Sequence]],
                             schema: DataFeedSchema) -> str:
    """Inverse of the parser — used by the data generator (the reference's
    MultiSlotDataGenerator protocol, python/paddle/fluid/incubate/data_generator)."""
    by_name = dict(slot_values)
    parts: list[str] = []
    for slot in schema.slots:
        vals = by_name.get(slot.name, ())
        parts.append(str(len(vals)))
        parts.extend(str(v) for v in vals)
    return " ".join(parts)
