"""ctypes binding for the native MultiSlot parser (slot_parser.cc).

Loads ``libslotparser.so`` from this directory after ``make`` has brought
it up to date with its source (native/loader.py; ``PBTPU_NO_NATIVE_BUILD=1``
opts out of the native path altogether). ``parse_lines`` mirrors
``parser._parse_python`` exactly — same columnar output, same error
behavior — so the two paths are interchangeable and tested against each
other (tests/test_native_parser.py).
"""

from __future__ import annotations

import ctypes
from typing import Iterable

import numpy as np

from paddlebox_tpu.native.loader import load_native


def _configure(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.sp_parse.restype = c.c_void_p
    lib.sp_parse.argtypes = [
        c.c_char_p, c.c_int64, c.c_int32,
        c.POINTER(c.c_int32), c.POINTER(c.c_int32), c.POINTER(c.c_int32),
        c.c_int32, c.c_int32, c.c_char_p, c.c_int64]
    lib.sp_num_examples.restype = c.c_int64
    lib.sp_num_examples.argtypes = [c.c_void_p]
    lib.sp_sparse_nnz.restype = c.c_int64
    lib.sp_sparse_nnz.argtypes = [c.c_void_p, c.c_int32]
    lib.sp_copy_sparse_values.restype = None
    lib.sp_copy_sparse_values.argtypes = [c.c_void_p, c.c_int32, c.c_void_p]
    lib.sp_copy_sparse_offsets.restype = None
    lib.sp_copy_sparse_offsets.argtypes = [c.c_void_p, c.c_int32, c.c_void_p]
    lib.sp_copy_floats.restype = None
    lib.sp_copy_floats.argtypes = [c.c_void_p, c.c_int32, c.c_void_p]
    lib.sp_copy_ins_ids.restype = None
    lib.sp_copy_ins_ids.argtypes = [c.c_void_p, c.c_void_p]
    lib.sp_free.restype = None
    lib.sp_free.argtypes = [c.c_void_p]
    lib.sp_hash64.restype = c.c_uint64
    lib.sp_hash64.argtypes = [c.c_char_p, c.c_int64]


def get_lib() -> ctypes.CDLL | None:
    return load_native("libslotparser.so", _configure)



def available() -> bool:
    return get_lib() is not None


def parse_buffer(buf: bytes, schema, with_ins_id: bool = False,
                 n_threads: int = 0):
    """Parse a raw MultiSlot text buffer into a SlotRecordBatch.

    Raises ValueError on malformed input (same contract as the Python
    parser); returns None when the native library is unavailable.
    """
    from paddlebox_tpu.data.schema import SlotType
    from paddlebox_tpu.data.slot_record import SlotRecordBatch

    lib = get_lib()
    if lib is None:
        return None
    slots = schema.slots
    n = len(slots)
    types = (ctypes.c_int32 * n)(
        *[0 if s.type == SlotType.UINT64 else 1 for s in slots])
    used = (ctypes.c_int32 * n)(*[1 if s.is_used else 0 for s in slots])
    widths = (ctypes.c_int32 * n)(*[s.max_len for s in slots])
    errbuf = ctypes.create_string_buffer(512)
    res = lib.sp_parse(buf, len(buf), n, types, used, widths,
                       1 if with_ins_id else 0, n_threads, errbuf,
                       len(errbuf))
    if not res:
        raise ValueError(errbuf.value.decode("utf-8", "replace"))
    try:
        num = lib.sp_num_examples(res)
        sparse_slots = schema.sparse_slots
        float_slots = schema.float_slots
        sparse_values, sparse_offsets = [], []
        for s in range(len(sparse_slots)):
            nnz = lib.sp_sparse_nnz(res, s)
            vals = np.empty(nnz, dtype=np.int64)
            offs = np.zeros(num + 1, dtype=np.int64)
            if nnz:
                lib.sp_copy_sparse_values(
                    res, s, vals.ctypes.data_as(ctypes.c_void_p))
            lib.sp_copy_sparse_offsets(
                res, s, offs.ctypes.data_as(ctypes.c_void_p))
            sparse_values.append(vals)
            sparse_offsets.append(offs)
        float_values = []
        for f, slot in enumerate(float_slots):
            fv = np.empty(num * slot.max_len, dtype=np.float32)
            if len(fv):
                lib.sp_copy_floats(res, f,
                                   fv.ctypes.data_as(ctypes.c_void_p))
            float_values.append(fv)
        ins = np.zeros(num, dtype=np.uint64)
        if with_ins_id and num:
            lib.sp_copy_ins_ids(res, ins.ctypes.data_as(ctypes.c_void_p))
        return SlotRecordBatch(
            schema=schema, num=int(num),
            sparse_values=sparse_values, sparse_offsets=sparse_offsets,
            float_values=float_values, ins_id=ins,
            search_id=np.zeros(num, dtype=np.uint64),
            rank=np.zeros(num, dtype=np.int32),
            cmatch=np.zeros(num, dtype=np.int32),
        )
    finally:
        lib.sp_free(res)


def parse_lines(lines: Iterable[str], schema, with_ins_id: bool = False):
    if get_lib() is None:
        # Bail before touching `lines`: consuming a one-shot iterator here
        # would hand the Python fallback an exhausted generator.
        return None
    buf = "\n".join(lines).encode("utf-8")
    return parse_buffer(buf, schema, with_ins_id=with_ins_id)


def hash64_native(s: str | bytes) -> int:
    lib = get_lib()
    if isinstance(s, str):
        s = s.encode("utf-8")
    return int(lib.sp_hash64(s, len(s)))
