"""ctypes binding of the string reference (`mergetree_ref.cpp`).

The library is compiled with the system g++ on first use into
``benchmark/.build/`` (a fixed path inside the checkout), named by the
hash of its source so that a changed source never loads an old build.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "mergetree_ref.cpp")
BUILD = os.path.join(os.path.dirname(HERE), ".build")
NKEYS = 8
ABSENT = -1

_lib = None

_I32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_I64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_F64 = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")


def lib() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    with open(SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:16]
    path = os.path.join(BUILD, f"mergetree_ref.{tag}.so")
    if not os.path.exists(path):
        os.makedirs(BUILD, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        subprocess.run(["g++", "-O2", "-shared", "-fPIC", "-o", tmp, SRC],
                       check=True, capture_output=True, timeout=300)
        os.replace(tmp, path)
    so = ctypes.CDLL(path)
    so.ref_gen_lagged.restype = ctypes.c_int64
    so.ref_gen_lagged.argtypes = [
        ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, ctypes.c_double,
        ctypes.c_double, ctypes.c_double, _F64, _F64, _F64, _I64, _I64,
        _I64, _I32, ctypes.c_int32, _I32, _I32, _I32, _I32, _I32, _I32,
    ]
    so.ref_replay.restype = ctypes.c_int64
    so.ref_replay.argtypes = [ctypes.c_int64] + [_I32] * 12 + [
        ctypes.c_int32, ctypes.c_int32, _I32, _I32, ctypes.c_int64,
    ]
    _lib = so
    return so


def gen_lagged(n_ops, n_clients, window, lag_zero_frac, t_ins, t_rem,
               type_u, pos_u, lag_u, lag_draw, len_draw, range_draw,
               arena, initial_len):
    """Run the generator loop; returns (columns dict, arena length)."""
    cols = {k: np.zeros(n_ops, np.int32) for k in
            ("op_type", "pos1", "pos2", "ref_seq", "buf_start", "ins_len")}
    used = lib().ref_gen_lagged(
        n_ops, n_clients, window, lag_zero_frac, t_ins, t_rem,
        np.ascontiguousarray(type_u, np.float64),
        np.ascontiguousarray(pos_u, np.float64),
        np.ascontiguousarray(lag_u, np.float64),
        np.ascontiguousarray(lag_draw, np.int64),
        np.ascontiguousarray(len_draw, np.int64),
        np.ascontiguousarray(range_draw, np.int64),
        np.ascontiguousarray(arena, np.int32), initial_len,
        cols["op_type"], cols["pos1"], cols["pos2"], cols["ref_seq"],
        cols["buf_start"], cols["ins_len"],
    )
    if used < 0:
        raise AssertionError(f"generator emitted an invalid op at index "
                             f"{-used - 1}")
    return cols, int(used)


def replay(stream, initial_len: int, control: bool = False):
    """The final (text int32[L], props int32[L, NKEYS]) of `stream`
    (an object with the lagged stream's columns), ABSENT where a key is
    unset. `control=True` is the comparison's control: annotations
    resolve their range at the head instead of at the op's refSeq."""
    n = len(stream.op_type)
    cap = int(initial_len + int(np.sum(stream.ins_len)))
    text = np.zeros(max(cap, 1), np.int32)
    props = np.zeros((max(cap, 1), NKEYS), np.int32)
    c = lambda a: np.ascontiguousarray(a, np.int32)  # noqa: E731
    k = lib().ref_replay(
        n, c(stream.op_type), c(stream.pos1), c(stream.pos2), c(stream.seq),
        c(stream.ref_seq), c(stream.client), c(stream.buf_start),
        c(stream.ins_len), c(stream.prop_key), c(stream.prop_val),
        c(stream.min_seq), c(stream.text), initial_len, int(control),
        text, props, cap,
    )
    if k < 0:
        raise AssertionError(f"reference could not apply op {-k - 1}")
    return text[:k], props[:k]
