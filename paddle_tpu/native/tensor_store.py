"""The combined-tensor checkpoint file (save_combine_op.cc /
load_combine_op.cc analog), read and written in Python.

The format is tensor_store.cc's, byte for byte — little-endian::

    magic "PTCK" | u32 format_version | u32 n_tensors
    per tensor: u32 name_len | name | u8 dtype | u8 ndim | i64 dims[ndim]
                | u64 nbytes | raw data

so the Python-free loaders (native/pjrt_serving.cc compiles
tensor_store.cc in) read what this module writes, and
tests/test_tensor_store.py holds the two implementations to each other.
Python does its own I/O here: saving a model or a checkpoint — the
train/serve main path — must not need a C++ compiler. Dtype codes come
from the shared table in native/dtypes.py; writes go to a temp file and
rename into place, so a failed save never clobbers an existing good
checkpoint."""

from __future__ import annotations

import glob as _glob
import itertools as _itertools
import os
import struct
from typing import Dict

import numpy as np

from .dtypes import code_of, dtype_of

__all__ = ["save_tensors", "load_tensors", "MAGIC"]

MAGIC = b"PTCK"
_FORMAT_VERSION = 1  # tensor_store.cc's kVersion
_TMP_SEQ = _itertools.count(1)  # thread-safe staging-file uniquifier


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except OSError:
        # EPERM etc: the pid exists but isn't ours — treat as alive
        return True
    return True


def _clean_orphan_tmps(path: str) -> None:
    """Remove staging files for THIS target left by DEAD writer pids —
    a SIGKILLed/power-lost writer dies between the tmp write and the
    rename, and nothing else ever collects its litter. Live pids (a
    concurrent writer in another process) are never touched; neither is
    this process's own staging (same-path writes serialize in io.py, so
    any same-pid tmp seen here belongs to an in-flight writer)."""
    for tmp in _glob.glob(_glob.escape(path) + ".tmp.*"):
        parts = tmp[len(path):].split(".")  # ['', 'tmp', '<pid>', '<seq>']
        try:
            pid = int(parts[2])
        except (IndexError, ValueError):
            continue
        if pid == os.getpid() or _pid_alive(pid):
            continue
        try:
            os.remove(tmp)
        except OSError:
            continue
        from ..observe.families import RESILIENCE_ORPHANS_CLEANED

        RESILIENCE_ORPHANS_CLEANED.inc()


def save_tensors(path: str, tensors: Dict[str, np.ndarray]) -> None:
    _clean_orphan_tmps(path)
    # normalize + dtype-check everything BEFORE touching the filesystem
    prepared = []
    for name, arr in tensors.items():
        a = np.asarray(arr)
        if not a.flags["C_CONTIGUOUS"]:
            # (ascontiguousarray alone would promote a 0-d array to 1-d)
            a = np.ascontiguousarray(a).reshape(a.shape)
        if a.ndim > 255:
            raise ValueError("%r has %d dims; the format holds 255"
                             % (name, a.ndim))
        prepared.append((name.encode(), a, code_of(a.dtype)))

    # unique staging name: concurrent writers to the same target (e.g. a
    # sync save racing an async background write) each stage their own
    # temp file — the final os.replace is last-writer-wins, never a torn
    # or interleaved file
    tmp = "%s.tmp.%d.%d" % (path, os.getpid(), next(_TMP_SEQ))
    finished = False
    try:
        # streams straight to disk: no second copy of a full checkpoint
        with open(tmp, "wb") as f:
            f.write(MAGIC + struct.pack("<II", _FORMAT_VERSION,
                                        len(prepared)))
            for name, a, code in prepared:
                f.write(struct.pack("<I", len(name)) + name
                        + struct.pack("<BB", code, a.ndim)
                        + struct.pack("<%dq" % a.ndim, *a.shape)
                        + struct.pack("<Q", a.nbytes))
                f.write(_raw_bytes(a))
        # fault-injection site, placed EXACTLY in the crash window that
        # matters: the staged tmp is complete, the rename has not
        # happened — a 'crash' here leaves the litter a real power loss
        # leaves (previous checkpoint intact, orphaned tmp on disk); a
        # 'raise' here surfaces like any transient write error (the
        # finally below removes the staging file)
        from ..resilience.faults import fault_point

        fault_point("checkpoint.write")
        os.replace(tmp, path)
        finished = True
    finally:
        if not finished:
            try:
                os.remove(tmp)
            except OSError:
                pass


def _raw_bytes(a: np.ndarray) -> np.ndarray:
    """The C-contiguous array's own memory as flat bytes (no copy; works
    for bfloat16, which has no buffer-protocol format of its own)."""
    return a.reshape(-1).view(np.uint8)


def _read_exact(f, n: int, path: str) -> bytes:
    buf = f.read(n)
    if len(buf) != n:
        raise IOError("cannot read checkpoint %s (truncated)" % path)
    return buf


def load_tensors(path: str) -> Dict[str, np.ndarray]:
    try:
        f = open(path, "rb")
    except OSError:
        raise IOError("cannot read checkpoint %s (missing or bad header)"
                      % path) from None
    with f:
        header = f.read(12)
        if len(header) != 12 or header[:4] != MAGIC \
                or struct.unpack("<I", header[4:8])[0] != _FORMAT_VERSION:
            raise IOError("cannot read checkpoint %s (missing or bad "
                          "header)" % path)
        out: Dict[str, np.ndarray] = {}
        for _ in range(struct.unpack("<I", header[8:])[0]):
            (nlen,) = struct.unpack("<I", _read_exact(f, 4, path))
            name = _read_exact(f, nlen, path).decode()
            code, nd = struct.unpack("<BB", _read_exact(f, 2, path))
            shape = struct.unpack("<%dq" % nd, _read_exact(f, 8 * nd, path))
            (nbytes,) = struct.unpack("<Q", _read_exact(f, 8, path))
            dt = dtype_of(code)
            if nbytes != int(np.prod(shape, dtype=np.int64)) * dt.itemsize:
                raise IOError("cannot read checkpoint %s (%r: %d bytes for "
                              "shape %s %s)" % (path, name, nbytes,
                                                shape, dt))
            # one read straight into the array's own buffer
            arr = np.empty(shape, dtype=dt)
            if f.readinto(_raw_bytes(arr)) != nbytes:
                raise IOError("cannot read checkpoint %s (truncated)" % path)
            out[name] = arr
        return out
