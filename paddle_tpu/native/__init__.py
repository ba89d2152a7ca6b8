"""Native (C++) runtime components, loaded via ctypes.

The reference glues C++ to Python with pybind11 (paddle/fluid/pybind/);
pybind11 isn't available in this image, so the native pieces expose a C
API consumed through ctypes. Libraries are compiled on first use with g++
and cached next to the source (rebuilt when a source or header is newer).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_LOCK = threading.Lock()
_LIBS = {}


def _python_embed_flags():
    """Include + link flags for libs that embed CPython (serving.cc),
    derived from THE RUNNING interpreter via sysconfig — a PATH
    python3-config could belong to a different installation and link the
    wrong libpython."""
    import sysconfig

    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION") or sysconfig.get_config_var(
        "VERSION")
    flags = ["-I" + inc]
    if libdir:
        flags += ["-L" + libdir, "-Wl,-rpath," + libdir]
    flags += ["-lpython" + ver, "-ldl", "-lm"]
    return flags


def pjrt_include_dir():
    """Directory holding xla/pjrt/c/pjrt_c_api.h, or None. Checked in
    order: PD_PJRT_INCLUDE env override, then the tensorflow wheel's
    include tree (resolved by path, never imported)."""
    import sysconfig

    candidates = []
    env = os.environ.get("PD_PJRT_INCLUDE")
    if env:
        candidates.append(env)
    candidates.append(os.path.join(sysconfig.get_paths()["purelib"],
                                   "tensorflow", "include"))
    for inc in candidates:
        if os.path.exists(os.path.join(inc, "xla", "pjrt", "c",
                                       "pjrt_c_api.h")):
            return inc
    return None


def _pjrt_flags():
    """PJRT C API include + dl. No python flags: the whole point of
    pjrt_serving is a libpython-free dependency closure."""
    inc = pjrt_include_dir()
    if inc is None:
        raise RuntimeError(
            "pjrt_c_api.h not found; install a tensorflow wheel or set "
            "PD_PJRT_INCLUDE to an XLA include tree")
    return ["-I" + inc, "-ldl"]


_EXTRA_FLAGS = {"serving": _python_embed_flags,
                "train": _python_embed_flags,
                "pjrt_serving": _pjrt_flags}

# additional .cc files compiled into the named library
_EXTRA_SOURCES = {"pjrt_serving": ["tensor_store.cc"]}


def _build(name: str) -> str:
    srcs = [os.path.join(_DIR, name + ".cc")] + [
        os.path.join(_DIR, s) for s in _EXTRA_SOURCES.get(name, ())]
    so = os.path.join(_DIR, "lib" + name + ".so")
    # every local header counts as a dependency of every library
    # (serving.cc and train.cc include embed_common.h)
    deps = srcs + [os.path.join(_DIR, f) for f in os.listdir(_DIR)
                   if f.endswith(".h")]
    with _BUILD_LOCK:
        if (not os.path.exists(so)
                or os.path.getmtime(so) < max(os.path.getmtime(d)
                                              for d in deps)):
            extra = _EXTRA_FLAGS.get(name)
            # linked beside the library and renamed over it: the lock
            # above is this process's, and another process (a test
            # worker, a trainer) that finds the file while the linker
            # writes it would load a part of it ("file too short")
            tmp = os.path.join(_DIR, "lib%s.%d.tmp.so" % (name, os.getpid()))
            cmd = (["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                    "-pthread"] + srcs + (extra() if extra else [])
                   + ["-o", tmp])
            try:
                subprocess.run(cmd, check=True, capture_output=True)
                os.replace(tmp, so)
            finally:
                if os.path.exists(tmp):
                    os.remove(tmp)
    return so


def load(name: str) -> ctypes.CDLL:
    if name not in _LIBS:
        _LIBS[name] = ctypes.CDLL(_build(name))
    return _LIBS[name]
