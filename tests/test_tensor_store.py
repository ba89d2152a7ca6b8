"""Native checkpoint serde tests (save_combine_op/load_combine_op analog:
round-trip, dtype coverage, version-header rejection, io.py integration)."""

import os

import numpy as np
import pytest

from paddle_tpu.native.tensor_store import MAGIC, load_tensors, save_tensors


def test_round_trip_all_dtypes(tmp_path):
    path = str(tmp_path / "ckpt")
    tensors = {
        "w": np.random.RandomState(0).randn(4, 3).astype(np.float32),
        "ids": np.arange(7, dtype=np.int64),
        "d": np.random.RandomState(1).randn(2, 2, 2),
        "i32": np.array([[1, 2]], np.int32),
        "mask": np.array([1, 0, 1], np.uint8),
        "scalar": np.float32(3.5),
    }
    save_tensors(path, tensors)
    got = load_tensors(path)
    assert set(got) == set(tensors)
    for k, v in tensors.items():
        a = np.asarray(v)
        assert got[k].shape == a.shape and got[k].dtype == a.dtype
        np.testing.assert_array_equal(got[k], a)
    with open(path, "rb") as f:
        assert f.read(4) == MAGIC


def test_bad_header_rejected(tmp_path):
    path = str(tmp_path / "junk")
    with open(path, "wb") as f:
        f.write(b"NOPE" + b"\x00" * 64)
    with pytest.raises(IOError):
        load_tensors(path)


def test_io_save_load_uses_native_format(tmp_path, fresh_programs):
    import paddle_tpu as fluid
    from paddle_tpu.core.scope import scope_guard

    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.fc(x, size=3)
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        before, = exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                          fetch_list=[y.name], scope=scope)
        fluid.io.save_params(exe, str(tmp_path), main_program=main,
                             scope=scope)
        # checkpoint file carries the native magic
        blob = os.path.join(str(tmp_path), "__model_combined__")
        with open(blob, "rb") as f:
            assert f.read(4) == MAGIC
        # clobber params, reload, outputs must match
        for n in list(scope.local_var_names()):
            v = scope.find_var(n)
            if hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1:
                scope.set_var(n, np.zeros_like(np.asarray(v)))
        fluid.io.load_params(exe, str(tmp_path), main_program=main,
                             scope=scope)
        after, = exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                         fetch_list=[y.name], scope=scope)
    np.testing.assert_allclose(before, after, rtol=1e-6)


def _cc_lib():
    """tensor_store.cc through ctypes: the implementation the Python-free
    loaders compile in (native/pjrt_serving.cc)."""
    import ctypes as c

    from paddle_tpu.native import load

    lib = load("tensor_store")
    lib.ts_write_begin.restype = c.c_void_p
    lib.ts_write_begin.argtypes = [c.c_char_p]
    lib.ts_write_add.argtypes = [c.c_void_p, c.c_char_p, c.c_int, c.c_int,
                                 c.POINTER(c.c_int64), c.c_void_p, c.c_int64]
    lib.ts_write_end.argtypes = [c.c_void_p]
    lib.ts_read_open.restype = c.c_void_p
    lib.ts_read_open.argtypes = [c.c_char_p]
    lib.ts_read_name.restype = c.c_char_p
    lib.ts_read_data.restype = c.c_void_p
    lib.ts_read_nbytes.restype = c.c_int64
    for fn in ("ts_read_count", "ts_read_close"):
        getattr(lib, fn).argtypes = [c.c_void_p]
    for fn in ("ts_read_name", "ts_read_dtype", "ts_read_ndim",
               "ts_read_data", "ts_read_nbytes"):
        getattr(lib, fn).argtypes = [c.c_void_p, c.c_int]
    lib.ts_read_dims.argtypes = [c.c_void_p, c.c_int, c.POINTER(c.c_int64)]
    return lib


def _interop_tensors():
    import ml_dtypes

    rs = np.random.RandomState(0)
    return {
        "w": rs.randn(4, 3).astype(np.float32),
        "ids": np.arange(7, dtype=np.int64),
        "bf": rs.randn(2, 5).astype(ml_dtypes.bfloat16),
        "scalar": np.asarray(np.float32(3.5)),
        "empty": np.zeros((0, 3), np.int32),
    }


def test_cc_reader_reads_what_python_writes(tmp_path):
    """The Python writer and tensor_store.cc agree byte for byte: the C++
    reader sees every tensor Python saved."""
    import ctypes as c

    from paddle_tpu.native.dtypes import code_of

    lib, path = _cc_lib(), str(tmp_path / "py.ptck")
    tensors = _interop_tensors()
    save_tensors(path, tensors)
    h = lib.ts_read_open(path.encode())
    assert h
    try:
        assert lib.ts_read_count(h) == len(tensors)
        for i, (name, a) in enumerate(tensors.items()):
            assert lib.ts_read_name(h, i).decode() == name
            assert lib.ts_read_dtype(h, i) == code_of(a.dtype)
            nd = lib.ts_read_ndim(h, i)
            dims = (c.c_int64 * max(nd, 1))()
            lib.ts_read_dims(h, i, dims)
            assert tuple(dims[j] for j in range(nd)) == a.shape
            assert lib.ts_read_nbytes(h, i) == a.nbytes
            assert c.string_at(lib.ts_read_data(h, i), a.nbytes) \
                == a.tobytes()
    finally:
        lib.ts_read_close(h)


def test_python_reader_reads_what_cc_writes(tmp_path):
    import ctypes as c

    from paddle_tpu.native.dtypes import code_of

    lib, path = _cc_lib(), str(tmp_path / "cc.ptck")
    tensors = _interop_tensors()
    h = lib.ts_write_begin(path.encode())
    assert h
    for name, a in tensors.items():
        dims = (c.c_int64 * max(a.ndim, 1))(*a.shape)
        assert lib.ts_write_add(h, name.encode(), code_of(a.dtype), a.ndim,
                                dims, a.ctypes.data_as(c.c_void_p), a.nbytes)
    assert lib.ts_write_end(h)
    got = load_tensors(path)
    assert list(got) == list(tensors)
    for name, a in tensors.items():
        assert got[name].dtype == a.dtype and got[name].shape == a.shape
        np.testing.assert_array_equal(got[name], a)
    # and the two writers produce the same bytes
    save_tensors(path + ".py", tensors)
    with open(path, "rb") as f1, open(path + ".py", "rb") as f2:
        assert f1.read() == f2.read()


def test_truncated_checkpoint_rejected(tmp_path):
    path = str(tmp_path / "ckpt")
    save_tensors(path, {"w": np.arange(100, dtype=np.float32)})
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(blob[:-7])
    with pytest.raises(IOError, match="truncated"):
        load_tensors(path)


def test_a_library_appears_under_its_name_only_whole(tmp_path, monkeypatch):
    """Several processes build in one checkout (six test workers, a
    trainer beside its servers), and the build lock is a process's own:
    one that found ``lib<name>.so`` while another's linker was writing it
    loaded a part of it (``OSError: file too short``, one start in ten
    with ten processes 0.6 s apart on a fresh checkout). The linker
    writes a temporary beside the library, which is renamed over the
    name; a failed build leaves nothing."""
    import ctypes
    import shutil
    import subprocess

    from paddle_tpu import native

    shutil.copy(os.path.join(native._DIR, "tensor_store.cc"), tmp_path)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    so = str(tmp_path / "libtensor_store.so")
    real, outputs = subprocess.run, []

    def run(cmd, **kw):
        outputs.append(cmd[cmd.index("-o") + 1])
        done = real(cmd, **kw)
        assert not os.path.exists(so)       # not before it is whole
        return done

    monkeypatch.setattr(native.subprocess, "run", run)
    assert native._build("tensor_store") == so
    assert len(outputs) == 1 and outputs[0] != so
    assert sorted(os.listdir(tmp_path)) == ["libtensor_store.so",
                                            "tensor_store.cc"]
    assert ctypes.CDLL(so).ts_write_begin is not None
    os.remove(so)
    (tmp_path / "tensor_store.cc").write_text("this is not C++\n")
    with pytest.raises(subprocess.CalledProcessError):
        native._build("tensor_store")
    assert os.listdir(tmp_path) == ["tensor_store.cc"]
