"""Input layers (reference: python/paddle/fluid/layers/io.py — data:39,
py_reader:636, double_buffer).

py_reader in the reference is an op stack: a LoDTensorBlockingQueue fed
from Python, popped by create_py_reader_op, wrapped by buffered_reader's
async device prefetch (operators/reader/buffered_reader.cc). Here the
executor feeds arrays directly, so PyReader is a host-side prefetcher: a
producer thread pulls batches from the user reader and jax.device_put's
them ahead of the train loop (JAX async dispatch = the double buffer).
"""

from __future__ import annotations

import queue as _queue
import threading

import numpy as np

from ..core.program import default_main_program, default_startup_program

__all__ = ["data", "PyReader", "py_reader", "double_buffer",
           "create_py_reader_by_data", "read_file", "open_files",
           "random_data_generator", "Preprocessor", "load",
           "shuffle", "batch"]


def data(name, shape, dtype="float32", lod_level=0, append_batch_size=True,
         stop_gradient=True):
    """Declare a feed variable. With append_batch_size, a leading -1 batch
    dim is added (reference io.py:39). On TPU the concrete shape is bound at
    compile time from the first feed (bucketing handles variation)."""
    shape = list(shape)
    if append_batch_size:
        shape = [-1] + shape
    block = default_main_program().global_block()
    v = block.create_var(
        name=name, shape=shape, dtype=dtype, is_data=True,
        stop_gradient=stop_gradient, lod_level=lod_level,
    )
    # mirror into startup so program pairs stay consistent (reference parity)
    default_startup_program()
    return v


class PyReader:
    """Iterable device-prefetching reader (reference layers/io.py:636
    py_reader + reader/buffered_reader.cc double buffering).

        reader = PyReader(feed_list=[img, label], capacity=64)
        reader.decorate_batch_generator(gen)   # gen yields tuples of arrays
        for feed in reader():
            exe.run(main, feed=feed, fetch_list=[loss])
    """

    def __init__(self, feed_list=None, capacity=64, use_double_buffer=True,
                 iterable=True):
        self.feed_list = feed_list or []
        self.capacity = capacity
        self.use_double_buffer = use_double_buffer
        self._gen = None

    def decorate_batch_generator(self, reader, places=None):
        self._gen = reader

    def decorate_sample_list_generator(self, reader, places=None):
        """reader yields lists of per-sample tuples (DataFeeder format)."""
        from ..data_feeder import DataFeeder

        feeder = DataFeeder(self.feed_list)

        def gen():
            for samples in reader():
                fd = feeder.feed(samples)
                yield tuple(fd[v.name] for v in self.feed_list)

        self._gen = gen

    def __call__(self):
        return iter(self)

    def __iter__(self):
        import jax

        if self._gen is None:
            raise RuntimeError("decorate a generator before iterating")
        q = _queue.Queue(maxsize=self.capacity)
        stop = object()
        failure = []
        cancelled = threading.Event()

        def _put(item):
            # bounded put that gives up when the consumer walked away
            # (early break from the feed loop): otherwise the producer
            # thread blocks forever pinning `capacity` device batches
            while not cancelled.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except _queue.Full:
                    continue
            return False

        def produce():
            try:
                for batch in self._gen():
                    if self.use_double_buffer:
                        # async device transfer overlaps the training step
                        batch = tuple(jax.device_put(b) for b in batch)
                    if not _put(batch):
                        return
            except BaseException as exc:  # noqa: BLE001 - re-raised below
                # surface producer errors to the consumer: a reader that
                # dies mid-pass must not look like a clean end-of-data
                failure.append(exc)
            finally:
                _put(stop)

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        names = [v.name for v in self.feed_list]
        try:
            while True:
                item = q.get()
                if item is stop:
                    if failure:
                        raise failure[0]
                    return
                yield dict(zip(names, item))
        finally:
            cancelled.set()  # unblock + retire the producer on early exit
            # ...and see it gone: a producer still inside device_put when
            # the interpreter finalizes is killed mid-C++ and aborts the
            # process. Bounded: a generator stuck in user code is left
            # behind rather than waited on forever.
            t.join(timeout=5.0)

    def windows(self, k):
        """Group the reader's feeds into stacked K-windows for
        ``Executor.run_repeated(..., feed_stacked=True)`` — K real
        minibatches per device dispatch (one host round-trip per
        window instead of one per step):

            for window, steps in reader.windows(8):
                exe.run_repeated(main, feed=window, fetch_list=[loss],
                                 steps=steps, feed_stacked=True)

        Yields ``(stacked_feed, steps)``; ``steps`` is the window
        length. The tail window may be shorter, and a batch whose
        shapes differ from the window in progress (e.g. the final
        partial batch) flushes the window early so stacking never mixes
        shapes — each distinct (steps, shape) pair compiles once."""
        if k < 1:
            raise ValueError("windows(k) needs k >= 1; got %r" % (k,))
        from ..reader import stack_feed_window

        buf, shapes = [], None
        for feed in self:
            sig = {n: tuple(np.shape(v)) for n, v in feed.items()}
            if buf and sig != shapes:
                yield stack_feed_window(buf), len(buf)
                buf = []
            shapes = sig
            buf.append(feed)
            if len(buf) == k:
                yield stack_feed_window(buf), len(buf)
                buf = []
        if buf:
            yield stack_feed_window(buf), len(buf)


def py_reader(capacity, shapes, dtypes, lod_levels=None, name=None,
              use_double_buffer=True):
    """Legacy functional form; returns a PyReader without bound feed vars
    (caller supplies dicts)."""
    r = PyReader(capacity=capacity, use_double_buffer=use_double_buffer)
    r.shapes, r.dtypes = shapes, dtypes
    return r


def double_buffer(reader, place=None, name=None):
    """Decorator form over a plain batch reader (reference layers/io.py
    double_buffer): prefetch one batch to device ahead of consumption."""
    import jax

    def buffered():
        q = _queue.Queue(maxsize=2)
        stop = object()

        def produce():
            try:
                for b in reader():
                    q.put(jax.tree_util.tree_map(jax.device_put, b))
            finally:
                q.put(stop)

        threading.Thread(target=produce, daemon=True).start()
        while True:
            item = q.get()
            if item is stop:
                return
            yield item

    return buffered


def create_py_reader_by_data(capacity, feed_list, name=None,
                             use_double_buffer=True):
    """reference layers/io.py create_py_reader_by_data: a PyReader bound
    to existing feed vars."""
    return PyReader(feed_list=feed_list, capacity=capacity,
                    use_double_buffer=use_double_buffer)


def read_file(reader):
    """reference layers/io.py read_file: with op-based file readers gone
    (PyReader feeds the executor directly), this returns the reader's
    bound feed variables — or a Preprocessor's declared outputs."""
    if isinstance(reader, Preprocessor):
        return reader()
    return list(getattr(reader, "feed_list", []) or [])


def open_files(filenames, shapes=None, lod_levels=None, dtypes=None,
               thread_num=None, buffer_size=None, pass_num=1,
               is_test=False):
    """reference layers/io.py open_files over recordio files: returns a
    PyReader-style generator chaining paddle_tpu.recordio_writer files
    (the op-based multi-file reader stack is subsumed by PyReader +
    the native datafeed)."""
    from ..recordio_writer import recordio_reader

    names = [filenames] if isinstance(filenames, str) else list(filenames)

    def gen():
        for _ in range(pass_num):
            for f in names:
                yield from recordio_reader(f)()

    return gen


def random_data_generator(low, high, shapes, lod_levels=None,
                          for_parallel=True):
    """reference layers/io.py random_data_generator: an endless reader of
    uniform random float batches with the given shapes."""
    import numpy as np

    def gen():
        while True:
            yield tuple(np.random.uniform(low, high, s).astype("float32")
                        for s in shapes)

    return gen


class Preprocessor:
    """reference layers/io.py Preprocessor: declare in-graph transforms
    over a reader's outputs. Ops built inside block() are ordinary main-
    program ops; inputs() hands out the reader's feed variables and
    outputs() records the transformed variables, which read_file() (or
    calling the preprocessor) then returns to the model builder.

        p = Preprocessor(py_reader)
        with p.block():
            img, lbl = p.inputs()
            p.outputs(scale(img, 1/255.), lbl)
        img, lbl = p()
    """

    def __init__(self, reader, name=None):
        self._reader = reader
        self._outs = None
        self._in_block = False

    def block(self):
        import contextlib

        @contextlib.contextmanager
        def _ctx():
            self._in_block = True
            yield
            self._in_block = False
            if self._outs is None:
                raise ValueError("Preprocessor.block() ended without "
                                 "outputs()")

        return _ctx()

    def inputs(self):
        if not self._in_block:
            raise RuntimeError("inputs() must be called inside block()")
        return list(getattr(self._reader, "feed_list", []) or [])

    def outputs(self, *outs):
        if not self._in_block:
            raise RuntimeError("outputs() must be called inside block()")
        self._outs = list(outs)

    def __call__(self):
        if self._outs is None:
            raise RuntimeError("define the block() transforms first")
        return list(self._outs)


def load(out, file_path, load_as_fp16=False):
    """reference layers/io.py load: fill `out` from a saved checkpoint
    file (io.py combined format) — immediate scope load."""
    import os

    import numpy as np

    from ..core.scope import global_scope
    from ..io import _load_blob

    _, data = _load_blob(os.path.dirname(file_path) or ".",
                         os.path.basename(file_path))
    if out.name not in data:
        raise RuntimeError("%s lacks variable %r" % (file_path, out.name))
    arr = np.asarray(data[out.name])
    if load_as_fp16:
        arr = arr.astype(np.float16)
    global_scope().set_var(out.name, arr)
    return out


def shuffle(reader, buffer_size):
    """reference layers/io.py shuffle (op-based reader decorator): works
    over PyReader generators or plain reader creators here."""
    from ..reader import shuffle as _shuffle

    if isinstance(reader, PyReader):
        reader._gen = _shuffle(reader._gen, buffer_size)
        return reader
    return _shuffle(reader, buffer_size)


def batch(reader, batch_size):
    """reference layers/io.py batch decorator (see shuffle)."""
    from ..reader import batch as _batch

    if isinstance(reader, PyReader):
        reader._gen = _batch(reader._gen, batch_size)
        return reader
    return _batch(reader, batch_size)
