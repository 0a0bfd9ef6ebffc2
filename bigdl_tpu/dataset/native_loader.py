"""Native-accelerated batching + prefetching device feed.

Reference: MTLabeledBGRImgToBatch (dataset/image/MTLabeledBGRImgToBatch.scala)
-- the reference's multi-threaded batch assembly -- and the double-buffered
device-feed requirement in SURVEY.md section 7 ('Spark-as-ingest without
Spark-in-the-loop': pull host shards into a device-feed queue while the step
never leaves the device).

Two pieces:

- ``NativeBatcher``: gathers + channel-normalizes minibatches through the
  C++ kernel (native/batch_assembler.cpp, built on first use with g++,
  ctypes binding -- no pybind11).  Falls back to numpy, with a warning,
  where no compiler exists; ``NativeBatcher.lib`` says which ran.
- ``Prefetcher``: a bounded background queue that assembles the next batches
  while the device is busy -- the ctypes call releases the GIL so assembly
  overlaps with the training step.
"""

import ctypes
import hashlib
import logging
import os
import platform
import queue
import subprocess
import threading
from typing import Iterator, Optional

import numpy as np

log = logging.getLogger("bigdl_tpu.dataset")

_LIB = None
_TRIED = False


def build_native_lib(name: str):
    """Build ``native/<name>.cpp`` with g++ into ``build/`` (git-ignored)
    and load it.  The file name carries a digest of the source and the
    machine's architecture, so what loads was built from the current
    source: a stale ``build/*.so`` that came along with a copy of the
    tree is never picked up.  Callers fall back to pure python/numpy
    when the build fails."""
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    src = os.path.join(here, "native", f"{name}.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(
            f.read() + platform.machine().encode()).hexdigest()[:12]
    out_dir = os.path.join(here, "build")
    so_path = os.path.join(out_dir, f"lib{name}-{digest}.so")
    if not os.path.exists(so_path):
        os.makedirs(out_dir, exist_ok=True)
        tmp = f"{so_path}.{os.getpid()}.tmp"
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, src, "-lpthread"],
            check=True, capture_output=True)
        os.replace(tmp, so_path)     # atomic: concurrent builders agree
    return ctypes.CDLL(so_path)


def _build_and_load():
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    try:
        lib = build_native_lib("batch_assembler")
        lib.bigdl_gather_normalize.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int64, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int,
        ]
        lib.bigdl_gather_labels.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int64, ctypes.POINTER(ctypes.c_int32),
        ]
        _LIB = lib
    except Exception as e:  # toolchain missing -> numpy fallback
        log.warning("native batch assembler unavailable (%s); numpy fallback", e)
        _LIB = None
    return _LIB


def _fptr(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _i64ptr(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


class NativeBatcher:
    """Index-gather + normalize minibatches from a contiguous sample pool.

    ``features``: (N, ...) float32; ``labels``: (N, ...) int32 or None.
    """

    def __init__(self, features: np.ndarray, labels: Optional[np.ndarray],
                 mean=None, std=None, n_threads: int = 0):
        self.features = np.ascontiguousarray(features, np.float32)
        self.pool = self.features.reshape(len(features), -1)
        self.sample_shape = features.shape[1:]
        self.labels = (None if labels is None
                       else np.ascontiguousarray(labels, np.int32).reshape(
                           len(labels), -1))
        self.label_shape = () if labels is None else np.shape(labels)[1:]
        self.channels = 0
        self.mean = np.zeros(1, np.float32)
        self.std = np.ones(1, np.float32)
        if mean is not None:
            self.mean = np.ascontiguousarray(mean, np.float32)
            self.std = np.ascontiguousarray(std, np.float32)
            self.channels = self.mean.size
        self.n_threads = n_threads or min(8, os.cpu_count() or 1)
        self.lib = _build_and_load()

    def batch(self, indices: np.ndarray):
        indices = np.ascontiguousarray(indices, np.int64)
        b = len(indices)
        out = np.empty((b, self.pool.shape[1]), np.float32)
        if self.lib is not None:
            self.lib.bigdl_gather_normalize(
                _fptr(self.pool), _i64ptr(indices), b, self.pool.shape[1],
                _fptr(self.mean), _fptr(self.std), self.channels, _fptr(out),
                self.n_threads)
        else:
            out[:] = self.pool[indices]
            if self.channels:
                shaped = out.reshape((b,) + self.sample_shape)
                shaped -= self.mean
                shaped /= self.std
        x = out.reshape((b,) + self.sample_shape)
        if self.labels is None:
            return x, None
        lab = np.empty((b, self.labels.shape[1]), np.int32)
        if self.lib is not None:
            self.lib.bigdl_gather_labels(
                self.labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                _i64ptr(indices), b, self.labels.shape[1],
                lab.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        else:
            lab[:] = self.labels[indices]
        return x, lab.reshape((b,) + self.label_shape)


class Prefetcher:
    """Bounded background prefetch queue over any iterator (the
    double-buffered device feed; reference: MTLabeledBGRImgToBatch's
    producer threads)."""

    _DONE = object()

    def __init__(self, it: Iterator, depth: int = 2):
        self.q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._thread = threading.Thread(target=self._run, args=(it,),
                                        daemon=True)
        self._thread.start()

    def _run(self, it):
        try:
            for item in it:
                self.q.put(item)
        finally:
            self.q.put(self._DONE)

    def __iter__(self):
        return self

    def __next__(self):
        item = self.q.get()
        if item is self._DONE:
            raise StopIteration
        return item


def prefetch(iterator: Iterator, depth: int = 2) -> Iterator:
    return Prefetcher(iterator, depth)
