"""Native (C++) runtime: GIL-free data-pipeline core (libptdata.so).

Reference parity: the reference's C++ dataloader stack
(paddle/fluid/operators/reader/blocking_queue.h, buffered_reader.cc and the
fluid dataloader worker processes). Here the native side owns the whole
epoch pipeline — shuffle, shard slicing, multithreaded row gather, prefetch
ring — for datasets backed by contiguous host arrays; Python only wraps the
popped buffers as Tensors.

The library compiles on first use (g++, ~1s) into the checkout's cache
root, under a name that carries a hash of its source — only a library
built from the committed source is ever loaded.  A host without a C++
toolchain runs the pure-Python path (`available()` -> False, with a
warning); a compile or load that fails raises.
"""
from __future__ import annotations

import ctypes
import functools
import hashlib
import os
import shutil
import subprocess
import threading
import warnings

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_lib = None
_lock = threading.Lock()
_build_err = None


@functools.lru_cache(maxsize=None)
def _so_path(src_name):
    """``<cache root>/native/lib<stem>-<source sha>.so`` — where the build
    of exactly this source lives.  Read once per process, outside the
    build lock."""
    from paddle_tpu.utils.compile_cache import cache_root
    with open(os.path.join(_DIR, src_name), "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    return os.path.join(
        cache_root(), "native",
        f"lib{os.path.splitext(src_name)[0]}-{digest}.so")


def _build_and_load(src_name, so_path):
    """Shared build-or-load: compile `so_path` unless that exact build
    exists, then dlopen. Raises FileNotFoundError without a toolchain,
    RuntimeError (with the compiler's stderr) when the source does not
    compile."""
    if not os.path.exists(so_path):
        if shutil.which("g++") is None:
            raise FileNotFoundError("g++")
        os.makedirs(os.path.dirname(so_path), exist_ok=True)
        tmp = f"{so_path}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                ["g++", "-O3", "-std=c++17", "-fPIC", "-pthread",
                 "-shared", "-o", tmp, os.path.join(_DIR, src_name)],
                check=True, capture_output=True, text=True)
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"{src_name} failed to compile:\n{e.stderr}") from e
        os.replace(tmp, so_path)     # concurrent builders: last one wins
    return ctypes.CDLL(so_path)


def _warn_no_toolchain(what):
    warnings.warn(f"no C++ toolchain (g++): {what} runs the pure-Python "
                  f"path", RuntimeWarning, stacklevel=3)


def _load():
    global _lib, _build_err
    so_path = _so_path("ptdata.cc")
    with _lock:
        if _lib is not None or _build_err is not None:
            return _lib
        try:
            lib = _build_and_load("ptdata.cc", so_path)
        except FileNotFoundError as e:      # no toolchain -> Python path
            _warn_no_toolchain("the data pipeline")
            _build_err = e
            return None
        lib.ptdata_shuffle.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64]
        lib.ptdata_shard_indices.argtypes = [
            ctypes.c_int64, ctypes.c_uint64, ctypes.c_int, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_void_p]
        lib.ptdata_gather.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int]
        lib.ptdata_loader_create.restype = ctypes.c_void_p
        lib.ptdata_loader_create.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int, ctypes.c_int64, ctypes.c_int64, ctypes.c_uint64,
            ctypes.c_int, ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int, ctypes.c_int]
        lib.ptdata_loader_num_batches.restype = ctypes.c_int64
        lib.ptdata_loader_num_batches.argtypes = [ctypes.c_void_p]
        lib.ptdata_loader_next.restype = ctypes.c_int64
        lib.ptdata_loader_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p)]
        lib.ptdata_loader_reset.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.ptdata_loader_destroy.argtypes = [ctypes.c_void_p]
        lib.ptdata_augment_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.c_uint64, ctypes.c_int]
        _lib = lib
        return _lib


def available():
    return _load() is not None


def shuffle_indices(n, seed):
    """Deterministic Fisher-Yates permutation of arange(n) in C++."""
    lib = _load()
    idx = np.arange(n, dtype=np.int64)
    if lib is None:
        return np.random.default_rng(seed).permutation(n)
    lib.ptdata_shuffle(idx.ctypes.data_as(ctypes.c_void_p), n, seed)
    return idx


def shard_indices(n, seed, shuffle, nranks, rank):
    """This rank's epoch indices (shuffled, padded, strided) — the
    DistributedBatchSampler index math, natively."""
    lib = _load()
    per = (n + nranks - 1) // nranks
    out = np.empty(per, dtype=np.int64)
    if lib is None:
        idx = np.arange(n)
        if shuffle:
            idx = np.random.default_rng(seed).permutation(n)
        idx = np.resize(idx, per * nranks)  # pad by cycling, like the C++
        return idx[rank::nranks].astype(np.int64)
    lib.ptdata_shard_indices(n, seed, 1 if shuffle else 0, nranks, rank,
                             out.ctypes.data_as(ctypes.c_void_p))
    return out


def gather_rows(src, indices, nthreads=None):
    """dst[i] = src[indices[i]] with multithreaded memcpy (no GIL)."""
    lib = _load()
    src = np.ascontiguousarray(src)
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    if lib is None:
        return src[indices]
    out = np.empty((len(indices),) + src.shape[1:], dtype=src.dtype)
    row_bytes = src.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    nthreads = nthreads or min(8, os.cpu_count() or 1)
    lib.ptdata_gather(src.ctypes.data_as(ctypes.c_void_p), row_bytes,
                      indices.ctypes.data_as(ctypes.c_void_p), len(indices),
                      out.ctypes.data_as(ctypes.c_void_p), nthreads)
    return out


def augment_batch(images, out_size, pad=0, random_crop=False,
                  random_flip=False, mean=0.0, std=1.0, to_chw=True,
                  seed=0, nthreads=None):
    """Fused native augmentation: zero-pad -> (random|center) crop ->
    random hflip -> /255 -> normalize -> float32 CHW/HWC, threaded over
    the batch with no GIL. images: uint8 [N, H, W, C]. Falls back to a
    numpy implementation when the native library is unavailable."""
    images = np.ascontiguousarray(images, dtype=np.uint8)
    n, h, w, c = images.shape
    oh, ow = (out_size, out_size) if isinstance(out_size, int) else out_size
    mean = np.ascontiguousarray(mean, np.float32).reshape(-1)
    std = np.ascontiguousarray(std, np.float32).reshape(-1)
    if mean.size == 1:
        mean = np.repeat(mean, c)
    if std.size == 1:
        std = np.repeat(std, c)
    if mean.size != c or std.size != c:
        raise ValueError(
            f"mean/std must have {c} entries (or 1), got "
            f"{mean.size}/{std.size}")
    lib = _load()
    if lib is not None:
        shape = (n, c, oh, ow) if to_chw else (n, oh, ow, c)
        out = np.empty(shape, np.float32)
        nthreads = nthreads or min(8, os.cpu_count() or 1)
        lib.ptdata_augment_batch(
            images.ctypes.data_as(ctypes.c_void_p), n, h, w, c,
            out.ctypes.data_as(ctypes.c_void_p), oh, ow, int(pad),
            int(bool(random_crop)), int(bool(random_flip)),
            mean.ctypes.data_as(ctypes.c_void_p),
            std.ctypes.data_as(ctypes.c_void_p), int(bool(to_chw)),
            ctypes.c_uint64(seed), nthreads)
        return out
    # numpy fallback: same semantics (incl. randomness), python-speed
    rng = np.random.default_rng(seed)
    padded = np.zeros((n, h + 2 * pad, w + 2 * pad, c), np.float32)
    padded[:, pad:pad + h, pad:pad + w] = images
    max_y = max(h + 2 * pad - oh, 0)
    max_x = max(w + 2 * pad - ow, 0)
    out = np.empty((n, oh, ow, c), np.float32)
    for i in range(n):
        oy = int(rng.integers(0, max_y + 1)) if random_crop else max_y // 2
        ox = int(rng.integers(0, max_x + 1)) if random_crop else max_x // 2
        crop = padded[i, oy:oy + oh, ox:ox + ow]
        if random_flip and rng.integers(0, 2):
            crop = crop[:, ::-1]
        out[i] = crop
    outv = (out / 255.0 - mean) / std
    return outv.transpose(0, 3, 1, 2).copy() if to_chw else outv


class NativeLoader:
    """Background C++ epoch loader over contiguous arrays.

    arrays: list of np.ndarray sharing dim 0 (the sample dim). Iterating
    yields tuples of np.ndarray batches, assembled and prefetched by the
    native producer thread. Not thread-safe; one iterator at a time.
    """

    def __init__(self, arrays, batch_size, seed=0, shuffle=False,
                 drop_last=False, nranks=1, rank=0, nthreads=None,
                 prefetch=4):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"libptdata unavailable: {_build_err}")
        self._lib = lib
        self.arrays = [np.ascontiguousarray(a) for a in arrays]
        n = self.arrays[0].shape[0]
        if any(a.shape[0] != n for a in self.arrays):
            raise ValueError("arrays must share dim 0")
        self.batch_size = int(batch_size)
        self.n_rows = n
        self._row_bytes = [
            a.itemsize * int(np.prod(a.shape[1:], dtype=np.int64))
            for a in self.arrays]
        srcs = (ctypes.c_void_p * len(self.arrays))(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in self.arrays])
        rbs = (ctypes.c_int64 * len(self.arrays))(*self._row_bytes)
        self._h = lib.ptdata_loader_create(
            srcs, rbs, len(self.arrays), n, self.batch_size, seed,
            1 if shuffle else 0, 1 if drop_last else 0, nranks, rank,
            nthreads or min(8, os.cpu_count() or 1), prefetch)
        self._epoch_seed = seed
        self._dirty = False   # producer mid-epoch (iterator abandoned early)

    def __len__(self):
        return self._lib.ptdata_loader_num_batches(self._h)

    def __iter__(self):
        # every __iter__ starts a FULL epoch (matching the Python path): if a
        # previous iterator was abandoned mid-epoch, restart the producer
        if self._dirty:
            self._epoch_seed += 1
            self._lib.ptdata_loader_reset(self._h, self._epoch_seed)
        self._dirty = True
        while True:
            bufs = [np.empty((self.batch_size,) + a.shape[1:], dtype=a.dtype)
                    for a in self.arrays]
            ptrs = (ctypes.c_void_p * len(bufs))(
                *[b.ctypes.data_as(ctypes.c_void_p).value for b in bufs])
            rows = self._lib.ptdata_loader_next(self._h, ptrs)
            if rows <= 0:
                self._epoch_seed += 1
                self._lib.ptdata_loader_reset(self._h, self._epoch_seed)
                self._dirty = False
                return
            yield tuple(b[:rows] for b in bufs)

    def close(self):
        if getattr(self, "_h", None):
            self._lib.ptdata_loader_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ------------------------------------------------------- PS sparse table
_pstable_lib = None
_pstable_err = None


def _pstable():
    """Load (building on first use) the native PS table kernels; None
    when no toolchain is available (callers fall back to numpy)."""
    global _pstable_lib, _pstable_err
    so_path = _so_path("pstable.cc")
    with _lock:
        if _pstable_lib is not None or _pstable_err is not None:
            return _pstable_lib
        try:
            lib = _build_and_load("pstable.cc", so_path)
        except FileNotFoundError as e:
            _warn_no_toolchain("the PS sparse table")
            _pstable_err = e
            return None
        lib.pstable_pull.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int]
        lib.pstable_push.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_float,
            ctypes.c_float, ctypes.c_int]
        _pstable_lib = lib
        return _pstable_lib


def pstable_available():
    return _pstable() is not None


def pstable_pull(data, ids, row_offset, n_threads=4):
    """data [R, D] float32 (C-contiguous), ids int64 any shape ->
    [*ids.shape, D] float32 (zeros for out-of-shard rows)."""
    lib = _pstable()
    ids = np.ascontiguousarray(ids, np.int64)
    flat = ids.reshape(-1)
    out = np.empty((flat.size, data.shape[1]), np.float32)
    lib.pstable_pull(
        data.ctypes.data_as(ctypes.c_void_p), data.shape[0], data.shape[1],
        flat.ctypes.data_as(ctypes.c_void_p), flat.size, row_offset,
        out.ctypes.data_as(ctypes.c_void_p), n_threads)
    return out.reshape(ids.shape + (data.shape[1],))


def pstable_push(data, acc, ids, grads, row_offset, lr, eps, optimizer):
    """In-place merged sparse update; optimizer 'sgd'|'adagrad'."""
    lib = _pstable()
    ids = np.ascontiguousarray(np.asarray(ids).reshape(-1), np.int64)
    grads = np.ascontiguousarray(
        np.asarray(grads, np.float32).reshape(ids.size, data.shape[1]))
    lib.pstable_push(
        data.ctypes.data_as(ctypes.c_void_p),
        acc.ctypes.data_as(ctypes.c_void_p) if acc is not None else None,
        data.shape[0], data.shape[1],
        ids.ctypes.data_as(ctypes.c_void_p), ids.size, row_offset,
        grads.ctypes.data_as(ctypes.c_void_p), lr, eps,
        1 if optimizer == "adagrad" else 0)
