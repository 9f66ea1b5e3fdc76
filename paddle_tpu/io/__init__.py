"""Data loading. Reference: python/paddle/io/__init__.py + fluid dataloader.

TPU-first data path: the DataLoader keeps a background thread pool for
batch assembly + an async host→device staging step (double buffering), which
plays the role of the reference's C++ multiprocess DataLoaderIter: keep the
accelerator fed so step time is never input-bound.
"""
from __future__ import annotations

import itertools
import queue
import threading

import numpy as np

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.framework.state import _rng
from paddle_tpu.observability.spans import span


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset does not support indexing")

    def __len__(self):
        raise RuntimeError("IterableDataset has no len()")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __len__(self):
        return min(len(d) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            item = d[idx]
            out.extend(item if isinstance(item, (tuple, list)) else [item])
        return tuple(out)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        for d in self.datasets:
            yield from d


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cum = np.cumsum([len(d) for d in self.datasets])

    def __len__(self):
        return int(self.cum[-1])

    def __getitem__(self, idx):
        di = int(np.searchsorted(self.cum, idx, side="right"))
        prev = 0 if di == 0 else int(self.cum[di - 1])
        return self.datasets[di][idx - prev]


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    if all(isinstance(l, float) for l in lengths):
        n = len(dataset)
        lengths = [int(np.floor(n * l)) for l in lengths]
        lengths[-1] = n - sum(lengths[:-1])
    total = sum(lengths)
    perm = np.random.default_rng(_rng.seed_val).permutation(total)
    out = []
    offset = 0
    for l in lengths:
        out.append(Subset(dataset, perm[offset:offset + l].tolist()))
        offset += l
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples
        self.generator = generator

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        rng = np.random.default_rng()
        if self.replacement:
            return iter(rng.integers(0, n, self.num_samples).tolist())
        return iter(rng.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, dtype=np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        rng = np.random.default_rng()
        idx = rng.choice(len(self.weights), self.num_samples,
                         replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False, batch_size=1,
                 drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Shard-aware sampler. Reference: python/paddle/io/__init__.py
    DistributedBatchSampler. On TPU the `rank` is the process index of a
    multi-host jax.distributed run (data parallel over DCN)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        if num_replicas is None:
            from paddle_tpu import distributed as dist
            num_replicas = dist.get_world_size()
        if rank is None:
            from paddle_tpu import distributed as dist
            rank = dist.get_rank()
        self.nranks = num_replicas
        self.local_rank = rank
        self.epoch = 0
        self.num_samples = int(np.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        indices = np.arange(n)
        if self.shuffle:
            rng = np.random.default_rng(self.epoch)
            indices = rng.permutation(n)
        indices = np.concatenate([indices, indices[:self.total_size - n]])
        indices = indices[self.local_rank:self.total_size:self.nranks]
        batch = []
        for idx in indices.tolist():
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch):
        self.epoch = epoch


_worker_info = threading.local()


def get_worker_info():
    return getattr(_worker_info, "info", None)


class WorkerInfo:
    def __init__(self, id, num_workers, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, Tensor):
        return Tensor(np.stack([np.asarray(b._value) for b in batch]))
    if isinstance(sample, np.ndarray):
        return Tensor(np.stack(batch))
    if isinstance(sample, (int, float, np.integer, np.floating)):
        return Tensor(np.asarray(batch))
    if isinstance(sample, (list, tuple)):
        return type(sample)(default_collate_fn([b[i] for b in batch])
                            for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    return batch


def _collate_numpy(batch):
    """default_collate_fn's structure, NUMPY leaves only — the worker-
    process collate (a forked child must never touch JAX/XLA: the
    parent's runtime threads don't survive the fork)."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float, np.integer, np.floating)):
        return np.asarray(batch)
    if isinstance(sample, (list, tuple)):
        return type(sample)(_collate_numpy([b[i] for b in batch])
                            for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: _collate_numpy([b[k] for b in batch]) for k in sample}
    return batch


def _tree_map_np(obj, fn):
    if isinstance(obj, np.ndarray):
        return fn(obj)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_map_np(o, fn) for o in obj)
    if isinstance(obj, dict):
        return {k: _tree_map_np(v, fn) for k, v in obj.items()}
    return obj


def _shm_pack(obj):
    """numpy leaves -> shared-memory descriptors (zero pickle-copy for
    the bulk bytes; reference use_shared_memory semantics)."""
    from multiprocessing import shared_memory
    blocks = []

    def pack(a):
        a = np.ascontiguousarray(a)
        if a.nbytes == 0:
            return ("__np__", a)
        shm = shared_memory.SharedMemory(create=True, size=a.nbytes)
        np.ndarray(a.shape, a.dtype, buffer=shm.buf)[...] = a
        name = shm.name
        # ownership transfers to the CONSUMER (parent unlinks after the
        # copy); drop this process's resource_tracker registration or
        # every worker shutdown spews leaked-segment warnings
        try:
            from multiprocessing import resource_tracker
            resource_tracker.unregister(shm._name, "shared_memory")
        except Exception:
            pass
        blocks.append(shm)
        return ("__shm__", name, a.shape, str(a.dtype))

    out = _tree_map_np(obj, pack)
    # close OUR handles (the segment lives until the parent unlinks)
    for b in blocks:
        b.close()
    return out


def _shm_unpack(obj):
    from multiprocessing import shared_memory

    def unpack(o):
        if isinstance(o, tuple) and o and o[0] == "__shm__":
            _, name, shape, dtype = o
            shm = shared_memory.SharedMemory(name=name)
            try:
                return np.array(np.ndarray(shape, dtype, buffer=shm.buf))
            finally:
                shm.close()
                shm.unlink()
        if isinstance(o, tuple) and o and o[0] == "__np__":
            return o[1]
        if isinstance(o, (list, tuple)):
            return type(o)(unpack(x) for x in o)
        if isinstance(o, dict):
            return {k: unpack(v) for k, v in o.items()}
        return o

    return unpack(obj)


def _shm_release(obj):
    """Unlink every shm descriptor in a payload WITHOUT copying it
    (cleanup for batches the consumer never took)."""
    from multiprocessing import shared_memory
    if isinstance(obj, tuple) and obj and obj[0] == "__shm__":
        try:
            shm = shared_memory.SharedMemory(name=obj[1])
            shm.close()
            shm.unlink()
        except FileNotFoundError:
            pass
        return
    if isinstance(obj, (list, tuple)):
        for o in obj:
            _shm_release(o)
    elif isinstance(obj, dict):
        for o in obj.values():
            _shm_release(o)


def _process_worker_loop(dataset, wid, num_workers, idx_q, res_q,
                         use_shm, worker_init_fn, default_collate):
    """Worker-process main (reference fluid/dataloader/worker.py
    _worker_loop): fetch index batches, run __getitem__ + transforms,
    collate to numpy, ship via shared memory. No JAX in here."""
    import traceback as _tb
    _worker_info.info = WorkerInfo(wid, num_workers, dataset)
    if worker_init_fn is not None:
        try:
            worker_init_fn(wid)
        except Exception:
            res_q.put((-1, "err", _tb.format_exc()))
            return
    while True:
        task = idx_q.get()
        if task is None:
            return
        i, idxs = task
        try:
            items = [dataset[j] for j in idxs]
            data = _collate_numpy(items) if default_collate else items
            payload = _shm_pack(data) if use_shm else data
            res_q.put((i, "ok", payload))
        except Exception:
            res_q.put((i, "err", _tb.format_exc()))


class DataLoader:
    """Reference: python/paddle/io/dataloader. Three batch-producing
    paths, fastest applicable wins:
      1. native C++ prefetch ring (array-backed datasets, libptdata);
      2. REAL worker processes (r5, reference dataloader_iter.py +
         worker.py): map-style datasets whose samples are numpy/python —
         __getitem__ + transforms run GIL-free in forked children,
         batches return through shared memory, the parent converts to
         device tensors;
      3. threaded prefetch (iterable datasets, tensor-producing
         datasets, or use_process_workers=False)."""

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False, use_process_workers=None):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = max(2, prefetch_factor)
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = batch_sampler.batch_size
        elif not self._iterable_mode:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle,
                batch_size=batch_size if batch_size is not None else 1,
                drop_last=drop_last)
            self.batch_size = batch_size
        else:
            self.batch_sampler = None
            self.batch_size = batch_size
        self.drop_last = drop_last
        self.use_shared_memory = use_shared_memory
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.use_process_workers = use_process_workers
        self._native_loader = None
        self._native_src_ids = None
        self._native_active = False

    def _process_mode(self):
        """Resolve whether num_workers>0 means PROCESSES here. Explicit
        flag wins; AUTO probes one sample — numpy/python samples go to
        forked workers, tensor-producing datasets stay on threads (a
        forked child must not touch the parent's XLA runtime, and
        device-array datasets gain nothing from escaping the GIL)."""
        if self.num_workers <= 0 or self._iterable_mode:
            return False
        if self.use_process_workers is not None:
            return bool(self.use_process_workers)
        cached = getattr(self, "_process_mode_cache", None)
        if cached is not None:
            return cached
        import multiprocessing
        if "fork" not in multiprocessing.get_all_start_methods():
            self._process_mode_cache = False
            return False
        try:
            first = next(iter(self.batch_sampler))[0]
            sample = self.dataset[first]
        except Exception:
            self._process_mode_cache = False
            return False
        ok = [True]

        def chk(o):
            if isinstance(o, (np.ndarray, int, float, str, bytes,
                              np.integer, np.floating)):
                return
            if isinstance(o, (list, tuple)):
                for x in o:
                    chk(x)
                return
            if isinstance(o, dict):
                for x in o.values():
                    chk(x)
                return
            ok[0] = False

        chk(sample)
        self._process_mode_cache = ok[0]
        return ok[0]

    def _iter_process_workers(self):
        """Reference dataloader_iter._DataLoaderIterMultiProcess: forked
        workers + shared-memory results + ordered reassembly."""
        import multiprocessing
        ctx = multiprocessing.get_context("fork")
        batches = list(self.batch_sampler)
        cap = self.prefetch_factor * self.num_workers
        idx_q = ctx.Queue()
        res_q = ctx.Queue()
        default_collate = self.collate_fn is default_collate_fn
        use_shm = self.use_shared_memory
        procs = [ctx.Process(
            target=_process_worker_loop,
            args=(self.dataset, w, self.num_workers, idx_q, res_q,
                  use_shm, self.worker_init_fn, default_collate),
            daemon=True) for w in range(self.num_workers)]
        import warnings as _warnings
        with _warnings.catch_warnings():
            # the interpreter warns that fork + multithreaded JAX can
            # deadlock; our children never touch JAX (numpy-only worker
            # loop, enforced by the _process_mode sample probe), which
            # is the same contract torch/paddle fork workers run under
            _warnings.simplefilter("ignore", RuntimeWarning)
            for p in procs:
                p.start()
        # bound BEFORE the try: the finally block below reads `results`,
        # and an exception while dispatching the first batches must
        # surface as itself, not as a masking NameError
        results = {}
        try:
            sent = 0
            for i, b in enumerate(batches[:cap]):
                idx_q.put((i, list(b)))
                sent += 1
            for i in range(len(batches)):
                while i not in results:
                    try:
                        j, status, payload = res_q.get(
                            timeout=self.timeout or 5.0)
                    except queue.Empty:
                        if self.timeout:
                            raise RuntimeError(
                                f"DataLoader worker timed out after "
                                f"{self.timeout}s")
                        if not any(p.is_alive() for p in procs) and \
                                res_q.empty():
                            raise RuntimeError(
                                "DataLoader worker processes died "
                                "unexpectedly")
                        continue
                    if status == "err":
                        raise RuntimeError(
                            f"DataLoader worker raised:\n{payload}")
                    results[j] = payload
                    if sent < len(batches):
                        idx_q.put((sent, list(batches[sent])))
                        sent += 1
                payload = results.pop(i)
                data = _shm_unpack(payload) if use_shm else payload
                if default_collate:
                    yield _tree_map_np(data, Tensor)
                else:
                    yield self.collate_fn(data)
        finally:
            for _ in procs:
                idx_q.put(None)
            for p in procs:
                p.join(timeout=2.0)
            for p in procs:
                if p.is_alive():
                    p.terminate()
            if use_shm:
                # early close / worker error: in-flight payloads hold
                # shm segments the workers UNREGISTERED (ownership was
                # handed to us) — unlink them or they outlive the
                # process and accumulate in /dev/shm
                leftovers = list(results.values())
                while True:
                    try:
                        _, status, payload = res_q.get_nowait()
                    except queue.Empty:
                        break
                    except (OSError, ValueError):
                        break
                    if status == "ok":
                        leftovers.append(payload)
                for payload in leftovers:
                    try:
                        _shm_release(payload)
                    except Exception:
                        pass

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no len()")
        return len(self.batch_sampler)

    def _iter_batches(self):
        if self._iterable_mode:
            it = iter(self.dataset)
            while True:
                batch = list(itertools.islice(it, self.batch_size or 1))
                if not batch:
                    return
                if len(batch) < (self.batch_size or 1) and self.drop_last:
                    return
                yield self.collate_fn(batch)
        else:
            for idx_batch in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in idx_batch])

    # ---- native (C++) fast path ----
    def _native_arrays(self):
        """Contiguous host arrays backing the dataset, or None. Datasets can
        opt in by defining native_arrays() (only valid when __getitem__ does
        no per-sample Python transform work)."""
        if self.collate_fn is not default_collate_fn:
            return None
        if hasattr(self.dataset, "native_arrays"):
            try:
                arrays = [np.asarray(a) for a in self.dataset.native_arrays()]
            except Exception:
                return None
        elif isinstance(self.dataset, TensorDataset):
            try:
                arrays = [np.asarray(t._value if isinstance(t, Tensor) else t)
                          for t in self.dataset.tensors]
            except Exception:
                return None
        else:
            return None
        # zero-copy only: a contiguity COPY would silently freeze the data
        # (in-place mutation visible on the Python path, stale here)
        if any(not a.flags["C_CONTIGUOUS"] for a in arrays):
            return None
        return arrays

    def _native_iter(self):
        """C++ epoch pipeline (shuffle+gather+prefetch off-GIL) when the
        dataset is array-backed and the sampling pattern is expressible
        (plain sequential/shuffled full-epoch BatchSampler)."""
        from paddle_tpu import native
        if not native.available() or self._iterable_mode:
            return None
        bs = self.batch_sampler
        if type(bs) is not BatchSampler:
            return None
        if type(bs.sampler) is SequenceSampler:
            shuffle = False
        elif type(bs.sampler) is RandomSampler and \
                not bs.sampler.replacement and bs.sampler._num_samples is None:
            shuffle = True
        else:
            return None
        srcs = self._native_sources()
        if srcs is None:
            return None
        rebuild = self._native_loader is None or \
            self._native_src_ids is None or \
            len(srcs) != len(self._native_src_ids) or \
            any(a is not b for a, b in zip(srcs, self._native_src_ids))
        if rebuild and self._native_active:
            return None   # can't swap the loader under a live iterator
        if rebuild:
            # (re)build when the backing tensors were rebound — keeps the
            # native path semantics aligned with the Python path, which
            # re-reads the dataset every epoch
            arrays = self._native_arrays()
            if arrays is None or arrays[0].shape[0] == 0:
                return None
            if self._native_loader is not None:
                self._native_loader.close()
            # match the Python path's shuffle entropy: deterministic only
            # when the user explicitly seeded the framework
            seed = _rng.seed_val if _rng.seeded else int(
                np.random.SeedSequence().entropy & ((1 << 63) - 1))
            self._native_loader = native.NativeLoader(
                arrays, bs.batch_size, seed=seed, shuffle=shuffle,
                drop_last=bs.drop_last, nthreads=self.num_workers or None)
            self._native_src_ids = srcs   # strong refs: identity is stable

        def gen():
            # claim the native stream at FIRST consumption (not creation):
            # a second live iterator falls back to the Python path instead
            # of resetting the shared producer mid-epoch
            if self._native_active:
                yield from self._iter_batches()
                return
            self._native_active = True
            try:
                for bufs in self._native_loader:
                    yield tuple(Tensor(b) for b in bufs)
            finally:
                self._native_active = False
        return gen()

    def _native_sources(self):
        """The dataset's backing buffer objects (STRONG refs — identity
        comparison detects rebinds; holding them prevents id reuse).
        None = not array-backed."""
        if self.collate_fn is not default_collate_fn:
            return None
        if hasattr(self.dataset, "native_arrays"):
            try:
                return list(self.dataset.native_arrays())
            except Exception:
                return None
        if isinstance(self.dataset, TensorDataset):
            return [t._value if isinstance(t, Tensor) else t
                    for t in self.dataset.tensors]
        return None

    def __iter__(self):
        """Every path's batches, each ``next()`` of the path under an
        ``io.next`` span: from the consumer asking to the batch being
        ready, once per batch delivered (the ``yield`` lies outside)."""
        batches, end = self._iter_path(), object()
        try:
            while True:
                with span("io.next") as wait:
                    batch = next(batches, end)
                    if batch is end:
                        wait.discard()
                        return
                yield batch
        finally:
            batches.close()

    def _iter_path(self):
        nat = self._native_iter()
        if nat is not None:
            yield from nat
            return
        if self.num_workers == 0:
            yield from self._iter_batches()
            return
        if self._process_mode():
            yield from self._iter_process_workers()
            return
        # threaded prefetch: bounded queue keeps up to prefetch_factor *
        # num_workers batches in flight
        q = queue.Queue(maxsize=self.prefetch_factor * self.num_workers)
        sentinel = object()

        if self._iterable_mode:
            def producer():
                _worker_info.info = WorkerInfo(0, self.num_workers, self.dataset)
                try:
                    for b in self._iter_batches():
                        q.put(b)
                finally:
                    q.put(sentinel)
            threads = [threading.Thread(target=producer, daemon=True)]
            n_sentinels = 1
        else:
            idx_q = queue.Queue()
            batches = list(self.batch_sampler)
            for i, b in enumerate(batches):
                idx_q.put((i, b))
            results = {}
            res_lock = threading.Condition()
            # backpressure: at most prefetch_factor * num_workers finished
            # batches buffered ahead of the consumer
            slots = threading.Semaphore(self.prefetch_factor * self.num_workers)

            def worker(wid):
                _worker_info.info = WorkerInfo(wid, self.num_workers, self.dataset)
                while True:
                    # acquire BEFORE pulling an index so the K in-flight slots
                    # always cover the K smallest unproduced indices — the
                    # consumer's next batch is guaranteed to be in flight
                    slots.acquire()
                    try:
                        i, idx_batch = idx_q.get_nowait()
                    except queue.Empty:
                        slots.release()
                        return
                    data = self.collate_fn([self.dataset[j] for j in idx_batch])
                    with res_lock:
                        results[i] = data
                        res_lock.notify_all()

            threads = [threading.Thread(target=worker, args=(w,), daemon=True)
                       for w in range(self.num_workers)]
            for t in threads:
                t.start()
            # ordered consumption
            for i in range(len(batches)):
                with res_lock:
                    while i not in results:
                        res_lock.wait()
                    data = results.pop(i)
                slots.release()
                yield data
            for t in threads:
                t.join()
            return

        for t in threads:
            t.start()
        done = 0
        while done < n_sentinels:
            item = q.get()
            if item is sentinel:
                done += 1
                continue
            yield item
        for t in threads:
            t.join()
