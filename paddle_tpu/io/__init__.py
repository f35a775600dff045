"""paddle_tpu.io — datasets & data loading.

Parity: python/paddle/io/ (DataLoader — reader.py:262; samplers, Dataset /
IterableDataset / TensorDataset; multiprocess workers in dataloader/worker.py).
On TPU the loader is host-side; worker parallelism uses threads feeding a
prefetch queue (the device never blocks on Python), which plays the role of
the reference's shared-memory worker transport.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Iterable, List, Optional

import numpy as np

from ..core.tensor import Tensor
from ..observability import goodput as _goodput
from ..observability.catalog import instrument as _instrument

_M_BATCHES = _instrument("dataloader_batches_total")
_M_BATCH_WAIT = _instrument("dataloader_batch_wait_seconds")

__all__ = [
    "Dataset", "IterableDataset", "TensorDataset", "ArrayDataset", "ComposeDataset",
    "ChainDataset", "Subset", "ConcatDataset", "random_split",
    "Sampler", "SequenceSampler", "RandomSampler", "WeightedRandomSampler",
    "BatchSampler", "DistributedBatchSampler", "DataLoader", "default_collate_fn",
    "get_worker_info",
]


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset has no __getitem__")

    def __len__(self):
        raise RuntimeError("IterableDataset has no __len__")


class TensorDataset(Dataset):
    def __init__(self, tensors: List[Tensor]):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ArrayDataset(Dataset):
    """Contiguous numpy-backed map-style dataset with NATIVE batch collation:
    DataLoader gathers whole batches through the C++ runtime
    (csrc/ptpu_runtime.cpp ptpu_gather_rows — parallel row memcpy outside the
    GIL), playing the role of the reference's C++ DataFeed/shared-memory
    worker transport (fluid/framework/data_feed.h:1144,
    io/dataloader/worker.py)."""

    def __init__(self, *arrays):
        assert arrays and all(len(a) == len(arrays[0]) for a in arrays)
        self.arrays = [np.ascontiguousarray(a) for a in arrays]

    def __getitem__(self, idx):
        out = tuple(a[idx] for a in self.arrays)
        return out if len(out) > 1 else out[0]

    def __len__(self):
        return len(self.arrays[0])


def _native_gather(arr: np.ndarray, indices, nthreads: int = 4) -> np.ndarray:
    """Batch-gather rows via the native runtime; numpy where there is
    neither a built library nor a compiler to build one."""
    import ctypes

    from ..lib import native_available, native_lib

    idx = np.ascontiguousarray(indices, np.int64)
    out = np.empty((len(idx),) + arr.shape[1:], arr.dtype)
    if not native_available():
        np.take(arr, idx, axis=0, out=out)
        return out
    lib = native_lib()
    row_bytes = int(arr.dtype.itemsize * np.prod(arr.shape[1:], dtype=np.int64))
    lib.ptpu_gather_rows(
        arr.ctypes.data_as(ctypes.c_char_p),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        len(idx), row_bytes,
        out.ctypes.data_as(ctypes.c_char_p), nthreads)
    return out


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = datasets

    def __getitem__(self, idx):
        out = []
        for ds in self.datasets:
            item = ds[idx]
            out.extend(item if isinstance(item, (list, tuple)) else [item])
        return tuple(out)

    def __len__(self):
        return min(len(d) for d in self.datasets)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = datasets

    def __iter__(self):
        return itertools.chain(*self.datasets)


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cum = np.cumsum([len(d) for d in self.datasets]).tolist()

    def __len__(self):
        return self.cum[-1]

    def __getitem__(self, idx):
        if idx < 0:
            idx += len(self)
        di = int(np.searchsorted(self.cum, idx, side="right"))
        prev = 0 if di == 0 else self.cum[di - 1]
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
    total = len(dataset)
    lengths = list(lengths)
    if all(isinstance(l, float) for l in lengths):
        lengths = [int(total * l) for l in lengths]
        lengths[-1] = total - sum(lengths[:-1])
    if sum(lengths) != total:
        raise ValueError("sum of lengths must equal dataset size")
    perm = np.random.permutation(total).tolist()
    out, off = [], 0
    for n in lengths:
        out.append(Subset(dataset, perm[off:off + n]))
        off += n
    return out


# -- samplers -----------------------------------------------------------------
class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))

    def __len__(self):
        return len(self.data_source)


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[: self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, dtype=np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        return iter(np.random.choice(len(self.weights), self.num_samples,
                                     replace=self.replacement, p=p).tolist())

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
    """Shards the index space across data-parallel ranks (parity:
    python/paddle/io/dataloader/batch_sampler.py DistributedBatchSampler)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        if num_replicas is None or rank is None:
            from ..distributed import get_rank, get_world_size

            num_replicas = num_replicas or get_world_size()
            rank = rank if rank is not None else get_rank()
        self.nranks = num_replicas
        self.local_rank = rank
        self.epoch = 0
        self.num_samples = int(np.ceil(len(dataset) / num_replicas))
        self.total_size = self.num_samples * num_replicas

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            indices = rng.permutation(n).tolist()
        else:
            indices = list(range(n))
        indices += indices[: self.total_size - n]
        indices = indices[self.local_rank:self.total_size:self.nranks]
        batch = []
        for idx in indices:
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


# -- collate ------------------------------------------------------------------
def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, Tensor):
        return Tensor(np.stack([np.asarray(s._value) for s in batch]))
    if isinstance(sample, np.ndarray):
        return Tensor(np.stack(batch))
    if isinstance(sample, (int, np.integer)):
        return Tensor(np.asarray(batch, dtype=np.int64))
    if isinstance(sample, (float, np.floating)):
        return Tensor(np.asarray(batch, dtype=np.float32))
    if isinstance(sample, (str, bytes)):
        return list(batch)
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    if isinstance(sample, (list, tuple)):
        return type(sample)(default_collate_fn(list(items))
                            for items in zip(*batch))
    return batch


class _WorkerInfo:
    def __init__(self, id_, num_workers, dataset):
        self.id = id_
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = threading.local()


def get_worker_info():
    return getattr(_worker_info, "info", None)


# -- loader -------------------------------------------------------------------
class DataLoader:
    """parity: python/paddle/io/reader.py:262 DataLoader.

    ``num_workers > 0`` spawns real worker PROCESSES with shared-memory
    batch transport (io/mp_loader.py — the analogue of the reference's
    dataloader/worker.py + shared-memory LoDTensor path); workers collate in
    numpy (GIL-free transforms, no forked TPU client) and the parent does
    the single host→device copy. ``in_order=False`` yields batches in
    arrival order instead of sampler order. ``worker_mode="thread"`` keeps
    the in-process prefetch pool (for transforms that must touch device
    tensors)."""

    _default_collate = staticmethod(default_collate_fn)

    def __init__(self, dataset, feed_list=None, places=None, return_list=True,
                 batch_sampler=None, batch_size=1, shuffle=False, drop_last=False,
                 collate_fn=None, num_workers=0, use_buffer_reader=True,
                 prefetch_factor=2, use_shared_memory=True, timeout=0,
                 worker_init_fn=None, persistent_workers=False,
                 in_order=True, worker_mode="process"):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.use_shared_memory = use_shared_memory
        self.persistent_workers = persistent_workers
        self.in_order = in_order
        if worker_mode not in ("process", "thread"):
            raise ValueError(
                f"worker_mode must be 'process' or 'thread', got "
                f"{worker_mode!r}")
        self.worker_mode = worker_mode
        self._pool = None
        # checkpointable position (distributed.resilience crash-resume):
        # counts batches yielded by the ACTIVE iterator; assumes one live
        # iterator at a time (the training-loop case)
        self._pos_epoch = 0
        self._pos_batch = 0
        self._resume_skip = 0
        # loader-vs-consumer utilization probe, refreshed per epoch:
        # wait_s = time the consumer blocked on the loader; busy_s = time
        # the consumer spent between batches (its own step time)
        self.last_epoch_stats = None
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        elif batch_size is None:
            self.batch_sampler = None
            self.batch_size = None
        else:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no len()")
        if self.batch_sampler is None:
            return len(self.dataset)
        return len(self.batch_sampler)

    def _fetch(self, indices):
        if (isinstance(self.dataset, ArrayDataset)
                and self.collate_fn is default_collate_fn):
            cols = tuple(Tensor(_native_gather(a, indices))
                         for a in self.dataset.arrays)
            return cols if len(cols) > 1 else cols[0]
        samples = [self.dataset[i] for i in indices]
        return self.collate_fn(samples)

    def _iter_sync(self, skip: int = 0):
        if self._iterable_mode:
            n = 0
            batch = []
            for item in self.dataset:
                batch.append(item)
                if len(batch) == self.batch_size:
                    n += 1
                    if n > skip:     # resume: re-stream, drop consumed
                        yield self.collate_fn(batch)
                    batch = []
            if batch and not self.drop_last and n + 1 > skip:
                yield self.collate_fn(batch)
            return
        if self.batch_sampler is None:
            for i in range(skip, len(self.dataset)):
                yield self.dataset[i]
            return
        # map-style resume skip is sampler-level: no sample is fetched for
        # the skipped batches
        for indices in itertools.islice(iter(self.batch_sampler), skip,
                                        None):
            yield self._fetch(indices)

    def _iter_threaded(self, skip: int = 0):
        """Prefetching thread pool: the stand-in for the reference's
        multiprocess worker + shared-memory transport (io/dataloader/worker.py)
        — on TPU hosts the goal is simply to keep the infeed ahead of step
        time."""
        q: "queue.Queue" = queue.Queue(self.prefetch_factor * self.num_workers)
        sentinel = object()
        idx_iter = itertools.islice(iter(self.batch_sampler), skip, None)
        lock = threading.Lock()
        exc = []

        def worker(wid):
            _worker_info.info = _WorkerInfo(wid, self.num_workers, self.dataset)
            if self.worker_init_fn is not None:
                self.worker_init_fn(wid)
            while True:
                with lock:
                    try:
                        indices = next(idx_iter)
                    except StopIteration:
                        break
                try:
                    q.put(self._fetch(indices))
                except Exception as e:  # propagate to consumer
                    exc.append(e)
                    break
            q.put(sentinel)

        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(self.num_workers)]
        for t in threads:
            t.start()
        done = 0
        while done < self.num_workers:
            item = q.get()
            if item is sentinel:
                done += 1
                continue
            yield item
        if exc:
            raise exc[0]

    def _iter_mp(self, skip: int = 0):
        from .mp_loader import WorkerPool

        pool = self._pool
        if pool is None or not pool.alive or pool.in_use:
            # a second live iterator over the same loader must not share
            # queues with the first (interleaved epochs would cross-deliver
            # batches) — it gets its own pool, torn down at exhaustion
            pool = WorkerPool(self)
            if self._pool is None or not self._pool.alive:
                self._pool = pool
        pool.in_use = True
        if self._iterable_mode:
            gen = pool.run_iterable_epoch(skip=skip)
        else:
            # resume skip happens before submission: skipped batches are
            # never fetched, collated, or shipped through shm
            gen = pool.run_map_epoch(
                itertools.islice(iter(self.batch_sampler), skip, None),
                self.in_order)
        clean = False
        try:
            for batch in gen:
                yield batch
            clean = True
        finally:
            gen.close()
            pool.in_use = False
            if not clean or not self.persistent_workers or pool is not self._pool:
                # an abandoned epoch leaves stale batches in the result
                # queue — a partially-consumed pool cannot be reused
                pool.shutdown()
                if pool is self._pool:
                    self._pool = None

    def _timed(self, gen):
        """Wrap an epoch iterator with the utilization probe."""
        wait_s = 0.0
        busy_s = 0.0
        n = 0
        try:
            while True:
                t0 = time.monotonic()
                try:
                    item = next(gen)
                except StopIteration:
                    # clean exhaustion: the epoch is over for position
                    # tracking (an abandoned iterator does NOT bump it)
                    self._pos_epoch += 1
                    self._pos_batch = 0
                    break
                t1 = time.monotonic()
                wait_s += t1 - t0
                n += 1
                self._pos_batch += 1
                _M_BATCH_WAIT.observe(t1 - t0)   # no-op unless obs enabled
                _M_BATCHES.inc()
                # consumer-blocked time is data_wait badput
                _goodput.account("data_wait", t1 - t0)
                yield item          # consumer runs while suspended here
                busy_s += time.monotonic() - t1
        finally:
            total = wait_s + busy_s
            self.last_epoch_stats = {
                "batches": n, "wait_s": wait_s, "busy_s": busy_s,
                "input_bound_frac": (wait_s / total) if total > 0 else 0.0,
            }

    def __iter__(self):
        skip = self._resume_skip
        self._resume_skip = 0
        self._pos_batch = skip
        if self.num_workers > 0:
            if self.worker_mode == "process" and (
                    self._iterable_mode or self.batch_sampler is not None):
                return self._timed(self._iter_mp(skip))
            if not self._iterable_mode and self.batch_sampler is not None:
                return self._timed(self._iter_threaded(skip))
        return self._timed(self._iter_sync(skip))

    # -- checkpointable position (distributed.resilience) -----------------
    def state_dict(self):
        """Loader position for exact crash-resume: epochs completed and
        batches yielded in the current epoch. Exact only for a
        deterministic sampler (``shuffle=False`` or epoch-seeded)."""
        return {"epoch": self._pos_epoch, "batch": self._pos_batch}

    def load_state_dict(self, sd) -> None:
        """Restore a :meth:`state_dict` position. The NEXT ``__iter__``
        fast-forwards ``sd['batch']`` batches — at the sampler level for
        map-style datasets (skipped batches are never fetched), by
        stream-and-discard for iterables."""
        self._pos_epoch = int(sd.get("epoch", 0))
        self._pos_batch = int(sd.get("batch", 0))
        self._resume_skip = self._pos_batch

    def __del__(self):
        pool = getattr(self, "_pool", None)
        if pool is not None:
            pool.shutdown()

    def __call__(self):
        return self.__iter__()


class SubsetRandomSampler(Sampler):
    """parity: io/sampler.py SubsetRandomSampler — random order over a fixed
    index subset."""

    def __init__(self, indices):
        self.indices = list(indices)
        if len(self.indices) == 0:
            raise ValueError(
                "SubsetRandomSampler: indices must not be empty")

    def __iter__(self):
        import numpy as _np

        order = _np.random.permutation(len(self.indices))
        return iter([self.indices[i] for i in order])

    def __len__(self):
        return len(self.indices)
