"""Multiprocess DataLoader workers (parity: python/paddle/io/reader.py:262
num_workers>0 + io/dataloader/worker.py): real processes, shared-memory
transport, ordered/unordered reassembly, worker_init_fn, persistent
workers, error propagation, and the loader-vs-step utilization probe."""
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.io import (ArrayDataset, DataLoader, Dataset,
                           IterableDataset, get_worker_info)
from paddle_tpu.core.tensor import Tensor


class _SquareDataset(Dataset):
    """Map-style dataset with a numpy transform; rows are 1 KiB so a
    16-item batch crosses the 16 KiB shared-memory threshold."""

    def __init__(self, n=64):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        x = np.full((256,), float(i), np.float32)
        return x * x, np.int64(i)


def _collect(loader):
    xs, ys = [], []
    for bx, by in loader:
        xs.append(np.asarray(bx.numpy() if isinstance(bx, Tensor) else bx))
        ys.append(np.asarray(by.numpy() if isinstance(by, Tensor) else by))
    return xs, ys


def test_mp_matches_sync_ordered():
    ds = _SquareDataset(64)
    ref_x, ref_y = _collect(DataLoader(ds, batch_size=16, num_workers=0))
    got_x, got_y = _collect(DataLoader(ds, batch_size=16, num_workers=2))
    assert len(got_x) == len(ref_x) == 4
    for a, b in zip(ref_x, got_x):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(ref_y, got_y):
        np.testing.assert_array_equal(a, b)


def test_mp_yields_device_tensors():
    loader = DataLoader(_SquareDataset(8), batch_size=4, num_workers=1)
    bx, by = next(iter(loader))
    assert isinstance(bx, Tensor) and isinstance(by, Tensor)
    assert tuple(bx.shape) == (4, 256)


def test_mp_unordered_same_multiset():
    ds = _SquareDataset(48)
    ref_y = _collect(DataLoader(ds, batch_size=8, num_workers=0))[1]
    got_y = _collect(DataLoader(ds, batch_size=8, num_workers=3,
                                in_order=False))[1]
    ref = sorted(tuple(a.tolist()) for a in ref_y)
    got = sorted(tuple(a.tolist()) for a in got_y)
    assert ref == got


def test_mp_no_shared_memory_path():
    ds = _SquareDataset(32)
    ref_x = _collect(DataLoader(ds, batch_size=8, num_workers=0))[0]
    got_x = _collect(DataLoader(ds, batch_size=8, num_workers=2,
                                use_shared_memory=False))[0]
    for a, b in zip(ref_x, got_x):
        np.testing.assert_array_equal(a, b)


def test_worker_init_fn_runs_in_each_worker(tmp_path):
    def init_fn(wid):
        (tmp_path / f"w{wid}").write_text(str(os.getpid()))

    loader = DataLoader(_SquareDataset(16), batch_size=4, num_workers=2,
                        worker_init_fn=init_fn)
    _collect(loader)
    pids = {(tmp_path / f"w{i}").read_text() for i in range(2)}
    assert len(pids) == 2            # two distinct worker processes
    assert str(os.getpid()) not in pids   # neither is the parent


def test_persistent_workers_reuse_pool():
    loader = DataLoader(_SquareDataset(32), batch_size=8, num_workers=2,
                        persistent_workers=True)
    ref = _collect(DataLoader(_SquareDataset(32), batch_size=8))[1]
    got1 = _collect(loader)[1]
    pool1 = loader._pool
    assert pool1 is not None and pool1.alive
    got2 = _collect(loader)[1]
    assert loader._pool is pool1     # same processes served both epochs
    for a, b in zip(ref, got1):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(ref, got2):
        np.testing.assert_array_equal(a, b)
    pool1.shutdown()


def test_concurrent_iterators_do_not_cross_deliver():
    # two live iterators over one loader must not share worker queues
    loader = DataLoader(_SquareDataset(32), batch_size=8, num_workers=2,
                        persistent_workers=True)
    ref = _collect(DataLoader(_SquareDataset(32), batch_size=8))[1]
    it1 = iter(loader)
    first = next(it1)
    it2 = iter(loader)
    got2 = [np.asarray(b[1].numpy()) for b in it2]
    got1 = [np.asarray(first[1].numpy())] + \
        [np.asarray(b[1].numpy()) for b in it1]
    for a, b in zip(ref, got1):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(ref, got2):
        np.testing.assert_array_equal(a, b)
    if loader._pool is not None:
        loader._pool.shutdown()


def test_bad_worker_mode_rejected():
    with pytest.raises(ValueError, match="worker_mode"):
        DataLoader(_SquareDataset(8), batch_size=4, worker_mode="processes")


def test_nonpersistent_pool_torn_down():
    loader = DataLoader(_SquareDataset(16), batch_size=4, num_workers=2)
    _collect(loader)
    assert loader._pool is None


class _FaultyDataset(Dataset):
    def __len__(self):
        return 16

    def __getitem__(self, i):
        if i == 11:
            raise ValueError("poisoned sample 11")
        return np.zeros((4,), np.float32)


def test_worker_error_propagates():
    loader = DataLoader(_FaultyDataset(), batch_size=4, num_workers=2)
    with pytest.raises(RuntimeError, match="poisoned sample 11"):
        for _ in loader:
            pass


class _ShardedStream(IterableDataset):
    """Workers shard the stream via get_worker_info (reference worker.py
    IterableDataset contract)."""

    def __init__(self, n=40):
        self.n = n

    def __iter__(self):
        info = get_worker_info()
        wid = info.id if info else 0
        nw = info.num_workers if info else 1
        for i in range(wid, self.n, nw):
            yield np.full((8,), float(i), np.float32)


def test_iterable_dataset_with_workers():
    loader = DataLoader(_ShardedStream(40), batch_size=5, num_workers=2)
    seen = []
    for batch in loader:
        seen.extend(np.asarray(batch.numpy())[:, 0].astype(int).tolist())
    assert sorted(seen) == list(range(40))


class _BusyDataset(Dataset):
    """CPU-heavy pure-Python transform — the GIL case multiprocess workers
    exist for. Each item says which process made it."""

    def __init__(self, n=24, iters=120_000):
        self.n = n
        self.iters = iters

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        acc = 0
        for k in range(self.iters):       # holds the GIL
            acc += k & 7
        return (np.full((64,), float(acc % 97 + i), np.float32),
                np.int64(os.getpid()))


def test_process_workers_run_cpu_bound_transforms_in_order_elsewhere():
    """What the loader guarantees whatever the host's load: process
    workers give the batches of the in-process loader, in its order, and
    every transform ran in a process that is not this one."""
    ds = _BusyDataset()
    ref_x, ref_pid = _collect(DataLoader(ds, batch_size=6, num_workers=0))
    got_x, got_pid = _collect(DataLoader(ds, batch_size=6, num_workers=3))
    assert len(got_x) == len(ref_x) == 4
    for a, b in zip(ref_x, got_x):
        np.testing.assert_array_equal(a, b)
    assert set(np.concatenate(ref_pid).tolist()) == {os.getpid()}
    made_by = set(np.concatenate(got_pid).tolist())
    assert os.getpid() not in made_by
    assert len(made_by) > 1          # the work was spread


def test_process_workers_beat_threads_on_cpu_bound_transforms():
    """The wall-clock claim, kept off the fast lane (tests/conftest.py
    ``_SLOW``): beside five other xdist workers it is a race against
    whatever else the host runs."""
    ds = _BusyDataset()
    kw = dict(batch_size=6, num_workers=3)

    def seconds(loader):
        t0 = time.monotonic()
        for _ in loader:
            pass
        return time.monotonic() - t0

    t_thread = seconds(DataLoader(ds, worker_mode="thread", **kw))
    t_proc = seconds(DataLoader(ds, **kw))
    # GIL serializes the thread pool; processes should win clearly — but
    # only where there is real parallelism to be had
    if (os.cpu_count() or 1) >= 2:
        assert t_proc < t_thread * 0.85, (t_proc, t_thread)


class _SlowDataset(Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        time.sleep(0.02)
        return np.zeros((4,), np.float32)


def test_utilization_probe_flags_input_bound_training():
    # slow loader + instant consumer → input-bound
    slow = DataLoader(_SlowDataset(), batch_size=2, num_workers=0)
    for _ in slow:
        pass
    assert slow.last_epoch_stats["input_bound_frac"] > 0.7

    # instant loader + slow consumer → compute-bound
    fast = DataLoader(_SquareDataset(8), batch_size=2, num_workers=0)
    for _ in fast:
        time.sleep(0.02)
    assert fast.last_epoch_stats["input_bound_frac"] < 0.5
    assert fast.last_epoch_stats["batches"] == 4


class _GilBoundDataset(Dataset):
    """Pure-python transform: holds the GIL the whole item, so thread
    workers serialize while process workers parallelize (the reason the
    reference uses real worker processes — io/dataloader/worker.py)."""

    def __len__(self):
        return 24

    def __getitem__(self, i):
        acc = 0
        for j in range(150_000):
            acc += j * j
        return np.asarray([i, acc % 7], np.int64), np.int64(os.getpid())


def test_process_workers_beat_threads_on_gil_bound_transforms():
    """Four persistent process workers serve a warm second epoch whole,
    from more than one process. That they beat threads on the clock is the
    slow lane's claim (``..._on_cpu_bound_transforms`` above)."""
    loader = DataLoader(_GilBoundDataset(), batch_size=4, num_workers=4,
                        worker_mode="process", persistent_workers=True)
    for _ in loader:          # warm epoch: pool spawn + first batches
        pass
    rows, pids = _collect(loader)
    assert sorted(np.concatenate(rows)[:, 0].tolist()) == list(range(24))
    made_by = set(np.concatenate(pids).tolist())
    assert os.getpid() not in made_by and len(made_by) > 1
