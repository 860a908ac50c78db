"""paddle.io: Dataset / DataLoader / samplers.

Reference parity: `python/paddle/io/` (`dataloader/dataloader_iter.py`
multiprocess workers) [UNVERIFIED — empty reference mount].

TPU-native notes: host input pipeline feeds the device via async transfers.
num_workers > 0 uses real multiprocessing workers (forked; samples fetched
and transformed in the workers, collation in the parent so device arrays
never cross the pipe), falling back to a prefetching thread pool when the
platform cannot fork.  DistributedBatchSampler shards by process
(data-parallel rank).
"""
from __future__ import annotations

import itertools
import math
import os
import queue
import threading
from typing import Iterable, Optional

import numpy as np

from ..core.tensor import Tensor, to_tensor

__all__ = ["Dataset", "IterableDataset", "TensorDataset", "ComposeDataset",
           "ChainDataset", "Subset", "ConcatDataset", "random_split",
           "Sampler", "SequenceSampler", "RandomSampler", "WeightedRandomSampler",
           "BatchSampler", "DistributedBatchSampler", "DataLoader",
           "DeviceFeeder", "get_worker_info", "default_collate_fn"]


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
            sample = d[idx]
            if isinstance(sample, (list, tuple)):
                out.extend(sample)
            else:
                out.append(sample)
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
    if all(isinstance(l, float) for l in lengths):
        n = len(dataset)
        counts = [int(math.floor(n * f)) for f in lengths]
        rem = n - sum(counts)
        for i in range(rem):
            counts[i % len(counts)] += 1
        lengths = counts
    total = sum(lengths)
    perm = np.random.permutation(total).tolist()
    out, offset = [], 0
    for l in lengths:
        out.append(Subset(dataset, perm[offset:offset + l]))
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

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(len(self.weights), self.num_samples,
                               replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
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
    """Shards the dataset across data-parallel ranks.

    Reference parity: `python/paddle/io/dataloader/batch_sampler.py`
    DistributedBatchSampler [UNVERIFIED].  Rank/world default to the jax
    process index/count (multi-controller TPU idiom).
    """

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        if num_replicas is None or rank is None:
            try:
                import jax
                num_replicas = num_replicas or jax.process_count()
                rank = rank if rank is not None else jax.process_index()
            except Exception:
                num_replicas, rank = 1, 0
        self.nranks = num_replicas
        self.local_rank = rank
        self.epoch = 0
        self.num_samples = int(math.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            indices = rng.permutation(n).tolist()
        else:
            indices = list(range(n))
        # pad to make divisible
        indices += indices[: self.total_size - len(indices)]
        local = indices[self.local_rank:self.total_size:self.nranks]
        batch = []
        for idx in local:
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


_worker_info = None


def get_worker_info():
    return _worker_info


def default_collate_fn(batch):
    from .._native import fast_stack  # C memcpy, GIL-free (native host path)
    sample = batch[0]
    if isinstance(sample, (Tensor,)):
        vals = fast_stack([np.asarray(b._value) for b in batch])
        return to_tensor(vals)
    if isinstance(sample, np.ndarray):
        return to_tensor(fast_stack(batch))
    if isinstance(sample, (int, np.integer)):
        return to_tensor(np.asarray(batch, np.int64))
    if isinstance(sample, (float, np.floating)):
        return to_tensor(np.asarray(batch, np.float32))
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return [default_collate_fn(list(items)) for items in transposed]
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch])
                for k in sample}
    return batch


class _MPUnavailable(RuntimeError):
    pass


_mp_dataset = None
_mp_ring = None
_mp_wid = None


def _sweep_stale_shm_rings():
    """Unlink /dev/shm/pt_dl_<pid>_* rings whose owning process is gone
    (a SIGKILLed run never reaches its finally-unlink; names are unique
    per run, so creation-time shm_unlink can't reclaim them)."""
    try:
        for name in os.listdir("/dev/shm"):
            if not name.startswith("pt_dl_"):
                continue
            try:
                pid = int(name.split("_")[2])
                os.kill(pid, 0)       # raises if the owner is gone
            except (ValueError, IndexError):
                continue
            except ProcessLookupError:
                try:
                    os.unlink(os.path.join("/dev/shm", name))
                except OSError:
                    pass
            except PermissionError:
                pass                  # alive under another uid
    except OSError:
        pass                          # no /dev/shm on this platform


def _mp_worker_init(dataset, init_fn, counter, ring_names=None):
    global _mp_dataset, _mp_ring, _mp_wid
    # the chip belongs to the parent: whatever a worker does with jax
    # (a dataset that builds Tensors) stays on the host
    import jax
    jax.config.update("jax_platforms", "cpu")
    _mp_dataset = dataset
    # explicit 0..num_workers-1 id from a shared counter; the process
    # _identity is a parent-global counter that drifts out of range on
    # the second epoch's fresh pool
    with counter.get_lock():
        _mp_wid = counter.value
        counter.value += 1
    if ring_names and _mp_wid < len(ring_names):
        # shared-memory batch path (the reference's C++ shared-mem
        # tensor transport): attach THIS worker's SPSC ring.  A worker
        # RESPAWNED after a crash (wid >= num_workers) must not reuse a
        # dead peer's ring — its leftover slots would corrupt SPSC
        # ordering — so replacements ship batches over the pipe.
        from .._native import ShmRing
        _mp_ring = ShmRing.attach(ring_names[_mp_wid])
    if init_fn is not None:
        init_fn(_mp_wid)


def _mp_fetch(indices):
    samples = [_mp_dataset[i] for i in indices]
    if _mp_ring is not None:
        import pickle
        blob = pickle.dumps(samples, protocol=pickle.HIGHEST_PROTOCOL)
        # one shm memcpy instead of pipe-chunked transfer; oversized
        # batches fall back to the pipe for just that batch
        if _mp_ring.write(blob):
            return ("__shm__", _mp_wid)
    return samples


def _mp_probe():
    return _mp_dataset is not None


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.worker_init_fn = worker_init_fn
        self.return_list = return_list
        self.use_shared_memory = use_shared_memory
        self.persistent_workers = persistent_workers
        # persistent-worker state: (pool, rings) kept across epochs when
        # persistent_workers=True; spawn-mode re-pickling of the dataset
        # and fork/ring setup then happen once, not per epoch
        self._mp_pool = None
        self._mp_rings = []
        self._thread_pool = None
        self._iterable = isinstance(dataset, IterableDataset)
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = batch_sampler.batch_size
        elif not self._iterable:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last)
            self.batch_size = batch_size
        else:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last

    def __len__(self):
        if self._iterable:
            raise TypeError("length of IterableDataset DataLoader unknown")
        return len(self.batch_sampler)

    def __iter__(self):
        if self._iterable:
            yield from self._iter_iterable()
        elif self.num_workers == 0:
            yield from self._iter_single()
        else:
            try:
                yield from self._iter_multiprocess()
            except _MPUnavailable:
                yield from self._iter_threaded()

    def _iter_iterable(self):
        batch = []
        for sample in self.dataset:
            batch.append(sample)
            if len(batch) == self.batch_size:
                yield self.collate_fn(batch)
                batch = []
        if batch and not getattr(self, "drop_last", False):
            yield self.collate_fn(batch)

    def _iter_single(self):
        for indices in self.batch_sampler:
            yield self.collate_fn([self.dataset[i] for i in indices])

    def _mp_create_pool(self):
        """Create the worker pool + shm rings (one-time when
        persistent_workers, per-epoch otherwise)."""
        import multiprocessing as mp

        # forking after the XLA runtime started its thread pools can
        # deadlock children; spawn (dataset pickled once into workers)
        # is the safe method then
        from jax._src import xla_bridge as _xb
        method = "spawn" if _xb.backends_are_initialized() else "fork"
        try:
            ctx = mp.get_context(method)
        except ValueError as e:  # pragma: no cover - non-POSIX
            raise _MPUnavailable(str(e))

        depth = max(2, self.prefetch_factor * self.num_workers)

        # shared-memory batch transport (one SPSC ring per worker; see
        # _native/shm_ring.c).  Ring depth >= outstanding prefetch so a
        # worker never deadlocks against a slow consumer.
        rings, ring_names = [], None
        if self.use_shared_memory:
            from .._native import ShmRing, shm_ring_available
            if shm_ring_available():
                import uuid
                _sweep_stale_shm_rings()
                slot_mb = int(os.environ.get(
                    "PADDLE_TPU_SHM_SLOT_MB", "16"))
                tag = uuid.uuid4().hex[:8]
                names = [f"/pt_dl_{os.getpid()}_{tag}_{w}"
                         for w in range(self.num_workers)]
                rings = [ShmRing.create(n, depth + 2, slot_mb << 20)
                         for n in names]
                if all(r is not None for r in rings):
                    ring_names = names
                else:
                    for r in rings:
                        if r is not None:
                            r.close()
                    rings = []

        # spawned workers read JAX_PLATFORMS when they import jax,
        # before the initializer runs (unpickling the dataset may
        # already build arrays): start them pinned to the host
        platforms = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            counter = ctx.Value("i", 0)
            pool = ctx.Pool(
                self.num_workers,
                initializer=_mp_worker_init,
                initargs=(self.dataset, self.worker_init_fn, counter,
                          ring_names))
            # smoke round: spawn-unpickle failures crash CHILDREN after
            # Pool() returns, leaving every result pending forever; a
            # bounded probe turns that hang into the threaded fallback
            pool.apply_async(_mp_probe).get(timeout=60)
        except Exception as e:  # unpicklable dataset/init_fn, dead pool
            try:
                pool.terminate()
            except Exception:
                pass
            for r in rings:
                r.close()
            raise _MPUnavailable(str(e))
        finally:
            if platforms is None:
                del os.environ["JAX_PLATFORMS"]
            else:
                os.environ["JAX_PLATFORMS"] = platforms
        return pool, rings

    def _mp_teardown(self, pool=None, rings=None):
        """Terminate a pool + rings (default: the persistent ones)."""
        own = pool is None and rings is None
        pool = pool if pool is not None else self._mp_pool
        rings = rings if rings is not None else self._mp_rings
        if pool is not None:
            try:
                pool.terminate()
                pool.join()
            except Exception:
                pass
        for r in rings or []:
            try:
                r.close()
            except Exception:
                pass
        if own or pool is self._mp_pool:
            self._mp_pool, self._mp_rings = None, []

    @staticmethod
    def _mp_drain_pending(pending, rings):
        """Consume every outstanding worker result so a kept-alive pool's
        shm rings hold no unread slots for the next epoch (early ``break``
        leaves up to ``depth`` results in flight)."""
        import pickle
        while not pending.empty():
            samples = pending.get().get(timeout=60)
            if (isinstance(samples, tuple) and len(samples) == 2
                    and samples[0] == "__shm__"):
                pickle.loads(rings[samples[1]].read())

    def shutdown(self):
        """Stop persistent workers (no-op when none are alive)."""
        self._mp_teardown()
        tp = self._thread_pool
        if tp is not None:
            self._thread_pool = None
            tp.shutdown(wait=False)

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass

    def _iter_multiprocess(self):
        """Real multiprocess workers (the reference's dataloader_iter
        worker pool): the dataset is shared into forked workers
        (copy-on-write, nothing pickled per item), workers run
        __getitem__ — the GIL-bound decode/augment cost — and ship
        sample lists back; the parent collates so jax device arrays
        never cross the pipe.  With persistent_workers=True the pool
        and rings outlive the epoch and are reused by the next one."""
        if self._mp_pool is not None:
            pool, rings = self._mp_pool, self._mp_rings
        else:
            pool, rings = self._mp_create_pool()
            if self.persistent_workers:
                self._mp_pool, self._mp_rings = pool, rings
        depth = max(2, self.prefetch_factor * self.num_workers)
        keep = self.persistent_workers
        try:
            import pickle
            pending = queue.Queue()
            it = iter(self.batch_sampler)

            def submit_next():
                try:
                    indices = next(it)
                except StopIteration:
                    return False
                pending.put(pool.apply_async(_mp_fetch, (list(indices),)))
                return True

            for _ in range(depth):
                if not submit_next():
                    break
            while not pending.empty():
                res = pending.get()
                samples = res.get()
                if (isinstance(samples, tuple) and len(samples) == 2
                        and samples[0] == "__shm__"):
                    samples = pickle.loads(rings[samples[1]].read())
                submit_next()
                yield self.collate_fn(samples)
            if keep:
                pending = None  # clean exhaustion: nothing left in flight
        finally:
            if keep and pool is self._mp_pool:
                if pending is not None:
                    try:
                        self._mp_drain_pending(pending, rings)
                    except Exception:
                        # a worker died mid-drain: the pool is no longer
                        # trustworthy for reuse
                        self._mp_teardown()
            else:
                self._mp_teardown(pool, rings)

    def _iter_threaded(self):
        """Prefetch with a thread pool (host-side pipeline; the heavy work
        — decode/augment — releases the GIL in numpy, and device transfer
        overlaps via jax async dispatch).  persistent_workers keeps the
        executor across epochs."""
        from concurrent.futures import ThreadPoolExecutor

        keep = self.persistent_workers
        if keep and self._thread_pool is not None:
            pool = self._thread_pool
        else:
            pool = ThreadPoolExecutor(max_workers=self.num_workers)
            if keep:
                self._thread_pool = pool
        depth = max(2, self.prefetch_factor * self.num_workers)
        pending = queue.Queue()
        try:
            it = iter(self.batch_sampler)

            def submit_next():
                try:
                    indices = next(it)
                except StopIteration:
                    return False
                fut = pool.submit(
                    lambda idx: self.collate_fn(
                        [self.dataset[i] for i in idx]), indices)
                pending.put(fut)
                return True

            for _ in range(depth):
                if not submit_next():
                    break
            while not pending.empty():
                fut = pending.get()
                submit_next()
                yield fut.result()
        finally:
            if keep and pool is self._thread_pool:
                while not pending.empty():  # early exit: let stragglers
                    try:                    # finish so state stays clean
                        pending.get().result(timeout=60)
                    except Exception:
                        pass
            else:
                pool.shutdown(wait=True)


from .device_feeder import DeviceFeeder  # noqa: E402  (imports core.pipeline)
