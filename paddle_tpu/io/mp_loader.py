"""Multiprocess DataLoader workers with shared-memory ndarray transport.

TPU-native re-design of the reference's multiprocess loader tier
(reference: python/paddle/io/dataloader/worker.py:281 ``_worker_loop``,
dataloader_iter.py:459 ``multiprocessing.Process`` spawn + index queues,
worker.py:184 ``_WorkerException``). Python-transform-heavy datasets are
GIL-bound under the thread tier (io/dataloader.py); real processes give
true parallelism for decode/augment pipelines.

Differences from the reference, driven by the TPU runtime:

- **spawn, not fork.** The parent holds a live XLA client (and possibly
  the chip); forking a process with XLA/grpc threads deadlocks.
  Workers are spawned fresh and FORCE ``JAX_PLATFORMS=cpu`` before any
  unpickling, so a worker can never claim the single TPU chip out from
  under the trainer.
- **Shared-memory ndarray transport.** Batch arrays travel as
  ``multiprocessing.shared_memory`` segments (name/shape/dtype skeleton
  through the result queue) instead of being pickled through a pipe —
  one memcpy worker-side, one parent-side copy into the device transfer.
  Small leaves (< _SHM_MIN bytes) pickle directly; the segment overhead
  would dominate.
- **Ordered reorder buffer** in the parent restores sampler order, and a
  worker exception is delivered at exactly the batch position it
  happened (the reference's _task_infos/_WorkerException semantics).

The thread tier remains the fallback: unpicklable datasets/collate_fns,
IterableDataset (inherently sequential), or spawn failure fall back with
a one-time warning.
"""
from __future__ import annotations

import os
import pickle
import queue as pyqueue
import threading
import time
import traceback
from typing import Any, List, Optional

import numpy as np

_SHM_MIN = 1 << 16          # below this, pickling through the queue wins
_SPAWN_CTX = None


def _ctx():
    global _SPAWN_CTX
    if _SPAWN_CTX is None:
        import multiprocessing as mp
        _SPAWN_CTX = mp.get_context("spawn")
    return _SPAWN_CTX


class _ShmArray:
    """Skeleton of an ndarray riding a SharedMemory segment."""

    __slots__ = ("name", "shape", "dtype", "was_tensor")

    def __init__(self, name, shape, dtype, was_tensor):
        self.name = name
        self.shape = shape
        self.dtype = dtype
        self.was_tensor = was_tensor


class _NpTensor:
    """A Tensor leaf converted to numpy for transport (small ones)."""

    __slots__ = ("arr",)

    def __init__(self, arr):
        self.arr = arr


class _WorkerError:
    """reference: io/dataloader/worker.py:184 _WorkerException — the
    original traceback travels as text; the parent re-raises the same
    exception type with it appended. Only the type NAME is stored (a
    locally-defined exception class would make this object — and with
    it the whole result — unpicklable and silently dropped by the
    queue's feeder thread); builtin exception types are resolved back
    on reraise, others degrade to RuntimeError with the traceback."""

    def __init__(self, exc, tb=None):
        self.exc_type_name = type(exc).__name__
        self.msg = str(exc)
        self.tb = traceback.format_exc() if tb is None else tb

    def reraise(self):
        import builtins
        cls = getattr(builtins, self.exc_type_name, None)
        if not (isinstance(cls, type) and issubclass(cls, BaseException)):
            cls = RuntimeError
        try:
            e = cls(f"{self.msg}\n\n[DataLoader worker traceback]\n"
                    f"{self.tb}")
        except Exception:
            e = RuntimeError(
                f"{self.exc_type_name}: {self.msg}\n{self.tb}")
        raise e


def _encode(obj, created):
    """Replace big ndarray/Tensor leaves with shm skeletons (segments
    appended to ``created``); Tensor leaves become numpy with a marker
    so the parent restores the type."""
    # local import: the worker has forced the cpu platform by now
    from .._core.tensor import Tensor
    was_tensor = isinstance(obj, Tensor)
    if was_tensor:
        obj = np.asarray(obj.numpy())
    if isinstance(obj, np.ndarray):
        if obj.nbytes >= _SHM_MIN:
            from multiprocessing import shared_memory
            obj = np.ascontiguousarray(obj)
            shm = shared_memory.SharedMemory(create=True, size=obj.nbytes)
            np.ndarray(obj.shape, obj.dtype, buffer=shm.buf)[...] = obj
            created.append(shm)
            return _ShmArray(shm.name, obj.shape, str(obj.dtype),
                             was_tensor)
        return _NpTensor(obj) if was_tensor else obj
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):
        return type(obj)(*(_encode(x, created) for x in obj))
    if isinstance(obj, (list, tuple)):
        return type(obj)(_encode(x, created) for x in obj)
    if isinstance(obj, dict):
        return {k: _encode(v, created) for k, v in obj.items()}
    return obj


def _decode(obj):
    """Parent-side: materialize shm skeletons (copy out + unlink) and
    restore Tensor leaves."""
    from multiprocessing import shared_memory
    from .._core.tensor import Tensor
    if isinstance(obj, _ShmArray):
        shm = shared_memory.SharedMemory(name=obj.name)
        try:
            arr = np.array(np.ndarray(obj.shape, np.dtype(obj.dtype),
                                      buffer=shm.buf))
        finally:
            shm.close()
            shm.unlink()
        return Tensor(arr) if obj.was_tensor else arr
    if isinstance(obj, _NpTensor):
        return Tensor(obj.arr)
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):
        return type(obj)(*(_decode(x) for x in obj))
    if isinstance(obj, (list, tuple)):
        return type(obj)(_decode(x) for x in obj)
    if isinstance(obj, dict):
        return {k: _decode(v) for k, v in obj.items()}
    return obj


def _release(obj):
    """Unlink shm segments of an undelivered payload (early close)."""
    from multiprocessing import shared_memory
    if isinstance(obj, _ShmArray):
        try:
            shm = shared_memory.SharedMemory(name=obj.name)
            shm.close()
            shm.unlink()
        except Exception:
            pass
    elif isinstance(obj, (list, tuple)):
        for x in obj:
            _release(x)
    elif isinstance(obj, dict):
        for x in obj.values():
            _release(x)


def _np_collate(batch):
    """Pure-numpy default collate for the worker side (no jax, no device
    — the parent wraps the stacked arrays into Tensors). Mirrors
    default_collate_fn's structure handling."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float, np.number)):
        return np.asarray(batch)
    if isinstance(sample, (str, bytes)):
        return list(batch)
    if isinstance(sample, dict):
        return {k: _np_collate([b[k] for b in batch]) for k in sample}
    if isinstance(sample, (tuple, list)):
        return [_np_collate(list(items)) for items in zip(*batch)]
    # Tensor or unknown: defer to the full collate (cpu jax in worker)
    from .dataloader import default_collate_fn
    return default_collate_fn(batch)


def _worker_main(wid, num_workers, ds_bytes, collate_bytes, init_bytes,
                 seed, task_q, result_q):
    """Worker process entry (reference: worker.py:281 _worker_loop).
    The FIRST action pins jax to cpu — before unpickling the dataset,
    whose module imports may pull in paddle_tpu/jax."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("PADDLE_TPU_DEVICE", None)
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    try:
        dataset = pickle.loads(ds_bytes)
        collate_fn = pickle.loads(collate_bytes) if collate_bytes else None
        init_fn = pickle.loads(init_bytes) if init_bytes else None
        import random as pyrandom
        np.random.seed((seed + wid) % (2 ** 32))
        pyrandom.seed(seed + wid)
        from . import dataloader as dl
        dl._worker_info_tls.info = dl.WorkerInfo(
            id=wid, num_workers=num_workers, dataset=dataset)
        if init_fn is not None:
            init_fn(wid)
    except Exception as e:  # startup failure: surface on the first batch
        result_q.put(pickle.dumps((-1, _WorkerError(e))))
        return
    while True:
        task = task_q.get()
        if task is None:
            return
        seq, indices = task
        created: List[Any] = []
        try:
            samples = [dataset[i] for i in indices]
            batch = (collate_fn(samples) if collate_fn is not None
                     else _np_collate(samples))
            payload = _encode(batch, created)
            # pickle HERE: mp.Queue serializes in a background feeder
            # thread that silently DROPS unpicklable items (the parent
            # would wait on this seq forever). Self-pickling turns that
            # into a deliverable error; re-pickling the bytes in the
            # feeder is a cheap memcpy.
            blob = pickle.dumps((seq, payload))
        except Exception as e:  # noqa: BLE001
            for shm in created:
                try:
                    shm.close()
                    shm.unlink()
                except Exception:
                    pass
            blob = pickle.dumps((seq, _WorkerError(e)))
            result_q.put(blob)
            continue
        result_q.put(blob)
        for shm in created:
            shm.close()
            # the parent owns the segment now; drop this process's
            # resource-tracker claim so its exit doesn't unlink/warn
            try:
                from multiprocessing import resource_tracker
                resource_tracker.unregister(shm._name, "shared_memory")
            except Exception:
                pass


class _MPPool:
    """The spawned worker pool: processes + queues + a monotonically
    increasing task-sequence counter. With ``persistent_workers=True``
    (reference: reader.py DataLoader arg) one pool serves every epoch —
    the spawn+import cost (seconds) is paid once, not per epoch. Seqs
    never reset, so results of an abandoned epoch are recognized (and
    their shm released) by the next epoch's ``seq < base`` filter."""

    def __init__(self, loader, num_workers):
        ctx = _ctx()
        self.procs: list = []
        self.closed = False
        # pickled HERE (not via Process args) so failures raise in the
        # parent synchronously -> thread-tier fallback
        ds_bytes = pickle.dumps(loader.dataset)
        collate_bytes = (pickle.dumps(loader.collate_fn)
                         if loader.collate_fn is not None else b"")
        init_fn = getattr(loader, "worker_init_fn", None)
        init_bytes = pickle.dumps(init_fn) if init_fn is not None else b""
        self.num_workers = max(1, num_workers)
        self.task_q = ctx.Queue()
        self.result_q = ctx.Queue()
        self.next_seq = 0
        seed = int(np.random.randint(0, 2 ** 31 - 1))
        for wid in range(self.num_workers):
            p = ctx.Process(
                target=_worker_main,
                args=(wid, self.num_workers, ds_bytes, collate_bytes,
                      init_bytes, seed, self.task_q, self.result_q),
                daemon=True)
            p.start()
            self.procs.append(p)

    def _drain_release(self):
        try:
            while True:
                _, payload = pickle.loads(self.result_q.get_nowait())
                if not isinstance(payload, _WorkerError):
                    _release(payload)
        except pyqueue.Empty:
            pass
        except Exception:
            pass

    def close(self):
        if self.closed:
            return
        self.closed = True
        # sentinels FIRST, then join, then release: a worker mid-batch
        # finishes, puts its payload, and only then takes the sentinel —
        # draining before the join would miss (and leak) that segment
        for _ in self.procs:
            try:
                self.task_q.put_nowait(None)
            except Exception:
                pass
        for p in self.procs:
            p.join(timeout=2.0)
        self._drain_release()
        for p in self.procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=0.5)
        self._drain_release()
        for q in (self.task_q, self.result_q):
            try:
                q.cancel_join_thread()
                q.close()
            except Exception:
                pass

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class MPLoaderIter:
    """Process-pool prefetching iterator for map-style datasets.

    Parent keeps ``cap = num_workers * prefetch_factor`` tasks in
    flight through the pool's task queue; results return out of order
    and a reorder buffer restores sampler order. Construction raises
    (pickle/spawn errors) so DataLoader can fall back to the thread
    tier."""

    def __init__(self, loader, num_workers, prefetch_factor, pool=None):
        self._own_pool = pool is None
        self._pool = pool if pool is not None else _MPPool(loader,
                                                           num_workers)
        prev = getattr(self._pool, "live_iter", None)
        prev = prev() if prev is not None else None
        if prev is not None and not prev._closed:
            # one live iterator per pool: two concurrent consumers would
            # steal each other's results off the shared queue
            prev.close()
        import weakref
        self._pool.live_iter = weakref.ref(self)
        self._procs = self._pool.procs            # liveness checks/tests
        self._closed = False
        self.dataset = loader.dataset
        self._wrap_default = loader.collate_fn is None
        self._sampler_it = iter(loader.batch_sampler)
        self._cap = max(2, self._pool.num_workers * prefetch_factor)
        self._base = self._pool.next_seq          # this epoch's first seq
        self._next_task = self._base
        self._next_out = self._base
        self._buf: dict = {}
        self._errs: dict = {}
        self._exhausted = False
        self._timeout = getattr(loader, "timeout", 0) or 0
        self._fill()

    def _fill(self):
        while not self._exhausted and \
                self._next_task - self._next_out < self._cap:
            try:
                indices = next(self._sampler_it)
            except StopIteration:
                self._exhausted = True
                self._pool.next_seq = self._next_task
                return
            self._pool.task_q.put((self._next_task, list(indices)))
            self._next_task += 1
        self._pool.next_seq = max(self._pool.next_seq, self._next_task)

    def __iter__(self):
        return self

    def __next__(self):
        if self._next_out >= self._next_task and self._exhausted:
            self.close()
            raise StopIteration
        deadline = (time.monotonic() + self._timeout) if self._timeout \
            else None
        while self._next_out not in self._buf and \
                self._next_out not in self._errs:
            try:
                seq, payload = pickle.loads(
                    self._pool.result_q.get(timeout=1.0))
            except pyqueue.Empty:
                dead = [p for p in self._procs if not p.is_alive()]
                if dead and self._next_out < self._next_task:
                    self.close()
                    raise RuntimeError(
                        f"DataLoader worker (pid {dead[0].pid}) exited "
                        f"unexpectedly (exitcode={dead[0].exitcode})")
                if deadline is not None and time.monotonic() > deadline:
                    self.close()
                    raise RuntimeError(
                        f"DataLoader timed out after {self._timeout}s "
                        "waiting for a worker batch")
                continue
            if 0 <= seq < self._base:
                # stragglers of an abandoned earlier epoch (persistent
                # pool): release and drop
                if not isinstance(payload, _WorkerError):
                    _release(payload)
                continue
            if isinstance(payload, _WorkerError):
                # startup failures (seq==-1) surface at the next batch
                self._errs[self._next_out if seq < 0 else seq] = payload
            else:
                self._buf[seq] = payload
        if self._next_out in self._errs:
            err = self._errs.pop(self._next_out)
            self.close()
            err.reraise()
        payload = self._buf.pop(self._next_out)
        self._next_out += 1
        self._fill()
        batch = _decode(payload)
        if self._wrap_default:
            batch = _tensorize(batch)
        return batch

    def close(self):
        if self._closed:
            return
        self._closed = True
        for p in self._buf.values():
            _release(p)
        self._buf.clear()
        # in-flight seqs of this epoch stay owned by the pool; the next
        # epoch's base filter releases any stragglers
        self._pool.next_seq = max(self._pool.next_seq, self._next_task)
        if self._own_pool:
            self._pool.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def _tensorize(batch):
    """Wrap the worker's numpy default-collate output into Tensors
    (structure mirror of default_collate_fn's output types)."""
    from .._core.tensor import Tensor
    if isinstance(batch, np.ndarray):
        return Tensor(batch)
    if isinstance(batch, dict):
        return {k: _tensorize(v) for k, v in batch.items()}
    if isinstance(batch, list):
        return [_tensorize(b) for b in batch]
    return batch
