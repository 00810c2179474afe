"""DataLoader (reference: python/paddle/io/dataloader/dataloader_iter.py).

Three worker transports, fastest applicable wins:

  - native: C++ prefetcher for TensorDataset + default collation
    (shuffle/gather/queueing off the GIL) — the hot path for tensor data;
  - threads (default fallback): thread-pool ``__getitem__`` + a bounded
    background prefetch queue — enough when ``__getitem__`` releases the
    GIL (numpy slicing, file I/O);
  - processes (``use_process_workers=True``): the reference's
    multiprocess worker/shared-memory design for GIL-BOUND ``__getitem__``
    transforms (pure-Python augmentation pipelines): each worker process
    collates whole batches and ships ndarray payloads through
    ``multiprocessing.shared_memory`` segments (one memcpy each side, no
    pickling of array bytes), with batch-index reordering so delivery
    order matches the sampler. Fork-safety contract: ``__getitem__``
    must return numpy/python data, not device-backed Tensors created in
    the parent — a forked worker reading those goes through XLA state
    that did not survive the fork (``TensorDataset`` is materialized to
    numpy in the parent automatically).

``DevicePrefetcher`` composes on top: it stages the NEXT host batch onto
the device (async ``device_put`` / a TrainStep's sharded ``stage``) while
the current step runs — double buffering so input H2D overlaps compute.
"""

from __future__ import annotations

import collections
import os
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Optional

import numpy as np

from ..core.tensor import Tensor
from ..framework.random import default_seed
from .dataset import Dataset, IterableDataset, TensorDataset
from .sampler import BatchSampler


class _WorkerInfo:
    def __init__(self, id=0, num_workers=0, dataset=None):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info: Optional[_WorkerInfo] = None


def get_worker_info():
    return _worker_info


def _collate(batch, wrap):
    """One recursive collator for both public collate fns: ``wrap``
    decides what a stacked ndarray leaf becomes (Tensor vs raw numpy)."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return wrap(np.stack([np.asarray(s._value) for s in batch]))
    if isinstance(sample, np.ndarray):
        return wrap(np.stack(batch))
    if isinstance(sample, (int, float, np.number)):
        return wrap(np.asarray(batch))
    if isinstance(sample, (list, tuple)):
        return type(sample)(_collate([b[i] for b in batch], wrap)
                            for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: _collate([b[k] for b in batch], wrap) for k in sample}
    return list(batch)


def numpy_collate_fn(batch):
    """``default_collate_fn`` with numpy leaves instead of Tensors — what
    process workers run: a forked worker must never touch jax (live XLA
    thread state does not survive fork), so batches cross the process
    boundary as raw ndarrays and become Tensors in the parent."""
    return _collate(batch, lambda a: a)


def default_collate_fn(batch):
    """Stack samples into batched numpy/Tensor structures."""
    return _collate(batch, Tensor)


# ------------------------------------------------------- process workers
# Reference: python/paddle/io/dataloader/worker.py + the C++ shared-memory
# queue. Each worker process owns whole BATCHES (indices in, collated
# batch out): ndarray payloads travel through multiprocessing.shared_memory
# segments (worker writes once, parent copies once and unlinks), everything
# else rides the result queue's pickle. Fork start inherits the dataset —
# no per-epoch dataset pickling — and workers stay numpy-only. Fork of a
# multithreaded (jax-initialized) parent is the reference's own POSIX
# default and shares its caveat: a child can inherit a lock held at fork
# time. Workers run only numpy/queue code, which keeps this safe in
# practice; PADDLE_TPU_MP_START=spawn|forkserver overrides (at the cost
# of per-epoch dataset pickling and child re-imports).

def _shm_unregister(shm):
    """The creating process's resource_tracker would unlink the segment at
    worker exit — but ownership transfers to the parent (which unlinks
    after copying). Deregister on the worker side."""
    try:
        from multiprocessing import resource_tracker
        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:
        pass


def _shm_encode(obj, segs):
    """obj -> picklable tag tree; ndarray leaves move into shm segments
    (appended to ``segs``). Tensors are read out via numpy (worker-side
    Tensors only appear from user collate_fns) and tagged so the parent
    restores the type."""
    was_tensor = isinstance(obj, Tensor)
    if was_tensor:
        obj = np.asarray(obj._value)
    if isinstance(obj, np.ndarray) and not obj.dtype.hasobject:
        from multiprocessing import shared_memory
        shm = shared_memory.SharedMemory(create=True,
                                         size=max(1, obj.nbytes))
        np.ndarray(obj.shape, obj.dtype, buffer=shm.buf)[...] = obj
        segs.append(shm)
        return ("nd", shm.name, obj.dtype.str, obj.shape, was_tensor)
    if isinstance(obj, (list, tuple)):
        return ("seq", type(obj) is tuple,
                [_shm_encode(o, segs) for o in obj])
    if isinstance(obj, dict):
        return ("map", {k: _shm_encode(v, segs) for k, v in obj.items()})
    return ("obj", obj)


def _shm_decode(msg, to_tensor):
    tag = msg[0]
    if tag == "nd":
        from multiprocessing import shared_memory
        _, name, dtype, shape, was_tensor = msg
        shm = shared_memory.SharedMemory(name=name)
        try:
            arr = np.ndarray(shape, dtype, buffer=shm.buf).copy()
        finally:
            shm.close()
            try:
                shm.unlink()
            except FileNotFoundError:
                pass
        return Tensor(arr) if (was_tensor or to_tensor) else arr
    if tag == "seq":
        _, is_tuple, items = msg
        out = [_shm_decode(m, to_tensor) for m in items]
        return tuple(out) if is_tuple else out
    if tag == "map":
        return {k: _shm_decode(v, to_tensor) for k, v in msg[1].items()}
    return msg[1]


def _shm_discard(msg):
    """Unlink the segments of an undecoded payload (shutdown drain)."""
    if msg[0] == "nd":
        from multiprocessing import shared_memory
        try:
            shm = shared_memory.SharedMemory(name=msg[1])
            shm.close()
            shm.unlink()
        except Exception:
            pass
    elif msg[0] == "seq":
        for m in msg[2]:
            _shm_discard(m)
    elif msg[0] == "map":
        for m in msg[1].values():
            _shm_discard(m)


def _process_worker_loop(dataset, collate_fn, index_q, result_q, wid,
                         num_workers, worker_init_fn, base_seed):
    global _worker_info
    _worker_info = _WorkerInfo(wid, num_workers, dataset)
    np.random.seed((base_seed + wid) % (2 ** 32))
    # fault injection (FLAGS_fault_inject 'dataloader_worker:...'): an
    # armed site makes this worker HARD-EXIT mid-batch — the death shape
    # the parent's restart-with-backoff machinery recovers from, as
    # opposed to a clean exception (which rides result_q and re-raises)
    from ..testing import faults as _faults
    _fault = _faults.site("dataloader_worker")
    try:
        if worker_init_fn is not None:
            worker_init_fn(wid)
        while True:
            item = index_q.get()
            if item is None:
                return
            bidx, indices = item
            try:
                _fault.check(batch=bidx)
            except _faults.InjectedFault:
                # flush the result queue's feeder thread before dying:
                # os._exit mid-flush can kill the feeder while it holds
                # the queue's shared write lock, wedging every SURVIVOR's
                # put() forever (seen once under a loaded box in r14).
                # The death shape the parent sees is unchanged — nothing
                # is reported, no sentinel, just a vanished process.
                try:
                    result_q.close()
                    result_q.join_thread()
                except Exception:
                    pass
                os._exit(3)     # simulated worker death, not an error
            segs = []
            try:
                batch = collate_fn([dataset[i] for i in indices])
                result_q.put((bidx, "ok", _shm_encode(batch, segs)))
            except Exception as e:  # surfaced on the parent side, in order
                import traceback
                for s in segs:  # partial encode: don't leak segments
                    try:
                        s.close()
                        s.unlink()
                    except Exception:
                        pass
                segs = []
                result_q.put((bidx, "err",
                              f"{e!r}\n{traceback.format_exc()[-2000:]}"))
            for s in segs:
                s.close()
                _shm_unregister(s)
    except KeyboardInterrupt:
        pass


class DataLoader:
    def __init__(self, dataset: Dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False, use_process_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = max(prefetch_factor, 1)
        self.use_buffer_reader = use_buffer_reader
        # process workers are OPT-IN (for GIL-bound __getitem__); the
        # thread pool / native prefetcher stay the default transport
        self.use_process_workers = bool(use_process_workers)
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self._iterable_mode = isinstance(dataset, IterableDataset)
        self._native = None   # lazily-built native fast path
        self._epoch = 0
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size, drop_last=drop_last)
            # plain sampling over a TensorDataset with default collation is
            # the hot path — serve it from the native (C++) prefetcher:
            # shuffle + gather + queueing run off the GIL
            # (paddle_tpu/native, reference: DataLoader C workers)
            # exact-type check: a subclass may override __getitem__ (per-
            # sample transforms), which this path bypasses
            self._native_eligible = (
                use_shared_memory
                and self.collate_fn is default_collate_fn
                and type(dataset) is TensorDataset)
            self._native_cfg = (batch_size, shuffle, drop_last)

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no len()")
        return len(self.batch_sampler)

    def _native_batches(self):
        """C++ prefetcher path (see __init__); None when ineligible.

        Each call returns a generator with its OWN prefetcher handle, so
        concurrent or abandoned iterations can't steal each other's
        batches; the handle is destroyed when the generator closes."""
        if not getattr(self, "_native_eligible", False):
            return None
        from .. import native
        if not native.available():
            self._native_eligible = False
            return None
        if self._native is None:  # cache the contiguous views only
            try:
                arrays = [np.ascontiguousarray(
                    t._value if isinstance(t, Tensor) else t)
                    for t in self.dataset.tensors]
            except Exception:
                self._native_eligible = False
                return None
            if any(a.dtype.hasobject for a in arrays):
                # the C++ gather memcpys raw bytes — object arrays would
                # smuggle PyObject* without refcounts
                self._native_eligible = False
                return None
            self._native = arrays
        batch_size, shuffle, drop_last = self._native_cfg

        def gen():
            pf = native.BatchPrefetcher(
                self._native, batch_size=batch_size, shuffle=shuffle,
                drop_last=drop_last, capacity=self.prefetch_factor,
                n_threads=max(self.num_workers, 1))
            try:
                self._epoch += 1
                # same seed recipe as the fallback RandomSampler, so
                # paddle.seed() steers the data order on both paths
                for bufs in pf.epoch(seed=default_seed() + self._epoch):
                    yield tuple(Tensor(b) for b in bufs)
            finally:
                pf.close()
        return gen()

    def _process_batches(self):
        """Multiprocess worker path (see module docstring); None when
        ineligible (iterable dataset, num_workers==0, or opt-out). Each
        call owns its worker pool for one epoch; batches are reordered to
        sampler order and worker exceptions re-raise in the parent."""
        if (not self.use_process_workers or self.num_workers <= 0
                or self._iterable_mode):
            return None
        import multiprocessing as mp
        try:
            ctx = mp.get_context(
                os.environ.get("PADDLE_TPU_MP_START", "fork"))
        except ValueError:
            return None   # platform without fork: thread fallback
        n = self.num_workers
        to_tensor = self.collate_fn is default_collate_fn
        collate = numpy_collate_fn if to_tensor else self.collate_fn
        timeout = self.timeout or None
        dataset = self.dataset
        from .dataset import TensorDataset
        if isinstance(dataset, TensorDataset):
            # materialize device-backed tensors to numpy HERE, in the
            # parent, where jax is live: a forked worker reading a
            # jax-backed Tensor._value would go through XLA thread state
            # that did not survive the fork
            dataset = TensorDataset([
                np.asarray(t._value) if isinstance(t, Tensor)
                else np.asarray(t) for t in dataset.tensors])

        def gen():
            # fresh per-epoch base seed (like the native path): worker
            # augmentation randomness must not repeat across epochs
            self._epoch += 1
            base_seed = default_seed() + self._epoch
            index_q = ctx.Queue()
            result_q = ctx.Queue()

            def spawn(wid):
                w = ctx.Process(
                    target=_process_worker_loop,
                    args=(dataset, collate, index_q, result_q, wid,
                          n, self.worker_init_fn, base_seed),
                    daemon=True)
                w.start()
                return w

            workers = [spawn(wid) for wid in range(n)]
            from .. import flags as _flags
            from .. import observability as obs
            restart_budget = n * max(0, int(
                _flags.get_flag("dataloader_max_worker_restarts")))
            m_restarts = obs.registry().counter(
                "io_worker_restarts",
                "process DataLoader workers restarted after dying "
                "mid-epoch")
            sampler_it = enumerate(iter(self.batch_sampler))
            pending = {}        # bidx -> indices, fed but not delivered
            buffered = {}
            next_yield = 0
            restarts = 0
            # short poll so worker death is noticed promptly; ``timeout``
            # (the user knob) is enforced as accumulated silent time
            poll = min(timeout, 0.25) if timeout else 0.25
            silent = 0.0
            try:
                def feed():
                    item = next(sampler_it, None)
                    if item is not None:
                        bidx, indices = item
                        pending[bidx] = list(indices)
                        index_q.put((bidx, pending[bidx]))

                for _ in range(n * self.prefetch_factor):
                    feed()
                while pending:
                    try:
                        bidx, status, payload = result_q.get(timeout=poll)
                    except queue.Empty:
                        silent += poll
                        dead = [i for i, w in enumerate(workers)
                                if not w.is_alive()]
                        if dead:
                            # a dead worker's batch is lost (clean worker
                            # exceptions ride result_q; the shutdown
                            # sentinel is only sent after the loop) and
                            # waiting on the survivors would hang forever.
                            # Restart with backoff and resubmit every
                            # undelivered batch — WHICH one died with the
                            # worker is unknowable (the index queue is
                            # shared), so survivors may redo a few;
                            # duplicate deliveries are discarded below.
                            if restarts + len(dead) > restart_budget:
                                raise RuntimeError(
                                    f"DataLoader process workers died "
                                    f"{restarts + len(dead)} times (budget"
                                    f" {restart_budget}); giving up — see "
                                    f"FLAGS_dataloader_max_worker_restarts")
                            time.sleep(min(0.05 * (2 ** restarts), 1.0))
                            for i in dead:
                                # wide join margin: under a loaded box
                                # the OS can take well over the old
                                # 0.5 s to reap a dead child, and a
                                # replacement spawned beside an
                                # unreaped zombie slot flaked once in
                                # r14 — the join is on an already-dead
                                # process, so the margin costs nothing
                                # in the common case
                                workers[i].join(timeout=2.0)
                                workers[i] = spawn(i)
                            restarts += len(dead)
                            m_restarts.inc(len(dead))
                            for bidx2 in sorted(pending):
                                index_q.put((bidx2, pending[bidx2]))
                            silent = 0.0
                            continue
                        if timeout and silent >= timeout:
                            # workers alive but slow: a timeout, not a
                            # death — report it as what it is
                            raise RuntimeError(
                                f"DataLoader worker batch timed out "
                                f"after {timeout}s (workers alive; raise "
                                f"timeout or speed up __getitem__)")
                        continue
                    silent = 0.0
                    if bidx not in pending:
                        # duplicate of a resubmitted batch (the original
                        # arrived after a restart resubmit): drop it
                        if status == "ok":
                            _shm_discard(payload)
                        continue
                    del pending[bidx]
                    feed()
                    buffered[bidx] = (status, payload)
                    while next_yield in buffered:
                        status, payload = buffered.pop(next_yield)
                        next_yield += 1
                        if status != "ok":
                            raise RuntimeError(
                                f"DataLoader worker failed: {payload}")
                        yield _shm_decode(payload, to_tensor)
            finally:
                for _ in workers:
                    try:
                        index_q.put_nowait(None)
                    except Exception:
                        pass
                # drain undelivered payloads so their segments unlink
                while True:
                    try:
                        _, status, payload = result_q.get_nowait()
                    except Exception:
                        break
                    if status == "ok":
                        _shm_discard(payload)
                for _, payload in ((k, v[1]) for k, v in buffered.items()
                                   if v[0] == "ok"):
                    _shm_discard(payload)
                for w in workers:
                    w.join(timeout=2.0)
                    if w.is_alive():
                        w.terminate()
                # a worker mid-collate at the first drain may have
                # delivered AFTER it; its segment is worker-unregistered,
                # so only this post-join drain can unlink it
                while True:
                    try:
                        _, status, payload = result_q.get(timeout=0.2)
                    except Exception:
                        break
                    if status == "ok":
                        _shm_discard(payload)
        return gen()

    def _iter_batches(self):
        if self._iterable_mode:
            batch = []
            for sample in self.dataset:
                batch.append(sample)
                if len(batch) == self.batch_size:
                    yield self.collate_fn(batch)
                    batch = []
            if batch and not self.drop_last:
                yield self.collate_fn(batch)
            return

        if self.num_workers > 0:
            pool = ThreadPoolExecutor(max_workers=self.num_workers)
            try:
                for indices in self.batch_sampler:
                    samples = list(pool.map(self.dataset.__getitem__, indices))
                    yield self.collate_fn(samples)
            finally:
                pool.shutdown(wait=False)
        else:
            for indices in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in indices])

    def __iter__(self):
        proc_gen = self._process_batches()
        if proc_gen is not None:
            # the worker pool already prefetches n*prefetch_factor batches
            # ahead; a buffer-reader thread would only add a second queue
            yield from proc_gen
            return
        native_gen = self._native_batches()
        if native_gen is not None:
            # the C++ prefetcher already double-buffers off the GIL; the
            # Python buffer-reader thread would only add a second queue
            yield from native_gen
            return
        if not self.use_buffer_reader:
            yield from self._iter_batches()
            return
        # background prefetch: keep `prefetch_factor` batches ready
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch_factor)
        _SENTINEL = object()
        exc = []

        def producer():
            try:
                for b in self._iter_batches():
                    q.put(b)
            except BaseException as e:  # surfaced on the consumer side
                exc.append(e)
            finally:
                q.put(_SENTINEL)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is _SENTINEL:
                break
            yield item
        if exc:
            raise exc[0]


# ---------------------------------------------------------- device staging
def _default_stage(batch):
    """Async host->device placement for common batch shapes (Tensor /
    ndarray leaves in flat tuples/lists/dicts)."""
    import jax

    def place(x):
        if isinstance(x, Tensor):
            return Tensor(jax.device_put(x._value),
                          stop_gradient=x.stop_gradient)
        if isinstance(x, (np.ndarray, np.number)):
            return jax.device_put(np.asarray(x))
        return x

    if isinstance(batch, (list, tuple)):
        return type(batch)(place(b) for b in batch)
    if isinstance(batch, dict):
        return {k: place(v) for k, v in batch.items()}
    return place(batch)


class DevicePrefetcher:
    """Double-buffered device prefetch: stage batch N+1 host->device while
    the consumer runs step N, so input transfer overlaps compute.

    ``stage_fn`` maps a host batch to its device-resident form and must
    only DISPATCH (``jax.device_put`` and friends are async) — a
    TrainStep's ``stage`` applies the step's data sharding, the default
    places leaves on the default device. ``depth`` batches are kept
    staged ahead (2 = classic double buffering); staging happens eagerly
    on ``__next__`` so the H2D copy of the following batch is in flight
    before the current one is consumed."""

    def __init__(self, data, stage_fn: Optional[Callable] = None,
                 depth: int = 2):
        from .. import observability as obs

        self._data = data
        self._stage = stage_fn if stage_fn is not None else _default_stage
        self.depth = max(1, int(depth))
        # counts the staging dispatches; how long one takes is the
        # stage function's to say (TrainStep.stage: ``train.stage``)
        self._m_staged = obs.registry().counter(
            "io_batches_staged",
            "batches staged host->device by DevicePrefetcher")

    def __iter__(self):
        buf = collections.deque()
        it = iter(self._data)
        exhausted = False
        while True:
            while not exhausted and len(buf) < self.depth:
                try:
                    nxt = next(it)
                except StopIteration:
                    exhausted = True
                    continue
                buf.append(self._stage(nxt))
                self._m_staged.inc()
            if not buf:
                return
            yield buf.popleft()
