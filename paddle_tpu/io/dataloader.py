"""DataLoader (reference: python/paddle/fluid/reader.py:149 DataLoader,
dataloader/dataloader_iter.py, worker.py; C++ double-buffer
operators/reader/buffered_reader.cc).

TPU-native design: multiprocess workers feed a result queue (the
reference's shared-memory + blocking-queue design collapses to an mp.Queue
of numpy batches), and the iterator keeps a one-batch host->device
prefetch in flight so H2D overlaps with the train step (the
buffered_reader analog).
"""
import atexit
import itertools
import multiprocessing as mp
import queue as queue_mod
import threading
import time

import numpy as np

from ..core.tensor import Tensor
from ..obs import tracing
from ..resilience.retry import call_with_retry
from .dataset import IterableDataset
from .sampler import BatchSampler, SequenceSampler, RandomSampler


class WorkerInfo:
    def __init__(self, id, num_workers, dataset, seed):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset
        self.seed = seed


_worker_info = None


def get_worker_info():
    return _worker_info


def default_collate_fn(batch):
    """Stack list-of-samples into batch arrays (reference:
    dataloader/collate.py default_collate_fn)."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return Tensor(np.stack([np.asarray(s._value) for s in batch]))
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float)):
        return np.asarray(batch)
    if isinstance(sample, (str, bytes)):
        return batch
    if isinstance(sample, dict):
        return {k: default_collate_fn([s[k] for s in batch]) for k in sample}
    if isinstance(sample, (list, tuple)):
        return type(sample)(default_collate_fn(list(items))
                            for items in zip(*batch))
    return np.asarray(batch)


def _to_tensor_tree(obj):
    if isinstance(obj, np.ndarray):
        return Tensor(obj)
    if isinstance(obj, dict):
        return {k: _to_tensor_tree(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return type(obj)(_to_tensor_tree(v) for v in obj)
    return obj


def _tree_nbytes(obj):
    """Bytes of the arrays of a batch (the ``bytes`` of ``io.next_batch``)."""
    if isinstance(obj, dict):
        return sum(_tree_nbytes(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return sum(_tree_nbytes(v) for v in obj)
    return getattr(obj._value if isinstance(obj, Tensor) else obj,
                   "nbytes", 0)


def _convert(data, sp):
    """The batch as Tensors (a host-to-device copy per array), timed as
    ``io.next_batch.convert``; its size goes onto the enclosing span."""
    with tracing.span("io.next_batch.convert"):
        out = _to_tensor_tree(data)
    sp.attrs["bytes"] = _tree_nbytes(out)
    return out


def _worker_loop(dataset, index_queue, out_queue, collate_fn, worker_id,
                 num_workers, seed, iterable):
    global _worker_info
    _worker_info = WorkerInfo(worker_id, num_workers, dataset, seed)
    np.random.seed(seed)
    try:
        if iterable:
            it = iter(dataset)
            while True:
                cmd = index_queue.get()
                if cmd is None:
                    break
                batch_idx, batch_size = cmd
                samples = list(itertools.islice(it, batch_size))
                if not samples:
                    out_queue.put((batch_idx, StopIteration(), None))
                    break
                out_queue.put((batch_idx, collate_fn(samples), None))
        else:
            while True:
                cmd = index_queue.get()
                if cmd is None:
                    break
                batch_idx, indices = cmd
                try:
                    t0 = time.monotonic()
                    # transient I/O from remote-FS-backed datasets gets
                    # backoff+retry instead of poisoning the batch
                    samples = [call_with_retry(dataset.__getitem__, i,
                                               retry_on=(OSError,),
                                               base_delay=0.05)
                               for i in indices]
                    batch = collate_fn(samples)
                    # the worker's fetch+collate seconds travel with the
                    # batch: the parent records ``io.worker.produce``, so
                    # a reader can tell slow workers from a slow pipe
                    out_queue.put((batch_idx, batch,
                                   time.monotonic() - t0))
                except Exception as e:  # noqa: BLE001
                    out_queue.put((batch_idx, e, None))
    except KeyboardInterrupt:
        pass


class _MultiprocessIter:
    def __init__(self, loader):
        self.loader = loader
        self.ctx = mp.get_context("fork")
        self.out_queue = self.ctx.Queue()
        self.workers = []
        self.index_queues = []
        self.batches = iter(loader.batch_sampler)
        self.send_idx = 0
        self.rcvd_idx = 0
        self.reorder = {}
        self.done_sending = False
        seed = np.random.randint(0, 2 ** 31)
        for wid in range(loader.num_workers):
            iq = self.ctx.Queue()
            w = self.ctx.Process(
                target=_worker_loop,
                args=(loader.dataset, iq, self.out_queue, loader.collate_fn, wid,
                      loader.num_workers, seed + wid, False),
                daemon=True)
            w.start()
            self.workers.append(w)
            self.index_queues.append(iq)
        atexit.register(self._shutdown)
        # in-flight dispatch bounded by prefetch_factor per worker (the
        # reference/PyTorch semantic); each completed batch triggers one
        # _send_next, so this is the steady-state cap too
        for _ in range(loader.num_workers * loader.prefetch_factor):
            self._send_next()

    def _send_next(self):
        if self.done_sending:
            return
        try:
            indices = next(self.batches)
        except StopIteration:
            self.done_sending = True
            return
        wid = self.send_idx % len(self.workers)
        self.index_queues[wid].put((self.send_idx, indices))
        self.send_idx += 1

    def __next__(self):
        if self.rcvd_idx >= self.send_idx and self.done_sending:
            self._shutdown()
            raise StopIteration
        with tracing.span("io.next_batch", source="workers",
                          workers=len(self.workers)) as sp:
            with tracing.span("io.next_batch.wait"):
                # blocked on the pipe: the workers' pace, the transfer and
                # the unpickling thread together
                while self.rcvd_idx not in self.reorder:
                    idx, data, produce_s = self.out_queue.get()
                    self.reorder[idx] = data
                    if produce_s is not None:
                        tracing.record_span(
                            "io.worker.produce", produce_s,
                            worker=idx % len(self.workers),
                            seconds=produce_s)
            data = self.reorder.pop(self.rcvd_idx)
            self.rcvd_idx += 1
            self._send_next()
            if isinstance(data, Exception):
                self._shutdown()
                raise data
            return _convert(data, sp)

    def _shutdown(self):
        for iq in self.index_queues:
            try:
                iq.put(None)
            except Exception:  # noqa: BLE001
                pass
        for w in self.workers:
            w.join(timeout=1.0)
            if w.is_alive():
                w.terminate()
        self.workers = []


class _SingleProcessIter:
    def __init__(self, loader):
        self.loader = loader
        self.batches = iter(loader.batch_sampler)

    def __next__(self):
        indices = next(self.batches)
        # self time = fetch + collate in this process
        with tracing.span("io.next_batch", source="dataset",
                          workers=0) as sp:
            # same transient-I/O retry the multiprocess workers get
            samples = [call_with_retry(self.loader.dataset.__getitem__, i,
                                       retry_on=(OSError,), base_delay=0.05)
                       for i in indices]
            return _convert(self.loader.collate_fn(samples), sp)


class _IterableDatasetIter:
    def __init__(self, loader):
        self.loader = loader
        self.it = iter(loader.dataset)

    def __next__(self):
        samples = list(itertools.islice(self.it, self.loader.batch_size))
        if not samples:
            raise StopIteration
        if self.loader.drop_last and len(samples) < self.loader.batch_size:
            raise StopIteration
        return _to_tensor_tree(self.loader.collate_fn(samples))


class _PrefetchIter:
    """Bounded lookahead on a background thread (buffered_reader analog).

    ``depth`` (the DataLoader's ``prefetch_factor``) is a hard cap on
    how many batches exist ahead of the consumer: a slot semaphore is
    acquired BEFORE the next batch is materialized and released when
    the consumer takes one, so at most ``depth`` batches are ever
    buffered — a queue-maxsize bound alone would still let the filler
    hold one extra materialized batch while blocked in put()."""

    def __init__(self, inner, depth=2):
        self.inner = inner
        self.depth = max(1, int(depth))
        self._slots = threading.Semaphore(self.depth)
        self.q = queue_mod.Queue()
        self.thread = threading.Thread(target=self._fill, daemon=True)
        self.thread.start()

    def _fill(self):
        try:
            while True:
                self._slots.acquire()
                self.q.put(("data", next(self.inner)))
        except StopIteration:
            self.q.put(("stop", None))
        except Exception as e:  # noqa: BLE001
            self.q.put(("error", e))

    def __next__(self):
        with tracing.span("io.next_batch", source="prefetch") as sp:
            with tracing.span("io.next_batch.wait"):
                kind, payload = self.q.get()
            self._slots.release()  # consumer took a batch: free one slot
            if kind == "stop":
                raise StopIteration
            if kind == "error":
                raise payload
            sp.attrs["bytes"] = _tree_nbytes(payload)
            return payload


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None, return_list=True,
                 batch_sampler=None, batch_size=1, shuffle=False, drop_last=False,
                 collate_fn=None, num_workers=0, use_buffer_reader=True,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 prefetch_factor=2, persistent_workers=False):
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.use_buffer_reader = use_buffer_reader
        self.batch_size = batch_size
        self.drop_last = drop_last
        # caps BOTH the buffered-reader lookahead (at most this many
        # batches materialized ahead of the consumer) and, with workers,
        # the in-flight index dispatch per worker
        self.prefetch_factor = max(1, int(prefetch_factor))
        self._iterable = isinstance(dataset, IterableDataset)
        if self._iterable:
            self.batch_sampler = None
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = batch_sampler.batch_size
        else:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)

    def __iter__(self):
        # forking the workers and starting the reader thread: start-up work
        with tracing.span("io.loader.start", workers=self.num_workers,
                          buffered=bool(self.use_buffer_reader)):
            if self._iterable:
                inner = _IterableDatasetIter(self)
            elif self.num_workers > 0:
                inner = _MultiprocessIter(self)
            else:
                inner = _SingleProcessIter(self)
            it = (_PrefetchIter(inner, depth=self.prefetch_factor)
                  if self.use_buffer_reader else inner)

        class _Wrapper:
            def __iter__(w):
                return w

            def __next__(w):
                return next(it)

        return _Wrapper()

    def __len__(self):
        if self._iterable:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)

    # fluid-style constructors (reference: reader.py from_generator:432)
    @staticmethod
    def from_generator(feed_list=None, capacity=64, use_double_buffer=True,
                       iterable=True, return_list=False, use_multiprocess=False,
                       drop_last=True):
        """Legacy static-graph loader (reference: fluid/reader.py
        GeneratorLoader): returns an object whose
        set_sample_generator / set_sample_list_generator /
        set_batch_generator feed the static program; iterating yields
        Executor-ready feed dicts keyed by the feed_list var names (or
        plain lists with return_list=True). capacity/use_double_buffer
        are accepted for compatibility — host->device staging is XLA's
        job on TPU."""
        return _GeneratorLoader(feed_list, return_list, drop_last)


class _GeneratorLoader:
    """reference: fluid/reader.py GeneratorLoader (from_generator)."""

    def __init__(self, feed_list, return_list, drop_last):
        self.feed_list = list(feed_list or [])
        self.return_list = return_list
        self.drop_last = drop_last
        self._batch_gen = None

    def set_sample_generator(self, reader, batch_size, drop_last=True,
                             places=None):
        # default True matches the reference set_sample_generator; the
        # from_generator-level drop_last is a DIFFERENT knob there
        # (drop trailing batches fewer than the device count — moot for
        # this single-stream loader, kept as an API carrier). None (the
        # short-lived 'inherit' sentinel) normalizes to True.
        drop = True if drop_last is None else drop_last

        def batches():
            buf = []
            for sample in reader():
                buf.append(sample if isinstance(sample, (list, tuple))
                           else [sample])
                if len(buf) == batch_size:
                    yield [np.stack([row[i] for row in buf])
                           for i in range(len(buf[0]))]
                    buf = []
            if buf and not drop:
                yield [np.stack([row[i] for row in buf])
                       for i in range(len(buf[0]))]

        self._batch_gen = batches
        return self

    def set_sample_list_generator(self, reader, places=None):
        def batches():
            for sample_list in reader():
                yield [np.stack([row[i] for row in sample_list])
                       for i in range(len(sample_list[0]))]

        self._batch_gen = batches
        return self

    def set_batch_generator(self, reader, places=None):
        self._batch_gen = reader
        return self

    def __call__(self):
        return iter(self)

    def __iter__(self):
        if self._batch_gen is None:
            raise RuntimeError(
                "from_generator loader has no data source: call "
                "set_sample_generator / set_sample_list_generator / "
                "set_batch_generator first")
        for batch in self._batch_gen():
            arrays = [np.asarray(a) for a in batch]
            if self.return_list:
                yield arrays
            else:
                names = [getattr(v, "name", f"feed_{i}")
                         for i, v in enumerate(self.feed_list)]
                if len(names) != len(arrays):
                    raise ValueError(
                        f"from_generator batch has {len(arrays)} arrays "
                        f"but feed_list names {len(names)} — pass a "
                        "matching feed_list, or return_list=True")
                yield dict(zip(names, arrays))
