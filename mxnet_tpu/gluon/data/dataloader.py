"""DataLoader (reference: mxnet/gluon/data/dataloader.py).

Two worker models:

- ``worker_type="thread"`` (default): prefetching on the C++
  host-runtime thread pool (runtime/engine) when available, else a
  Python thread pool. TPU input pipelines are host-CPU-bound and the
  numpy-heavy batchify releases the GIL, so threads + a device
  double-buffer cover the reference's multiprocess workers + pinned
  memory for most pipelines.
- ``worker_type="process"``: a multiprocessing pool like the
  reference's, for Python-heavy transforms (PIL color jitter) that
  hold the GIL. Uses the *spawn* context — forking a JAX-threaded
  parent can deadlock — and each worker pins the CPU platform before
  touching JAX: the chip belongs to the parent process. Standard
  spawn rules apply: dataset/batchify must be picklable and script
  entry points need an ``if __name__ == "__main__":`` guard.
"""
from __future__ import annotations

import pickle
import queue
import threading
import time
import weakref
from typing import Optional

import numpy as _np

from ... import telemetry as _tm
from ...ndarray import NDArray, array
from .sampler import SequentialSampler, RandomSampler, BatchSampler

__all__ = ["DataLoader", "DevicePrefetcher", "default_batchify_fn",
           "window_iter"]


def window_iter(it, k: int):
    """Group an iterator into lists of up to `k` consecutive batches —
    the feed for the compiled K-step training loop
    (FusedTrainStep.run_steps stacks each window to (K, ...) and runs
    it as one lax.scan dispatch). The final window is ragged (shorter)
    when the epoch length is not a multiple of `k`. Compose with
    DevicePrefetcher so the prefetch thread fills the next window while
    the current dispatch runs:

        for window in window_iter(DevicePrefetcher(loader), k=8):
            losses = step.run_steps(window)
    """
    if k < 1:
        raise ValueError(f"window size must be >= 1; got {k}")
    win = []
    for item in it:
        win.append(item)
        if len(win) == k:
            yield win
            win = []
    if win:
        yield win


class DevicePrefetcher:
    """Double-buffered device feed (the pinned-memory prefetch
    analogue): a background thread pulls batches ahead of the consumer
    so host batchify + the host->device transfer of batch i+1 overlap
    with the device compute of batch i. NDArray creation already
    enqueues the transfer asynchronously; the prefetch thread's job is
    to keep pulling so those transfers are in flight before the
    training loop asks."""

    def __init__(self, loader, depth: int = 2):
        self._loader = loader
        self._depth = max(1, depth)

    def __len__(self):
        return len(self._loader)  # loaders only; generators raise

    def __iter__(self):
        q: "queue.Queue" = queue.Queue(maxsize=self._depth)
        _END = object()
        stop = threading.Event()

        def _put(item):
            # bounded put that aborts when the consumer went away, so
            # an early `break` in the training loop cannot leak a
            # thread blocked forever on a full queue
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
            return False

        def produce():
            try:
                for item in self._loader:
                    if not _put(item):
                        return
                _put(_END)
            except Exception as e:  # surface in the consumer
                _put(e)

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is _END:
                    return
                if isinstance(item, Exception):
                    raise item
                yield item
        finally:
            stop.set()


def default_batchify_fn(data):
    """Stack samples into a batch (reference: default_mp_batchify_fn)."""
    elem = data[0]
    if isinstance(elem, NDArray):
        return array(_np.stack([d.asnumpy() for d in data]))
    if isinstance(elem, (tuple, list)):
        return tuple(default_batchify_fn([d[i] for d in data])
                     for i in range(len(elem)))
    arr = _np.asarray(data)
    if arr.dtype == _np.float64:
        arr = arr.astype(_np.float32)
    return array(arr)


def _tree_to_numpy(obj):
    """Pickle-friendly transport form for cross-process batches."""
    if isinstance(obj, NDArray):
        return ("__nd__", obj.asnumpy())
    if isinstance(obj, tuple):
        return tuple(_tree_to_numpy(o) for o in obj)
    if isinstance(obj, list):
        return [_tree_to_numpy(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _tree_to_numpy(v) for k, v in obj.items()}
    return obj


def _tree_to_nd(obj):
    if isinstance(obj, tuple):
        if len(obj) == 2 and isinstance(obj[0], str) \
                and obj[0] == "__nd__":
            return array(obj[1])
        return tuple(_tree_to_nd(o) for o in obj)
    if isinstance(obj, list):
        return [_tree_to_nd(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _tree_to_nd(v) for k, v in obj.items()}
    return obj


#: worker-process globals, set once by _process_worker_init
_WORKER_STATE: dict = {}


def _process_worker_init(payload):
    """Spawn-context worker bootstrap. The dataset/batchify arrive as a
    pickle BLOB (not initargs objects) so nothing jax-backed unpickles
    before the platform is pinned: the chip belongs to the parent, a
    chip has one process at a time, and an NDArray materializing in an
    unpinned worker would try to take it and fail or hang."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    dataset, batchify_fn = pickle.loads(payload)
    _WORKER_STATE["dataset"] = dataset
    _WORKER_STATE["batchify"] = batchify_fn


def _process_worker_fn(indices):
    ds = _WORKER_STATE["dataset"]
    bf = _WORKER_STATE["batchify"]
    return _tree_to_numpy(bf([ds[i] for i in indices]))


class DataLoader:
    def __init__(self, dataset, batch_size=None, shuffle=False,
                 sampler=None, last_batch=None, batch_sampler=None,
                 batchify_fn=None, num_workers=0, pin_memory=False,
                 prefetch=None, thread_pool=True, timeout=120,
                 worker_type="thread", seed=None):
        self._dataset = dataset
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("need batch_size or batch_sampler")
            if sampler is None:
                # seed= makes a shuffled epoch sequence replayable
                # (accuracy-gated tests); default stays OS-entropy
                # like upstream
                sampler = RandomSampler(len(dataset), seed=seed) \
                    if shuffle else SequentialSampler(len(dataset))
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        self._batch_sampler = batch_sampler
        self._batchify_fn = batchify_fn or default_batchify_fn
        self._num_workers = num_workers
        self._prefetch = max(2, prefetch or 2 * max(num_workers, 1))
        self._timeout = timeout
        self._pin = pin_memory
        if worker_type not in ("thread", "process"):
            raise ValueError(f"worker_type {worker_type!r}: expected "
                             "'thread' or 'process'")
        self._worker_type = worker_type
        self._pool = None
        self._pool_finalizer = None

    def __len__(self):
        return len(self._batch_sampler)

    def _load_batch(self, indices):
        return self._batchify_fn([self._dataset[i] for i in indices])

    def __iter__(self):
        it = self._iter_impl()
        if self._pin:  # double-buffered device feed
            it = iter(DevicePrefetcher(it))
        return self._timed_iter(it)

    @staticmethod
    def _timed_iter(it):
        """Consumer-facing wrapper: the time the training loop spends
        blocked in next() — after any prefetch overlap — is the step's
        true data-wait, recorded as step_time_breakdown{phase=data}."""
        while True:
            enabled = _tm._ENABLED
            t0 = time.perf_counter() if enabled else 0.0
            try:
                item = next(it)
            except StopIteration:
                return
            if enabled:
                _tm.mark_phase("data", time.perf_counter() - t0, t0=t0)
            yield item

    # -- process workers (reference: the fork's multiprocessing.Pool) ------
    def _get_pool(self):
        if self._pool is None:
            import multiprocessing as mp

            ctx = mp.get_context("spawn")  # fork of a JAX-threaded
            # parent can deadlock in the child (locks held at fork)
            payload = pickle.dumps((self._dataset, self._batchify_fn))
            self._pool = ctx.Pool(self._num_workers,
                                  initializer=_process_worker_init,
                                  initargs=(payload,))
            self._pool_finalizer = weakref.finalize(
                self, DataLoader._shutdown_pool, self._pool)
        return self._pool

    @staticmethod
    def _shutdown_pool(pool):
        try:
            pool.terminate()
            pool.join()
        except Exception:
            pass

    def _iter_process(self):
        import multiprocessing as mp
        from collections import deque

        pool = self._get_pool()
        window = deque()
        it = iter(self._batch_sampler)

        def submit():
            indices = next(it, None)
            if indices is None:
                return False
            window.append(pool.apply_async(_process_worker_fn,
                                           (list(indices),)))
            return True

        for _ in range(self._prefetch):
            if not submit():
                break
        batch_idx = 0
        while window:  # ordered: results yielded in submission order
            res = window.popleft()
            try:
                if _tm._ENABLED:
                    _tm.set_gauge("dataloader_queue_depth",
                                  len(window) + 1)
                    t0 = time.perf_counter()
                    out = res.get(self._timeout)
                    _tm.observe("dataloader_worker_wait_seconds",
                                time.perf_counter() - t0)
                else:
                    out = res.get(self._timeout)  # worker errors
                    #                               re-raise here
            except mp.TimeoutError:
                raise TimeoutError(
                    f"DataLoader process worker timed out after "
                    f"{self._timeout}s waiting for batch {batch_idx} "
                    f"— a stalled/dead worker, or raise `timeout`"
                ) from None
            submit()
            batch_idx += 1
            yield _tree_to_nd(out)

    def _iter_impl(self):
        if self._num_workers == 0:
            for indices in self._batch_sampler:
                yield self._load_batch(indices)
            return
        if self._worker_type == "process":
            yield from self._iter_process()
            return
        # prefetch pipeline scheduled on the native host engine
        # (runtime/cc/engine.cc; Python-thread fallback has the same
        # semantics). Bounded window preserves batch order.
        from collections import deque
        eng = _shared_engine(self._num_workers)
        window = deque()
        it = iter(self._batch_sampler)

        def submit():
            indices = next(it, None)
            if indices is None:
                return False
            ev = threading.Event()
            slot = []

            def work(indices=indices, ev=ev, slot=slot):
                try:
                    slot.append(self._load_batch(indices))
                except Exception as e:  # surface in consumer
                    slot.append(e)
                finally:
                    ev.set()

            eng.push(work)
            window.append((ev, slot))
            return True

        for _ in range(self._prefetch):
            if not submit():
                break
        while window:
            ev, slot = window.popleft()
            if _tm._ENABLED:
                _tm.set_gauge("dataloader_queue_depth", len(window) + 1)
                t0 = time.perf_counter()
                done = ev.wait(self._timeout)
                _tm.observe("dataloader_worker_wait_seconds",
                            time.perf_counter() - t0)
            else:
                done = ev.wait(self._timeout)
            if not done:
                raise TimeoutError("DataLoader worker timed out")
            item = slot[0]
            if isinstance(item, Exception):
                raise item
            submit()
            yield item


_ENGINES = {}


def _shared_engine(num_workers):
    from ...runtime import engine as _engine
    key = num_workers
    if key not in _ENGINES:
        _ENGINES[key] = _engine.create(num_workers)
    return _ENGINES[key]
